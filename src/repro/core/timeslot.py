"""Time-slotted co-flow scheduling problem + exact paper accounting.

This module defines the schedule decision tensors and evaluates any
candidate schedule with the paper's exact equations:

  * device activity / power:   eqs. (19)-(21)
  * total energy:              eq. (22)
  * completion time M:         eqs. (39)-(45)
  * feasibility:               eqs. (25)-(30), (46), (47)

Each accounting call reads the schedule's nonzero entries once
(`schedule_coo`) and sums by bincount over them; the feasibility
residuals are written once (`residuals`) for `evaluate` and
`core.verify.check_schedule`.

A schedule is a pair of tensors
    x[f, e, w, t]  - Gbits of flow f carried on directed edge e,
                     wavelength w, during slot t
    (delta[f, t] = net injection is implied: sum of x out of src_f)
so both solvers (core.oracle exact MILP, core.solver JAX fast
path) and any heuristic can be scored identically.
"""
from __future__ import annotations

import copy
import dataclasses

import numpy as np
from scipy import sparse
from scipy.sparse import csgraph

from .. import trace
from .topology import KIND_SERVER, KIND_SWITCH, Topology
from .traffic import CoflowSet

TOL = 1e-6


@dataclasses.dataclass
class ScheduleProblem:
    """One co-flow scheduling instance: topology + demand + horizon.

    Units (paper Tables II-III): flow sizes and every schedule-tensor
    entry are **Gbits**; link capacities, `rho`, and `sigma` are **Gbps**
    (not GB/s — 1 Gbit = 0.125 GB); `slot_duration` is seconds, so a
    slot ships at most `cap * D` Gbits per (edge, wavelength).

    Construction is deterministic and side-effect free: `__post_init__`
    derives index arrays (`e_src`/`e_dst`, (F, E) `flow_edge_mask`,
    (E, W) `edge_w_ok`) from the topology alone — two problems built
    from equal inputs are interchangeable, which is what lets the sweep
    rebuild problems freely during its retry ladder."""

    topo: Topology
    coflow: CoflowSet
    n_slots: int                  # |T|
    rho: float = 8.0              # max egress rate per server, Gbps (Table III)
    q_weight: float = 100.0       # Q, earliest-slot fairness weight (Table III)
    # beyond-paper extension (TPU gradient buckets): flow f may not ship
    # before slot release_slot[f] (0-based).  None = all ready at t=0, which
    # is the paper's assumption for the shuffle phase.
    release_slot: np.ndarray | None = None
    # route pruning for sweep-scale solves: keep only edges on paths at most
    # `path_slack` hops longer than each flow's shortest route.  None keeps
    # the paper's full route space (any edge not touching src/dst wrongly).
    path_slack: int | None = None
    # weighted max-min fairness extension (arXiv 1904.03298 lineage): per-flow
    # positive weights for the "fair" LP objective — a flow's transport is
    # priced inversely to its weight, so heavier tenants get cheaper (hence
    # more) service.  None = uniform, which makes "fair" coincide with the
    # plain energy objective (pinned by tests/test_properties.py).
    flow_weight: np.ndarray | None = None

    def __post_init__(self):
        t = self.topo
        self.e_src = t.edges[:, 0].astype(np.int64)
        self.e_dst = t.edges[:, 1].astype(np.int64)
        self.is_server = np.array([d.kind == KIND_SERVER for d in t.devices])
        self.is_switch = np.array([d.kind == KIND_SWITCH for d in t.devices])
        self.p_max = np.array([d.p_max for d in t.devices])
        self.eps = np.array([d.eps for d in t.devices])
        self.sigma = np.array([t.switch_sigma.get(i, np.inf)
                               for i in range(t.n_vertices)])
        with trace.span("problem.mask"):
            self.flow_edge_mask = self._flow_edge_mask()
        # wavelength availability per edge
        self.edge_w_ok = t.cap > 0.0            # (E, W)
        if self.flow_weight is not None:
            w = np.asarray(self.flow_weight, dtype=np.float64)
            F = self.coflow.n_flows
            assert w.shape == (F,), (w.shape, F)
            assert np.isfinite(w).all() and (w > 0).all(), \
                "flow_weight entries must be positive and finite"
            self.flow_weight = w

    def _flow_edge_mask(self) -> np.ndarray:
        """(F, E) bool: 1 = flow f may use edge e."""
        t = self.topo
        F = self.coflow.n_flows
        src, dst = self.coflow.src, self.coflow.dst
        # never re-enter the source / leave the destination
        mask = ~(self.e_dst[None, :] == src[:, None])
        mask &= ~(self.e_src[None, :] == dst[:, None])
        if not t.server_relay:
            # eq. (46): servers never forward other servers' traffic (PON3);
            # where they do (BCube/DCell/PON5) nothing more is masked
            u_is_server = self.is_server[self.e_src]
            v_is_server = self.is_server[self.e_dst]
            mask &= ~(u_is_server[None, :] & (self.e_src[None, :] != src[:, None]))
            mask &= ~(v_is_server[None, :] & (self.e_dst[None, :] != dst[:, None]))
        if self.path_slack is not None:
            # edge (u, v) stays admissible for flow f iff it lies on some
            # src->dst walk within path_slack hops of the shortest one; a
            # flow with no route left (a failure stranded it) admits none,
            # since inf <= inf would admit every edge
            from_src = hop_rows(t, src)                       # (F, V)
            to_dst = hop_rows(t, dst, to=True)                # (F, V)
            through = from_src[:, self.e_src] + 1 + to_dst[:, self.e_dst]
            shortest = from_src[np.arange(F), dst]
            mask &= through <= (shortest + self.path_slack)[:, None]
            mask &= np.isfinite(shortest)[:, None]
        return mask

    # -- convenience sizes --------------------------------------------------
    @property
    def shape_x(self) -> tuple[int, int, int, int]:
        return (self.coflow.n_flows, self.topo.n_edges,
                self.topo.n_wavelengths, self.n_slots)

    @property
    def slot_cap_gbits(self) -> np.ndarray:
        """(E, W) capacity in Gbits per slot: C_uvw * D (eq. 28)."""
        return self.topo.cap * self.topo.slot_duration


_KEEP = object()          # rehorizon sentinel: "leave path_slack alone"


def rehorizon(p: ScheduleProblem, n_slots: int, *,
              path_slack=_KEEP) -> ScheduleProblem:
    """Copy of `p` with a new horizon, skipping the derived-array rebuild.

    None of __post_init__'s products (edge endpoints, flow_edge_mask,
    edge_w_ok, device kind/power arrays) depend on n_slots, so when the
    route-pruning setting is unchanged the copy shares them with `p` —
    this is what the horizon-doubling retry ladders (sweep/runner.py,
    core.arrivals) call instead of re-deriving everything per retry.
    Passing a different `path_slack` (e.g. None to drop pruning) falls
    back to full construction, since the mask genuinely changes."""
    if path_slack is not _KEEP and path_slack != p.path_slack:
        return ScheduleProblem(p.topo, p.coflow, n_slots=n_slots,
                               rho=p.rho, q_weight=p.q_weight,
                               release_slot=p.release_slot,
                               path_slack=path_slack,
                               flow_weight=p.flow_weight)
    q = copy.copy(p)          # shallow: derived arrays are shared
    q.n_slots = n_slots
    return q


@dataclasses.dataclass
class Metrics:
    energy_j: float
    completion_s: float
    fairness_term: float          # Q * sum_t t*delta_{f,t}
    feasible: bool
    max_violation: float
    psi: np.ndarray               # (E, W, T) total per-link traffic, Gbits
    active_devices: np.ndarray    # (V, W, T) bool
    served: np.ndarray            # (F,) Gbits delivered

    def objective(self, kind: str) -> float:
        # "fair" is a weighted re-pricing of the energy LP (core.solver),
        # so its exact-accounting base is energy too
        base = (self.completion_s if kind == "time" else self.energy_j)
        return base + self.fairness_term


def hop_rows(topo: Topology, vertices, *, to: bool = False) -> np.ndarray:
    """(len(vertices), V) directed hop counts from each of `vertices`
    (with `to`, from every vertex to each of them); inf where there is
    no path.  Dead edges (all-zero capacity, e.g. cut by core.failures)
    are not traversable.  Rows are found by BFS (scipy.sparse.csgraph)
    on first use and memoized per vertex on the topology instance, since
    sweeps build hundreds of ScheduleProblems over the same graphs and a
    problem reads only its endpoints' rows."""
    cache = getattr(topo, "_hop_rows_cache", None)
    if cache is None:
        alive = topo.cap.sum(axis=1) > 0.0
        u = topo.edges[alive, 0]
        v = topo.edges[alive, 1]
        shape = (topo.n_vertices,) * 2
        ones = np.ones(len(u))
        cache = topo._hop_rows_cache = {
            False: (sparse.csr_matrix((ones, (u, v)), shape=shape), {}),
            True: (sparse.csr_matrix((ones, (v, u)), shape=shape), {})}
    graph, rows = cache[to]
    vertices = np.asarray(vertices, dtype=np.int64).tolist()
    missing = sorted(set(vertices).difference(rows))
    if missing:
        with trace.span("problem.hops"):
            rows.update(zip(missing, csgraph.shortest_path(
                graph, unweighted=True, indices=missing)))
    if not vertices:
        return np.empty((0, topo.n_vertices))
    return np.stack([rows[x] for x in vertices])


def suggest_n_slots(topo: Topology, coflow: CoflowSet, *, rho: float = 8.0,
                    slack: float = 2.0, extra: int = 2) -> int:
    """Horizon heuristic for sweep-scale problems: a continuous-time lower
    bound on the shuffle makespan (max over vertices of offered Gbits
    divided by the tighter of the egress-rate cap rho and the incident
    per-wavelength link capacity), stretched by `slack` to give the greedy
    slot packer headroom, plus `extra` slots."""
    out_g = np.zeros(topo.n_vertices)
    in_g = np.zeros(topo.n_vertices)
    np.add.at(out_g, coflow.src, coflow.size)
    np.add.at(in_g, coflow.dst, coflow.size)
    cap_out = np.zeros(topo.n_vertices)
    cap_in = np.zeros(topo.n_vertices)
    per_edge = topo.cap.sum(axis=1)
    np.add.at(cap_out, topo.edges[:, 0], per_edge)
    np.add.at(cap_in, topo.edges[:, 1], per_edge)
    rate_out = np.minimum(np.maximum(cap_out, 1e-9), rho)
    rate_in = np.maximum(cap_in, 1e-9)
    t_lb = max(float((out_g / rate_out).max(initial=0.0)),
               float((in_g / rate_in).max(initial=0.0)))
    return max(int(np.ceil(slack * t_lb / topo.slot_duration)) + extra, 2)


@dataclasses.dataclass
class AccountingStats:
    """Counters for the exact accounting (`evaluate` and
    `verify.check_schedule`): `calls`, the schedule `entries` those
    calls read (each tensor's nonzeros) and the dense tensor `cells`
    they cover; entries / cells is the schedules' nonzero share.  Read
    via `accounting_stats()`; `python -m repro.sweep --profile` prints
    per-cell deltas."""

    calls: int = 0
    entries: int = 0
    cells: int = 0

    def snapshot(self) -> "AccountingStats":
        return dataclasses.replace(self)


ACCOUNTING_STATS = AccountingStats()


def accounting_stats() -> AccountingStats:
    """The live accounting counters (see AccountingStats)."""
    return ACCOUNTING_STATS


def _bincount(keys: np.ndarray, weights: np.ndarray, n: int) -> np.ndarray:
    """(n,) float64 sums of `weights` by key (np.bincount returns int64
    where `keys` is empty)."""
    return np.bincount(keys, weights=weights, minlength=n).astype(
        np.float64, copy=False)


def _sum_by(index: np.ndarray, rows: np.ndarray, n: int) -> np.ndarray:
    """(n, *rows.shape[1:]): out[j] sums the rows k of `rows` with
    index[k] == j (np.add.at's result, as one bincount)."""
    tail = rows.shape[1:]
    width = int(np.prod(tail, dtype=np.int64))
    keys = (index[:, None] * width + np.arange(width)).reshape(-1)
    return _bincount(keys, rows.reshape(-1), n * width).reshape((n,) + tail)


@dataclasses.dataclass(frozen=True)
class ScheduleCoo:
    """A schedule tensor read once: the flow, edge and slot of each
    nonzero entry and its Gbits `v`, with the two sums every accounting
    family starts from, both bincounts over those entries: `psi`
    (E, W, T), eq. (29), and `net` (F, V, W, T), each flow's outflow
    less inflow at every vertex."""

    f: np.ndarray
    e: np.ndarray
    t: np.ndarray
    v: np.ndarray
    psi: np.ndarray
    net: np.ndarray


def schedule_coo(p: ScheduleProblem, x: np.ndarray) -> ScheduleCoo:
    """Read the nonzero entries of `x` (F, E, W, T).  A zero adds
    nothing to a sum and every residual is a max from 0, so each reads
    over the entries as over the dense tensor, NaN and inf included.
    One vectorised scan finds the entries; the sums after it work on
    them alone."""
    assert x.shape == p.shape_x, (x.shape, p.shape_x)
    F, E, W, T = x.shape
    V = p.topo.n_vertices
    flat = x.reshape(-1)
    i = np.flatnonzero(flat != 0)
    v = flat[i].astype(np.float64, copy=False)
    f, e, w, t = np.unravel_index(i, x.shape)
    stats = ACCOUNTING_STATS
    stats.calls += 1
    stats.entries += int(i.size)
    stats.cells += int(x.size)
    psi = _bincount((e * W + w) * T + t, v, E * W * T)
    ends = np.concatenate([p.e_src[e], p.e_dst[e]])
    key = ((np.concatenate([f, f]) * V + ends) * W
           + np.concatenate([w, w])) * T + np.concatenate([t, t])
    net = _bincount(key, np.concatenate([v, -v]), F * V * W * T)
    return ScheduleCoo(f, e, t, v, psi.reshape(E, W, T),
                       net.reshape(F, V, W, T))


def _peak(values) -> float:
    """The largest of `values`, at least 0; NaN if any is NaN."""
    return float(np.max(values, initial=0.0))


def _delta(p: ScheduleProblem, c: ScheduleCoo) -> np.ndarray:
    """delta[f, t] = net injection at the source of flow f in slot t."""
    return c.net[np.arange(c.net.shape[0]), p.coflow.src].sum(axis=1)


FAMILIES = ("capacity", "egress", "ingress", "mask", "conservation",
            "demand", "release", "wavelength")


def residuals(p: ScheduleProblem, c: ScheduleCoo) -> dict[str, float]:
    """Worst residual of each constraint family (FAMILIES), in Gbits;
    the single copy that both `evaluate` (their max) and
    `core.verify.check_schedule` (each one) report."""
    F, V, W, T = c.net.shape
    D = p.topo.slot_duration
    flows = np.arange(F)
    res: dict[str, float] = {}
    # eq. (28): psi <= C*D   (W entries with zero capacity must carry nothing)
    res["capacity"] = _peak(c.psi - p.slot_cap_gbits[:, :, None])
    # eq. (26): server egress <= rho*D
    load = c.psi.sum(axis=1)                                   # (E, T)
    egress = _sum_by(p.e_src, load, V)
    res["egress"] = _peak(egress[p.is_server] - p.rho * D)
    # eq. (27): switch ingress <= sigma*D
    ingress = _sum_by(p.e_dst, load, V)
    sw = p.is_switch & np.isfinite(p.sigma)
    res["ingress"] = _peak(ingress[sw] - p.sigma[sw, None] * D)
    # flow-edge mask (eq. 46 et al.): entries on inadmissible (f, e)
    res["mask"] = _peak(c.v[~p.flow_edge_mask[c.f, c.e]])
    # eq. (25): conservation at intermediate vertices.  Passive vertices
    # (AWGR ports) conserve per wavelength (no O/E conversion); electronic
    # vertices (switches/OLT/backplanes/relay servers) may convert, so they
    # conserve the wavelength-summed flow.
    inter = np.ones((F, V), dtype=bool)
    inter[flows, p.coflow.src] = inter[flows, p.coflow.dst] = False
    passive = ~(p.is_server | p.is_switch)
    res["conservation"] = _peak(
        [_peak(np.abs(c.net[:, passive][inter[:, passive]])),
         _peak(np.abs(np.where(inter[:, :, None], c.net.sum(axis=2), 0.0)))])
    # eq. (30): demand satisfaction (report shortfall as violation)
    served = _delta(p, c).sum(axis=1)
    res["demand"] = _peak(np.abs(served - p.coflow.size))
    # release times (extension): no traffic before a flow's release slot
    res["release"] = 0.0
    if p.release_slot is not None:
        release = np.asarray(p.release_slot).astype(np.int64)
        res["release"] = _peak(c.v[c.t < release[c.f]])
    # eq. (47): one TX wavelength per server per slot (PON3)
    res["wavelength"] = 0.0
    if p.topo.one_wavelength_tx and p.topo.awgr_in_ports:
        tx = np.flatnonzero(p.is_server[p.e_src]
                            & np.isin(p.e_dst, p.topo.awgr_in_ports))
        n_w_used = (_sum_by(p.e_src[tx], c.psi[tx], V) > TOL).sum(axis=1)
        res["wavelength"] = _peak(n_w_used - 1)               # (V, T)
    return res


def _activity_energy(p: ScheduleProblem, psi: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, float]:
    """Device activity + energy (eqs. 19-22) of an (E, W, T') traffic
    tensor: per-vertex carried traffic beta, the ON mask, and total
    Joules.  Single source of truth for evaluate (T' = full horizon)
    and prefix_energy (T' = an executed epoch prefix)."""
    D = p.topo.slot_duration
    V = p.topo.n_vertices
    beta = _sum_by(p.e_src, psi, V) + _sum_by(p.e_dst, psi, V)
    active = beta > TOL
    energy = D * float((active * p.p_max[:, None, None]).sum())
    energy += D * float((p.eps[:, None, None] * beta
                         * p.is_server[:, None, None]).sum())
    return beta, active, energy


def prefix_energy(p: ScheduleProblem, x: np.ndarray, t_end: int) -> float:
    """Exact eq. (19)-(22) energy of the first `t_end` slots of x —
    evaluate()'s accounting applied to a schedule prefix (the online
    arrival engine re-plans the suffix, so only executed slots may burn
    Joules)."""
    return _activity_energy(p, x[:, :, :, :t_end].sum(axis=0))[2]


@trace.spanned("pack.evaluate")
def evaluate(p: ScheduleProblem, x: np.ndarray) -> Metrics:
    """Exact accounting of a schedule tensor with the paper's equations.

    `x` has shape (F, E, W, T) in Gbits; returns energy in Joules
    (eqs. 19-22), completion time in seconds (eqs. 39-45), and the worst
    constraint violation in Gbits (feasible iff <= 1e-4; NaN, and so
    infeasible, if any entry is NaN).  Pure numpy, deterministic, and
    platform-independent — this is the single source of truth both
    solvers and all sweeps report through."""
    c = schedule_coo(p, x)
    viol = _peak(list(residuals(p, c).values()))
    T = p.n_slots
    D = p.topo.slot_duration
    psi = c.psi

    # device activity (eqs. 31-38) and power (eqs. 19-21)
    beta, active, energy = _activity_energy(p, psi)       # eq. (22)

    # completion time M (eqs. 39-45): last active link's in-slot finish time
    with np.errstate(divide="ignore", invalid="ignore"):
        tx_time = np.where(psi > TOL,
                           psi / np.maximum(p.topo.cap[:, :, None], 1e-30), 0.0)
    t_idx = np.arange(1, T + 1)[None, None, :]
    omega = np.where(psi > TOL, D * (t_idx - 1) + tx_time, 0.0)   # eq. (39)
    completion = float(omega.max(initial=0.0))                    # eqs. (43-45)

    delta = _delta(p, c)                                  # eq. (30)
    fairness = p.q_weight * float((delta * t_idx[0, 0][None, :]).sum())
    return Metrics(energy_j=energy, completion_s=completion,
                   fairness_term=fairness, feasible=viol <= 1e-4,
                   max_violation=viol, psi=psi,
                   active_devices=active, served=delta.sum(axis=1))
