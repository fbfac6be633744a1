"""Plain reference for the co-flow scheduler's answers.

Written from the paper's model (arXiv:2008.03497, eqs. 19-30, 39-47)
and imports nothing of the program under test.  It holds a fabric as
plain arrays (`Fabric`), and for one co-flow problem it builds:

  * the edges each flow may use (`admissible_edges`);
  * the routing LP, keyed by row and column names (`build_lp`);
  * the LP's exact optimum (HiGHS, `optimum`);
  * `iters` iterations of diagonally preconditioned PDHG
    (Chambolle-Pock) on that LP from zero (`pdhg`), for the control;
  * the exact accounting of a schedule tensor x[f, e, w, t]: energy,
    completion time, per-flow delivered volume and the worst residual
    of every feasibility family (`account`).

`build_lp`, `pdhg` and `account` take `dtype`: float64 is the
reference; a lower dtype (float32, bfloat16) rounds every value to it
after each operation and gives the control, which the comparison must
refuse.
"""
from __future__ import annotations

import dataclasses
import hashlib

import ml_dtypes
import numpy as np
from scipy import sparse
from scipy.sparse import csgraph

SERVER, SWITCH, PASSIVE = 0, 1, 2
ACTIVE_GBITS = 1e-6      # a device is ON in a slot once it carries more
BF16 = ml_dtypes.bfloat16


@dataclasses.dataclass
class Fabric:
    """A data-centre fabric as plain arrays (units: Gbps, W, s)."""

    kind: np.ndarray            # (V,) SERVER | SWITCH | PASSIVE
    p_max: np.ndarray           # (V,) W while ON in a slot
    eps: np.ndarray             # (V,) W per Gbps of NIC-offloaded traffic
    sigma: np.ndarray           # (V,) switch ingress limit, Gbps (inf: none)
    edges: np.ndarray           # (E, 2) directed (u, v)
    cap: np.ndarray             # (E, W) Gbps per wavelength
    slot_s: float
    server_relay: bool          # False: servers forward nobody's traffic
    one_wavelength_tx: bool     # eq. 47 at AWGR ingress ports
    awgr_in: np.ndarray         # vertex ids of AWGR ingress ports
    task_servers: np.ndarray    # servers that may host map/reduce tasks

    @property
    def n_vertices(self) -> int:
        return len(self.kind)

    @property
    def n_wavelengths(self) -> int:
        return self.cap.shape[1]

    def fingerprint(self) -> str:
        """sha256 over every array and flag that shapes a schedule."""
        h = hashlib.sha256()
        for a in (self.kind.astype(np.int64), self.p_max.astype(np.float64),
                  self.eps.astype(np.float64),
                  self.sigma.astype(np.float64),
                  self.edges.astype(np.int64), self.cap.astype(np.float64),
                  np.sort(self.awgr_in.astype(np.int64)),
                  np.sort(self.task_servers.astype(np.int64)),
                  np.array([self.slot_s, self.server_relay,
                            self.one_wavelength_tx], np.float64)):
            h.update(str(a.shape).encode())
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()


class FabricBuilder:
    """Accumulates devices and directed links for a reference fabric."""

    def __init__(self, n_wavelengths: int):
        self.W = n_wavelengths
        self.kind, self.p_max, self.eps, self.names = [], [], [], []
        self.edges, self.caps = [], []
        self.sigma: dict[int, float] = {}

    def add(self, name: str, kind: int, p_max: float = 0.0,
            eps: float = 0.0) -> int:
        self.names.append(name)
        self.kind.append(kind)
        self.p_max.append(p_max)
        self.eps.append(eps)
        return len(self.kind) - 1

    def arc(self, u: int, v: int, cap_w) -> None:
        self.edges.append((u, v))
        self.caps.append(np.asarray(cap_w, np.float64))

    def link(self, u: int, v: int, cap_w) -> None:
        self.arc(u, v, cap_w)
        self.arc(v, u, cap_w)

    def build(self, *, slot_s: float, server_relay: bool = True,
              one_wavelength_tx: bool = False, awgr_in=()) -> Fabric:
        kind = np.asarray(self.kind, np.int64)
        sigma = np.full(len(kind), np.inf)
        for v, s in self.sigma.items():
            sigma[v] = s
        return Fabric(
            kind=kind, p_max=np.asarray(self.p_max, np.float64),
            eps=np.asarray(self.eps, np.float64), sigma=sigma,
            edges=np.asarray(self.edges, np.int64).reshape(-1, 2),
            cap=np.stack(self.caps).reshape(-1, self.W), slot_s=slot_s,
            server_relay=server_relay, one_wavelength_tx=one_wavelength_tx,
            awgr_in=np.asarray(awgr_in, np.int64),
            task_servers=np.flatnonzero(kind == SERVER))


@dataclasses.dataclass
class Problem:
    """One co-flow on a fabric over `n_slots` slots of `fabric.slot_s`."""

    fabric: Fabric
    src: np.ndarray             # (F,) vertex ids
    dst: np.ndarray
    size: np.ndarray            # (F,) Gbits
    n_slots: int
    rho: float                  # server egress limit, Gbps
    path_slack: int | None      # None: every route; k: shortest + k hops

    @property
    def n_flows(self) -> int:
        return len(self.src)


def round_to(a, dtype):
    """`a` rounded to `dtype` and held in float64 for the next operation."""
    a = np.asarray(a, np.float64)
    return a if dtype == np.float64 else a.astype(dtype).astype(np.float64)


# ---------------------------------------------------------------------------
# admissible routes
# ---------------------------------------------------------------------------

def admissible_edges(p: Problem) -> np.ndarray:
    """(F, E) bool: may flow f use directed edge e?

    A flow never enters its source or leaves its destination; where
    servers do not relay (eq. 46) it touches no other server; with a
    `path_slack` it keeps to edges on src->dst walks at most that many
    hops longer than the shortest one (dead edges are not walkable)."""
    fab = p.fabric
    u, v = fab.edges[:, 0], fab.edges[:, 1]
    ok = (v[None, :] != p.src[:, None]) & (u[None, :] != p.dst[:, None])
    if not fab.server_relay:
        srv = fab.kind == SERVER
        ok &= ~(srv[u][None, :] & (u[None, :] != p.src[:, None]))
        ok &= ~(srv[v][None, :] & (v[None, :] != p.dst[:, None]))
    if p.path_slack is not None:
        alive = fab.cap.sum(axis=1) > 0
        V = fab.n_vertices
        g = sparse.csr_matrix((np.ones(int(alive.sum())),
                               (u[alive], v[alive])), shape=(V, V))
        hops_from = csgraph.shortest_path(g, unweighted=True,
                                          indices=p.src)      # (F, V)
        hops_to = csgraph.shortest_path(g.T.tocsr(), unweighted=True,
                                        indices=p.dst)        # (F, V)
        shortest = hops_from[np.arange(p.n_flows), p.dst]
        ok &= (hops_from[:, u] + 1 + hops_to[:, v]
               <= (shortest + p.path_slack)[:, None])
    return ok


# ---------------------------------------------------------------------------
# routing LP
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LP:
    """min c.x  s.t.  A_eq x = b,  A_ub x <= h,  0 <= x <= xmax.

    Rows and columns carry names, so two LPs that order them differently
    can be compared entry by entry.  Columns: ("x", f, e, w) volume of
    flow f on edge e, wavelength w; ("inj", f, w) volume injected at the
    source on w; ("theta",) the completion time (min-time only).  Rows:
    ("c", f, vertex, w | -1) conservation (per wavelength at passive
    vertices, summed at electronic ones), ("d", f) demand, ("ew", e, w)
    link capacity, ("srv", u) server egress, ("sw", v) switch ingress."""

    cols: list
    eq_rows: list
    ub_rows: list
    A: sparse.csr_matrix        # rows: eq_rows then ub_rows
    b: np.ndarray
    h: np.ndarray
    c: np.ndarray
    xmax: np.ndarray

    @property
    def n_flows(self) -> int:
        return sum(r[0] == "d" for r in self.eq_rows)


def build_lp(p: Problem, objective: str, dtype=np.float64) -> LP:
    """The routing LP of the paper's relaxation over the whole horizon.

    Each admissible (f, e, w) carries volume out of e's tail and into
    e's head; the source injects the demand.  Energy prices a Gbit by
    the NIC offload of the server endpoints plus each powered
    endpoint's p_max over its incident capacity (plus 1e-6 per hop);
    min-time minimizes theta with every rate limit scaled by theta and
    a 1e-6/total per-hop tie-break."""
    fab = p.fabric
    F, W = p.n_flows, fab.n_wavelengths
    horizon = p.n_slots * fab.slot_s
    total = max(float(p.size.sum()), 1e-9)
    passive = fab.kind == PASSIVE
    server = fab.kind == SERVER
    switch = fab.kind == SWITCH
    ok = admissible_edges(p)
    cols, entries = [], []          # entries: (row name, column, value)
    limit = {}                      # ub row name -> rate limit (Gbps)
    for f in range(F):
        src, dst = int(p.src[f]), int(p.dst[f])
        for e in np.flatnonzero(ok[f]):
            u, v = (int(a) for a in fab.edges[e])
            for w in np.flatnonzero(fab.cap[e] > 0):
                j = len(cols)
                cols.append(("x", f, int(e), int(w)))
                entries.append((("c", f, u, int(w) if passive[u] else -1),
                                j, 1.0))
                if v != dst:
                    entries.append(
                        (("c", f, v, int(w) if passive[v] else -1), j, -1.0))
                ew = ("ew", int(e), int(w))
                limit[ew] = float(fab.cap[e, w])
                entries.append((ew, j, 1.0))
                if server[u] and np.isfinite(p.rho):
                    limit[("srv", u)] = p.rho
                    entries.append((("srv", u), j, 1.0))
                if switch[v] and np.isfinite(fab.sigma[v]):
                    limit[("sw", v)] = float(fab.sigma[v])
                    entries.append((("sw", v), j, 1.0))
        for w in range(W):
            j = len(cols)
            cols.append(("inj", f, w))
            entries.append((("c", f, src, -1), j, -1.0))
            entries.append((("d", f), j, 1.0))
    time_obj = objective == "time"
    if time_obj:
        j_theta = len(cols)
        cols.append(("theta",))
        entries += [(r, j_theta, -lim) for r, lim in limit.items()]

    eq_rows = sorted({r for r, _, _ in entries if r[0] in ("c", "d")})
    ub_rows = sorted(limit)
    row_id = {r: i for i, r in enumerate(eq_rows + ub_rows)}
    n = len(cols)
    A = sparse.csr_matrix(
        ([val for _, _, val in entries],
         ([row_id[r] for r, _, _ in entries], [j for _, j, _ in entries])),
        shape=(len(row_id), n))
    b = np.array([p.size[r[1]] if r[0] == "d" else 0.0 for r in eq_rows])
    h = (np.zeros(len(ub_rows)) if time_obj
         else np.array([limit[r] * horizon for r in ub_rows]))

    c = np.zeros(n)
    xmax = np.zeros(n)
    cap_sum = np.zeros(fab.n_vertices)
    np.add.at(cap_sum, fab.edges[:, 0], fab.cap.sum(axis=1))
    np.add.at(cap_sum, fab.edges[:, 1], fab.cap.sum(axis=1))
    per_gbit = np.where(fab.p_max > 0,
                        fab.p_max / np.maximum(cap_sum, 1e-9), 0.0)
    for j, col in enumerate(cols):
        if col[0] == "x":
            _, f, e, w = col
            u, v = fab.edges[e]
            xmax[j] = min(fab.cap[e, w] * horizon, total)
            if time_obj:
                c[j] = 1e-6 / total
            else:
                c[j] = (server[u] * fab.eps[u] + server[v] * fab.eps[v]
                        + per_gbit[u] + per_gbit[v] + 1e-6)
        elif col[0] == "inj":
            xmax[j] = p.size[col[1]]
        else:
            xmax[j] = horizon
            c[j] = 1.0
    A.data = round_to(A.data, dtype)
    return LP(cols=cols, eq_rows=eq_rows, ub_rows=ub_rows, A=A,
              b=round_to(b, dtype), h=round_to(h, dtype),
              c=round_to(c, dtype), xmax=round_to(xmax, dtype))


def pdhg(lp: LP, iters: int, dtype=np.float64) -> np.ndarray:
    """`iters` iterations of diagonally preconditioned PDHG from zero.

    tau_j = 1 / sum_i |A_ij|, sigma_i = 1 / sum_j |A_ij| (Pock and
    Chambolle 2011); c is scaled by max|c| (the minimizer is the same).
        x+ = clip(x - tau (c + A^T y), 0, xmax)
        y+ = y + sigma (A (2 x+ - x) - [b; h]),  y+ >= 0 on the ub rows
    Returns the primal iterate."""
    A = lp.A
    At = A.T.tocsr()
    absA = abs(A)
    tau = 1.0 / np.maximum(np.asarray(absA.sum(axis=0)).ravel(), 1e-12)
    sig = 1.0 / np.maximum(np.asarray(absA.sum(axis=1)).ravel(), 1e-12)
    c = round_to(lp.c / max(np.abs(lp.c).max(initial=0.0), 1e-12), dtype)
    q = np.concatenate([lp.b, lp.h])
    ub = np.arange(A.shape[0]) >= len(lp.eq_rows)
    tau, sig = round_to(tau, dtype), round_to(sig, dtype)
    x = np.zeros(A.shape[1])
    y = np.zeros(A.shape[0])
    for _ in range(iters):
        x_new = round_to(np.clip(x - tau * (c + At @ y), 0.0, lp.xmax),
                         dtype)
        y = y + sig * (A @ (2.0 * x_new - x) - q)
        y = round_to(np.where(ub, np.maximum(y, 0.0), y), dtype)
        x = x_new
    return x


def optimum(lp: LP) -> float:
    """The LP's optimal objective value, solved exactly by HiGHS."""
    from scipy.optimize import linprog

    n_eq = len(lp.eq_rows)
    A = lp.A.tocsr()
    r = linprog(lp.c, A_ub=A[n_eq:], b_ub=lp.h, A_eq=A[:n_eq], b_eq=lp.b,
                bounds=np.c_[np.zeros(len(lp.c)), lp.xmax], method="highs")
    if r.status != 0:
        raise RuntimeError(f"reference LP not solved: {r.message}")
    return float(r.fun)


# ---------------------------------------------------------------------------
# exact accounting of a schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Accounting:
    energy_j: float
    completion_s: float
    served: np.ndarray          # (F,) Gbits delivered
    residuals: dict             # family -> worst residual, Gbits


def account(p: Problem, x: np.ndarray, dtype=np.float64) -> Accounting:
    """Energy (eqs. 19-22), completion time (eqs. 39-45), delivered
    volume and feasibility residuals (eqs. 25-28, 30, 46, 47) of the
    schedule tensor x[f, e, w, t] (Gbits)."""
    fab = p.fabric
    F, E, W, T = x.shape
    D = fab.slot_s
    u, v = fab.edges[:, 0], fab.edges[:, 1]
    V = fab.n_vertices
    server = fab.kind == SERVER
    x = round_to(x, dtype)
    # incidence: out_of[v, e] = 1 iff e leaves v; into[v, e] likewise
    out_of = sparse.csr_matrix((np.ones(E), (u, np.arange(E))), shape=(V, E))
    into = sparse.csr_matrix((np.ones(E), (v, np.arange(E))), shape=(V, E))

    psi = round_to(x.sum(axis=0), dtype)                        # (E, W, T)
    psi_e = psi.sum(axis=1)                                       # (E, T)
    res = {"capacity": float((psi - fab.cap[:, :, None] * D)
                             .max(initial=0.0))}
    egress = out_of @ psi_e
    ingress = into @ psi_e
    res["egress"] = float((egress[server] - p.rho * D).max(initial=0.0))
    lim = np.isfinite(fab.sigma) & (fab.kind == SWITCH)
    res["ingress"] = float((ingress[lim] - fab.sigma[lim, None] * D)
                           .max(initial=0.0))
    ok = admissible_edges(p)
    res["mask"] = float(np.where(ok[:, :, None, None], 0.0, x)
                        .max(initial=0.0))

    # net outflow per (flow, vertex, wavelength, slot)
    xf = x.transpose(1, 0, 2, 3).reshape(E, -1)                   # (E, F*W*T)
    net = round_to(((out_of - into) @ xf).reshape(V, F, W, T), dtype)
    inner = np.ones((V, F), bool)
    inner[p.src, np.arange(F)] = False
    inner[p.dst, np.arange(F)] = False
    passive = fab.kind == PASSIVE
    cons = np.abs(net.sum(axis=2)).max(axis=2)                    # (V, F)
    cons_w = np.abs(net).max(axis=(2, 3))
    res["conservation"] = float(max(
        np.where(inner, cons, 0.0).max(initial=0.0),
        np.where(inner & passive[:, None], cons_w, 0.0).max(initial=0.0)))
    served = round_to(net[p.src, np.arange(F)].sum(axis=(1, 2)), dtype)
    res["demand"] = float(np.abs(served - p.size).max(initial=0.0))
    wav = 0.0
    if fab.one_wavelength_tx and len(fab.awgr_in):
        to_awgr = np.isin(v, fab.awgr_in)
        for s in np.flatnonzero(server):
            sel = (u == s) & to_awgr
            if sel.any():
                used = (psi[sel].sum(axis=0) > ACTIVE_GBITS).sum(axis=0)
                wav = max(wav, float(used.max(initial=0)) - 1.0)
    res["wavelength"] = wav

    # eqs. 19-22: a device is ON in (w, t) once it carries traffic
    beta = round_to((out_of + into) @ psi.reshape(E, -1), dtype).reshape(
        V, W, T)
    on = beta > ACTIVE_GBITS
    energy = D * float((on * fab.p_max[:, None, None]).sum())
    energy += D * float((fab.eps[:, None, None] * beta
                         * server[:, None, None]).sum())
    # eqs. 39-45: the last busy link's in-slot finish time
    busy = psi > ACTIVE_GBITS
    finish = (D * np.arange(T)[None, None, :]
              + psi / np.maximum(fab.cap[:, :, None], 1e-30))
    completion = float(np.where(busy, finish, 0.0).max(initial=0.0))
    return Accounting(energy_j=float(round_to(energy, dtype)),
                      completion_s=float(round_to(completion, dtype)),
                      served=served, residuals=res)
