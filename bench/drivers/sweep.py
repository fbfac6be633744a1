"""The "sweep" mix: batches of fresh co-flows, each scheduled and certified.

A step draws one co-flow from every template of the mix's deck
(`traffic.shuffle`, key (seed, 1, step, i), in an order of the key's),
builds their problems, solves them in one `solver.solve_fast_batch` call
under the mix's objective, and certifies every schedule with
`verify.check_schedule`.  A schedule the certificate refuses (the slot
packer could not finish inside the horizon) is solved once more, alone,
over twice the horizon (`timeslot.rehorizon`), as the program's sweep
retries it; one refused again counts as failed.  Every step meets the
same LP shapes, so the warm-up compiles all that the window runs: one
step on co-flows of key (seed, 0, 0, i) and a retry of one co-flow of
each template, all with the tolerance at infinity.

The check (`check`) compares every schedule of the window with the
plain reference:

  unanswered    problems of the window the program returned no schedule
                for (or schedules it returned for no problem);
  lp_gap        the LP handed to PDHG against the reference LP, entry by
                entry under row and column names (worst |a - r| / |r| over
                A, b, h, c, xmax); a missing or extra entry reads 1;
  pdhg_obj_gap  the objective of the program's PDHG solution against the
                LP's exact optimum (HiGHS), |c.x - c.x*| / |c.x*|;
  metric_gap    the reported energy, completion time and per-flow
                delivered volume against the reference accounting of the
                same schedule (worst relative gap);
  cert_gbits    the reference's worst feasibility residual of a schedule
                the program certified (Gbits); one it refused is no
                claim, and counts as failed instead.

`control` reads the same numbers with each layer's output replaced by
the reference computed one precision below the configuration's.
"""
from __future__ import annotations

import time

import numpy as np

import reference as ref
import traffic

# the precision one below each that a configuration may state for a layer
LOWER = {"float64": np.float32, "float32": ref.BF16}
# the numbers the check compares, each against limits/<cell>.json
NUMBERS = ("unanswered", "lp_gap", "pdhg_obj_gap", "metric_gap",
           "cert_gbits")


class Sweep:
    def __init__(self, cfg: dict, mix: dict, fabric: ref.Fabric, topo,
                 seed: int):
        from repro.core import solver, timeslot, traffic as ptraffic, verify

        self.solver, self.timeslot, self.verify = solver, timeslot, verify
        self.ptraffic = ptraffic
        self.cfg, self.mix, self.fabric = cfg, mix, fabric
        self.topo, self.seed = topo, seed
        self.objective = mix["objective"]
        self.levels = mix["levels"]
        # (template number, template): one entry per co-flow of a step
        self.deck = [(j, e) for j, e in enumerate(mix["deck"])
                     for _ in range(e["count"])]
        sched = cfg["schedule"]
        self.rho, self.path_slack = sched["rho_gbps"], sched["path_slack"]

    # -- traffic ------------------------------------------------------------
    def order(self, *key: int) -> list:
        """The deck's (template number, template) in a step's order."""
        perm = traffic.rng(self.seed, *key, -1).permutation(len(self.deck))
        return [self.deck[j] for j in perm]

    def coflows(self, *key: int) -> list:
        """The step's co-flows, one drawn from each entry of the deck."""
        return [traffic.shuffle(self.fabric, self.cfg["shuffle"], self.levels,
                                template, traffic.rng(self.seed, *key, i))
                for i, (_, template) in enumerate(self.order(*key))]

    def problems(self, *key: int):
        """[(program problem, reference problem)] for one step."""
        out = []
        sched = self.cfg["schedule"]
        for cf in self.coflows(*key):
            T = traffic.n_slots(self.fabric, cf, self.rho,
                                sched["horizon_slack"], sched["horizon_extra"])
            pcf = self.ptraffic.custom_coflow(cf.src, cf.dst, cf.size,
                                              self.topo.n_vertices)
            p = self.timeslot.ScheduleProblem(self.topo, pcf, n_slots=T,
                                              rho=self.rho,
                                              path_slack=self.path_slack)
            out.append((p, ref.Problem(self.fabric, cf.src, cf.dst, cf.size,
                                       T, self.rho, self.path_slack)))
        return out

    def certified(self, p, r, spans) -> bool:
        with spans.span("certify"):
            return self.verify.check_schedule(p, r.schedule).ok

    def solve(self, problems, tol: float):
        return list(self.solver.solve_fast_batch(
            problems, self.objective, iters=self.cfg["solver"]["iters"],
            tol=tol))

    def retry(self, p, rp, tol: float):
        """[(problem, reference problem, result)] over twice the horizon;
        empty where the program returned no result."""
        T = 2 * p.n_slots
        p = self.timeslot.rehorizon(p, T)
        rp = ref.Problem(rp.fabric, rp.src, rp.dst, rp.size, T, rp.rho,
                         rp.path_slack)
        return [(p, rp, r) for r in self.solve([p], tol)[:1]]

    def step(self, pairs, tol: float, spans) -> tuple[list, int]:
        """[(problem, reference problem, result, certified)] of one step,
        and how many problems went unanswered."""
        results = self.solve([p for p, _ in pairs], tol)
        out, missing = [], abs(len(results) - len(pairs))
        for (p, rp), r in zip(pairs, results):
            ok = self.certified(p, r, spans)
            if not ok:
                again = self.retry(p, rp, tol)
                missing += not again
                for p, rp, r in again:
                    ok = self.certified(p, r, spans)
            out.append((p, rp, r, ok))
        return out, missing

    # -- phases -------------------------------------------------------------
    def warm_up(self) -> None:
        """Compiles what a step runs: the batch, and a retry alone of one
        co-flow of each template (their LPs differ in shape)."""
        pairs = self.problems(0, 0)
        self.solve([p for p, _ in pairs], float("inf"))
        first = {}
        for i, (j, _) in enumerate(self.order(0, 0)):
            first.setdefault(j, i)
        for i in first.values():
            self.retry(*pairs[i], float("inf"))

    def window(self, seconds: float, spans) -> dict:
        """Whole steps until `seconds` have passed; returns observations."""
        tol = self.cfg["solver"]["tol_gbits"]
        done, unanswered = [], 0
        with spans.wrap(self.solver, "build_routing_lp", "lp_build",
                        keep=True):
            t0 = time.perf_counter()
            k = 0
            while True:
                with spans.span("step"):
                    out, missing = self.step(self.problems(1, k), tol, spans)
                done += out
                unanswered += missing
                k += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        lps = {id(args[0]): out for args, out in spans.kept["lp_build"]}
        return {"elapsed_s": elapsed, "steps": k, "unanswered": unanswered,
                "schedules": [(p, rp, r, ok, lps[id(p)])
                              for p, rp, r, ok in done]}

    def end_to_end(self, obs: dict) -> dict:
        good = sum(ok for _, _, _, ok, _ in obs["schedules"])
        return {"schedules_per_s": (good / obs["elapsed_s"], "schedules/s")}

    def counts(self, obs: dict) -> tuple[int, int]:
        sched = obs["schedules"]
        return (len(sched) + obs["unanswered"],
                sum(not ok for _, _, _, ok, _ in sched) + obs["unanswered"])

    def lp_sizes(self, obs: dict) -> list[tuple[int, int, int, int]]:
        """(n, m, nnz, PDHG iterations) of every schedule's LP."""
        return [(lp.n, lp.m, len(lp.val), r.iterations)
                for _, _, r, _, (lp, _) in obs["schedules"]]

    # -- correctness --------------------------------------------------------
    def check(self, obs: dict) -> dict:
        worst = dict.fromkeys(NUMBERS, 0.0)
        for _, rp, r, ok, (lp, idx) in obs["schedules"]:
            rlp = ref.build_lp(rp, self.objective)
            acc = ref.account(rp, r.schedule)
            got = {"lp_gap": lp_gap(lp, idx, rlp),
                   "pdhg_obj_gap": obj_gap(rlp, in_ref_order(r.lp_x, idx, rlp),
                                           ref.optimum(rlp)),
                   "metric_gap": metric_gap(
                       r.metrics.energy_j, r.metrics.completion_s,
                       r.metrics.served, acc, rp.size),
                   "cert_gbits": max(acc.residuals.values()) if ok else 0.0}
            _worst(worst, got)
        worst["unanswered"] = float(obs["unanswered"])
        return worst

    def control(self, obs: dict) -> dict:
        """The same numbers with each layer's output replaced by the
        reference computed one precision below the one the configuration
        states for that layer (`LOWER`): its LP, its PDHG solution after
        as many iterations as the program's, its accounting, and the
        schedule rounded to that precision."""
        low = {k: LOWER[v] for k, v in self.cfg["precision"].items()}
        worst = dict.fromkeys(NUMBERS, 0.0)
        for _, rp, r, _, _ in obs["schedules"]:
            rlp = ref.build_lp(rp, self.objective)
            x_low = ref.pdhg(rlp, r.iterations, dtype=low["pdhg"])
            acc = ref.account(rp, r.schedule)
            acc_low = ref.account(rp, r.schedule, dtype=low["accounting"])
            sched_low = ref.round_to(r.schedule, low["schedule"])
            got = {"lp_gap": lp_arrays_gap(
                       ref.build_lp(rp, self.objective, dtype=low["lp"]),
                       rlp),
                   "pdhg_obj_gap": obj_gap(rlp, x_low, ref.optimum(rlp)),
                   "metric_gap": metric_gap(
                       acc_low.energy_j, acc_low.completion_s,
                       acc_low.served, acc, rp.size),
                   "cert_gbits": max(ref.account(rp, sched_low)
                                     .residuals.values())}
            _worst(worst, got)
        worst["unanswered"] = 0.0
        return worst


def _worst(worst: dict, got: dict) -> None:
    """Fold one schedule's numbers into the worst so far (a number that
    is not finite reads 1)."""
    for k, v in got.items():
        worst[k] = max(worst[k], v if np.isfinite(v) else 1.0)


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------

def _col_names(idx, n: int, n_flows: int) -> list:
    """Names of the program's LP columns, in its order (see ref.LP)."""
    W = idx.n_inj // max(n_flows, 1)
    names = [("x", int(f), int(e), int(w))
             for f, e, w in zip(idx.kf, idx.ke, idx.kw)]
    names += [("inj", j // W, j % W) for j in range(idx.n_inj)]
    names += [("theta",)] * idx.n_theta
    assert len(names) == n, (len(names), n)
    return names


def _row_names(idx) -> list:
    return [_canon(k) for k in list(idx.eq_keys) + list(idx.ub_keys)]


def _canon(key) -> tuple:
    return tuple(int(k) if not isinstance(k, str) else k for k in key)


def obj_gap(lp: ref.LP, x: np.ndarray, opt: float) -> float:
    """|c.x - opt| / |opt| (1.0 where x has a missing column)."""
    return float(abs(lp.c @ x - opt) / max(abs(opt), 1e-300))


def in_ref_order(x: np.ndarray, idx, rlp: ref.LP) -> np.ndarray:
    """The program's LP vector `x` reordered to the reference's columns
    (nan where a reference column is missing)."""
    names = _col_names(idx, len(x), rlp.n_flows)
    pos = {name: j for j, name in enumerate(names)}
    return np.array([x[pos[c]] if c in pos else np.nan for c in rlp.cols])


def lp_gap(lp, idx, rlp: ref.LP) -> float:
    """Worst entry-wise relative gap between the program's LP and the
    reference's, matched by row and column names; 1.0 when the names
    differ."""
    cols = _col_names(idx, lp.n, rlp.n_flows)
    rows = _row_names(idx)
    if sorted(cols) != sorted(rlp.cols) or \
            sorted(rows) != sorted(rlp.eq_rows + rlp.ub_rows) or \
            len(idx.eq_keys) != len(rlp.eq_rows):
        return 1.0
    col_pos = {c: j for j, c in enumerate(rlp.cols)}
    row_pos = {r: i for i, r in enumerate(rlp.eq_rows + rlp.ub_rows)}
    cmap = np.array([col_pos[c] for c in cols])
    rmap = np.array([row_pos[r] for r in rows])
    from scipy import sparse

    A = sparse.csr_matrix((lp.val, (rmap[lp.row], cmap[lp.col])),
                          shape=rlp.A.shape)
    A.sum_duplicates()
    mine = ref.LP(cols=rlp.cols, eq_rows=rlp.eq_rows, ub_rows=rlp.ub_rows,
                  A=A, b=np.empty(0), h=np.empty(0), c=np.empty(0),
                  xmax=np.empty(0))
    q = np.concatenate([lp.b, lp.h])
    q_ref = np.empty(len(rows))
    q_ref[rmap] = q
    n_eq = len(rlp.eq_rows)
    mine.b, mine.h = q_ref[:n_eq], q_ref[n_eq:]
    for name, arr in (("c", lp.c), ("xmax", lp.xmax)):
        v = np.empty(lp.n)
        v[cmap] = arr
        setattr(mine, name, v)
    return lp_arrays_gap(mine, rlp)


def entry_gap(got, want) -> float:
    """Worst entry-wise |got - want| / |want| (0 where both are equal;
    1.0 where the shapes differ)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return 1.0
    d = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        g = np.where(d == 0, 0.0, d / np.abs(want))
    return float(np.nan_to_num(g, nan=1.0, posinf=1.0).max(initial=0.0))


def lp_arrays_gap(a: ref.LP, r: ref.LP) -> float:
    """Worst entry-wise relative gap between two LPs whose rows and
    columns have the same names in the same order; 1.0 where their
    nonzero patterns differ."""
    A, R = a.A.tocsr(), r.A.tocsr()
    for M in (A, R):
        M.sum_duplicates()
        M.eliminate_zeros()
        M.sort_indices()
    if A.shape != R.shape or not (np.array_equal(A.indptr, R.indptr)
                                  and np.array_equal(A.indices, R.indices)):
        return 1.0
    return max([entry_gap(A.data, R.data)]
               + [entry_gap(getattr(a, k), getattr(r, k))
                  for k in ("b", "h", "c", "xmax")])


def metric_gap(energy: float, completion: float, served: np.ndarray,
               acc: ref.Accounting, size: np.ndarray) -> float:
    """Worst relative gap of reported energy, completion time and
    per-flow delivered volume against the reference accounting."""
    gaps = [abs(energy - acc.energy_j) / max(abs(acc.energy_j), 1e-300),
            abs(completion - acc.completion_s)
            / max(abs(acc.completion_s), 1e-300),
            float((np.abs(np.asarray(served) - acc.served)
                   / np.maximum(size, 1e-300)).max(initial=0.0))]
    return float(max(gaps))
