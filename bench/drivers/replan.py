"""The "replan" mix: every healthy plan and its single-failure re-plans.

A step is first the sweep's (drivers/sweep.py): one co-flow drawn from
every template of the deck, one `solver.solve_fast_batch` call, the
certificate and its horizon retry.  Then, for every healthy plan, it
fails one element of each class the mix's "failures" names, one at a
time (N-1), degrades the plan's problem for each with the program's
`failures.degrade_problem` (the healthy horizon kept), re-plans all of
them in one `solver.solve_fast_ensemble` call, each member warm-started
from its healthy result, and certifies each re-plan.  A refused re-plan
is solved once more, alone and warm, over twice the horizon, as the
sweep retries; one refused again counts as failed.  Healthy plans and
re-plans alike count as schedules.

The classes of a 3-tier Clos fabric (edge, aggregation and core
switches, told apart by the reference fabric's own adjacency):

  edge-agg-link  an uplink of the edge switch of the co-flow's map task 0;
  agg-core-link  a link from an aggregation switch of map task 0's pod
                 to a core switch;
  agg-switch     an aggregation switch of reduce task 0's pod;
  core-switch    a core switch.

Within its class the driver fails the element the healthy schedule
loads most (Gbits summed over flows, wavelengths and slots; a link's
two arcs together, a switch's arcs into it; the lowest index on a tie).
At `path_slack` 0 the elements of a class are one orbit of the fabric's
automorphisms that fix every server, so each draw of a template meets
the same LP shapes whichever element the schedule loads most.

The reference problem of a re-plan, built after the window, is
`reference.Problem` over a copy of the reference fabric whose capacity
rows the driver zeroes itself: the failed link's two arcs, or every arc
at the failed switch.  A flow the reference can no longer route gets
demand 0 there; the check adds `dropped_gbits`, the demand the program
zeroed on flows the reference still routes (the worst schedule's).

The window runs inside `repro.trace.recording()` and puts the seconds of
the program's spans, by name, in `obs["program_spans"]`; the driver's
own phases are the bench spans `degrade` (choosing the elements and
degrading the problems) and `project` (`solver.project_warm_start`).
`obs["replans"]` counts the degraded problems, and `obs["ensemble"]`
holds the window's change in `solver.ensemble_stats()` where the
program has those counters.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np
from scipy import sparse
from scipy.sparse import csgraph

import reference as ref
from drivers.sweep import Sweep
from spans import Spans

CLASSES = ("edge-agg-link", "agg-core-link", "agg-switch", "core-switch")


class Failure(NamedTuple):
    name: str
    arcs: tuple[int, ...]       # directed edge rows the failure zeroes
    device: int | None          # the failed switch, None for a link


class Pending(NamedTuple):
    """A re-plan's reference problem, built at check time (`resolved`)
    so that the reference's own work stays out of the window."""
    healthy: ref.Problem
    fail: Failure


class Replan(Sweep):
    def __init__(self, cfg: dict, mix: dict, fabric: ref.Fabric, topo,
                 seed: int):
        super().__init__(cfg, mix, fabric, topo, seed)
        from repro.core import failures

        self.pfailures = failures
        self.classes = list(mix["failures"])
        unknown = set(self.classes) - set(CLASSES)
        if unknown:
            raise ValueError(f"unknown failure classes {sorted(unknown)}; "
                             f"have {list(CLASSES)}")
        self._roles()
        self.replans = 0

    # -- the fabric's tiers ------------------------------------------------
    def _roles(self) -> None:
        fab = self.fabric
        u, v = fab.edges[:, 0], fab.edges[:, 1]
        switch = fab.kind == ref.SWITCH
        server = fab.kind == ref.SERVER
        self.edge_sw = np.zeros(fab.n_vertices, bool)
        self.edge_sw[v[server[u] & switch[v]]] = True
        self.agg = np.zeros(fab.n_vertices, bool)
        self.agg[v[self.edge_sw[u] & switch[v] & ~self.edge_sw[v]]] = True
        self.core = switch & ~self.edge_sw & ~self.agg
        if not (self.edge_sw.any() and self.agg.any() and self.core.any()):
            raise ValueError("the fabric has no edge, aggregation and core "
                             "tiers to fail")

    def _up(self, x: int, tier: np.ndarray) -> np.ndarray:
        """The vertices of `tier` with an arc from `x`, in index order."""
        e = self.fabric.edges
        return np.unique(e[(e[:, 0] == x) & tier[e[:, 1]], 1])

    def _arcs(self, a: int, b: int) -> tuple[int, ...]:
        e = self.fabric.edges
        return tuple(int(i) for i in np.flatnonzero(
            ((e[:, 0] == a) & (e[:, 1] == b))
            | ((e[:, 0] == b) & (e[:, 1] == a))))

    def _at(self, x: int) -> tuple[int, ...]:
        e = self.fabric.edges
        return tuple(int(i) for i in np.flatnonzero((e[:, 0] == x)
                                                    | (e[:, 1] == x)))

    def failures(self, rp: ref.Problem, schedule: np.ndarray) -> list:
        """One Failure of each of the mix's classes for a healthy plan of
        `rp` whose schedule is `schedule` (F, E, W, T)."""
        load = np.asarray(schedule).sum(axis=(0, 2, 3))        # (E,)
        into = np.zeros(self.fabric.n_vertices)
        np.add.at(into, self.fabric.edges[:, 1], load)
        edge0 = int(self._up(int(rp.src[0]), self.edge_sw)[0])
        aggs0 = [int(a) for a in self._up(edge0, self.agg)]

        def link(cls, ends):
            arcs = max((self._arcs(a, b) for a, b in ends),
                       key=lambda arcs: (load[list(arcs)].sum(), -arcs[0]))
            return Failure(cls, arcs, None)

        def switch(cls, cands):
            dev = int(max(cands, key=lambda x: (into[x], -x)))
            return Failure(cls, self._at(dev), dev)

        out = []
        for cls in self.classes:
            if cls == "edge-agg-link":
                out.append(link(cls, [(edge0, a) for a in aggs0]))
            elif cls == "agg-core-link":
                out.append(link(cls, [(a, int(c)) for a in aggs0
                                      for c in self._up(a, self.core)]))
            elif cls == "agg-switch":
                dst_edge = int(self._up(int(rp.dst[0]), self.edge_sw)[0])
                out.append(switch(cls, self._up(dst_edge, self.agg)))
            else:
                out.append(switch(cls, np.flatnonzero(self.core)))
        return out

    # -- problems ------------------------------------------------------------
    def scenario(self, fail: Failure):
        """The program's FailureScenario of `fail`."""
        if fail.device is not None:
            return self.pfailures.FailureScenario(
                fail.name, failed_devices=(fail.device,))
        return self.pfailures.FailureScenario(fail.name, cut_edges=fail.arcs)

    def degrade(self, healthy, spans) -> list:
        """[(degraded problem, Pending reference, healthy result)], one
        for each failure of each healthy plan."""
        members = []
        with spans.span("degrade"):
            for p, rp, r, _ in healthy:
                for fail in self.failures(rp, r.schedule):
                    members.append((
                        self.pfailures.degrade_problem(
                            p, self.scenario(fail), n_slots=p.n_slots),
                        Pending(rp, fail), r))
        self.replans += len(members)
        return members

    def replan(self, problems, warm, tol: float):
        return list(self.solver.solve_fast_ensemble(
            problems, self.objective, warm=warm,
            iters=self.cfg["solver"]["iters"], tol=tol))

    def retry_warm(self, p, rp, warm, tol: float):
        """[(problem, reference problem, result)] of a re-plan over twice
        the horizon, warm; empty where the program returned no result."""
        p = self.timeslot.rehorizon(p, 2 * p.n_slots)
        return [(p, rp, r) for r in self.replan([p], [warm], tol)[:1]]

    def step(self, pairs, tol: float, spans) -> tuple[list, int]:
        healthy, missing = super().step(pairs, tol, spans)
        members = self.degrade(healthy, spans)
        results = self.replan([p for p, _, _ in members],
                              [w for _, _, w in members], tol)
        out = list(healthy)
        missing += abs(len(results) - len(members))
        for (p, rp, w), r in zip(members, results):
            ok = self.certified(p, r, spans)
            if not ok:
                again = self.retry_warm(p, rp, w, tol)
                missing += not again
                for p, rp, r in again:
                    ok = self.certified(p, r, spans)
            out.append((p, rp, r, ok))
        return out, missing

    # -- phases ----------------------------------------------------------------
    def warm_up(self) -> None:
        """Compiles what a step runs: the healthy batch and the re-plan
        ensemble of one step, and a retry alone of each of its healthy
        plans and re-plans (their LPs differ in shape)."""
        inf = float("inf")
        pairs = self.problems(0, 0)
        healthy = [(p, rp, r, False) for (p, rp), r in
                   zip(pairs, self.solve([p for p, _ in pairs], inf))]
        for p, rp in pairs:
            self.retry(p, rp, inf)
        members = self.degrade(healthy, Spans())
        self.replan([p for p, _, _ in members],
                    [w for _, _, w in members], inf)
        for p, rp, w in members:
            self.retry_warm(p, rp, w, inf)
        self.replans = 0

    def window(self, seconds: float, spans) -> dict:
        from repro import trace

        stats = getattr(self.solver, "ensemble_stats", None)
        before = stats().snapshot() if stats else None
        self.replans = 0
        with trace.recording() as rec, \
                spans.wrap(self.solver, "project_warm_start", "project"):
            obs = super().window(seconds, spans)
        obs["program_spans"] = rec.seconds()
        obs["replans"] = self.replans
        if stats:
            after = stats()
            obs["ensemble"] = {f.name: getattr(after, f.name)
                               - getattr(before, f.name)
                               for f in dataclasses.fields(after)}
        return obs

    # -- correctness -----------------------------------------------------------
    def resolved(self, obs: dict) -> dict:
        """`obs` with the reference problem of every re-plan built."""
        return dict(obs, schedules=[
            (p, degraded_reference(p, rp) if isinstance(rp, Pending) else rp,
             *rest) for p, rp, *rest in obs["schedules"]])

    def check(self, obs: dict) -> dict:
        obs = self.resolved(obs)
        worst = super().check(obs)
        worst["dropped_gbits"] = max(
            (float(np.maximum(rp.size - p.coflow.size, 0.0).sum())
             for p, rp, *_ in obs["schedules"]), default=0.0)
        return worst

    def control(self, obs: dict) -> dict:
        worst = super().control(self.resolved(obs))
        worst["dropped_gbits"] = 0.0
        return worst


def degraded_reference(p, pending: Pending) -> ref.Problem:
    """The reference problem of the re-plan `p` (the program's degraded
    problem): the healthy reference problem over the reference fabric
    with the failure's arcs zeroed, over `p`'s horizon; a flow the
    degraded fabric no longer routes gets demand 0."""
    rp = pending.healthy
    cap = rp.fabric.cap.copy()
    cap[list(pending.fail.arcs)] = 0.0
    probe = dataclasses.replace(
        rp, fabric=dataclasses.replace(rp.fabric, cap=cap),
        n_slots=p.n_slots)
    return dataclasses.replace(probe,
                               size=np.where(routable(probe), rp.size, 0.0))


def routable(p: ref.Problem) -> np.ndarray:
    """(F,) bool: has flow f a src->dst walk over live edges it may use
    (`reference.admissible_edges`)?"""
    fab = p.fabric
    ok = ref.admissible_edges(p) & (fab.cap.sum(axis=1) > 0)[None, :]
    V = fab.n_vertices
    out = np.zeros(p.n_flows, bool)
    for f in range(p.n_flows):
        e = fab.edges[ok[f]]
        g = sparse.csr_matrix((np.ones(len(e)), (e[:, 0], e[:, 1])),
                              shape=(V, V))
        reached = csgraph.breadth_first_order(g, int(p.src[f]),
                                              return_predecessors=False)
        out[f] = int(p.dst[f]) in set(reached.tolist())
    return out
