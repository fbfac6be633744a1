"""PDHG iterations per warm-started member of `solve_fast_ensemble`,
from the window's change in `solver.ensemble_stats()`; None where the
program has no such counters or ran no warm member."""


def read(obs: dict) -> float | None:
    e = obs.get("ensemble")
    if not e or not e["warm_members"]:
        return None
    return e["warm_iters"] / e["warm_members"]
