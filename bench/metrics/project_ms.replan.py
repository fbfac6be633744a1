"""Host milliseconds per re-plan in the program's `warm.project` span
(`solver.project_warm_start`: the healthy routing and duals mapped onto
the degraded LP, dead paths re-routed), retries included; None where
the window recorded no program spans or the program has no such span."""


def read(obs: dict) -> float | None:
    n = obs.get("replans")
    s = obs.get("program_spans", {}).get("warm.project")
    return 1e3 * sum(s) / n if n and s else None
