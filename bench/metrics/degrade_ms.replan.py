"""Host milliseconds per re-plan in the program's `failure.degrade` span
(`failures.degrade_problem`: the degraded fabric, the routable-flow
search and the degraded problem); None where the window recorded no
program spans or the program has no such span."""


def read(obs: dict) -> float | None:
    n = obs.get("replans")
    s = obs.get("program_spans", {}).get("failure.degrade")
    return 1e3 * sum(s) / n if n and s else None
