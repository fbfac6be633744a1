"""Host milliseconds per schedule in the program's `problem.mask` span
(a ScheduleProblem's flow-edge mask, hop-count rows found on a cache
miss included); None where the window recorded no program spans or the
program has no such span."""


def read(obs: dict) -> float | None:
    n = len(obs["schedules"])
    s = obs.get("program_spans", {}).get("problem.mask")
    return 1e3 * sum(s) / n if n and s else None
