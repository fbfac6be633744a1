"""Device milliseconds per schedule in the PDHG programs
(`_pdhg_run_adaptive`), from the profiler trace."""

PROGRAM = "_pdhg_run_adaptive"


def read(obs: dict) -> float | None:
    n = len(obs["schedules"])
    s = obs["trace"].program_s(PROGRAM)
    return 1e3 * s / n if n and s > 0 else None
