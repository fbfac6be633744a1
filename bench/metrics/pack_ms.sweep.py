"""Host milliseconds per schedule in `path_decompose`, `temporal_pack`
and `timeslot.evaluate` (the program's pack and exact re-scoring)."""


def read(obs: dict) -> float | None:
    n = len(obs["schedules"])
    s = obs["spans"].get("pack")
    return 1e3 * sum(s) / n if n and s else None
