"""Host milliseconds per schedule in `verify.check_schedule`."""


def read(obs: dict) -> float | None:
    n = len(obs["schedules"])
    s = obs["spans"].get("certify")
    return 1e3 * sum(s) / n if n and s else None
