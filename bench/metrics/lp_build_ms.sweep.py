"""Host milliseconds per schedule in `solver.build_routing_lp`."""


def read(obs: dict) -> float | None:
    n = len(obs["schedules"])
    s = obs["spans"].get("lp_build")
    return 1e3 * sum(s) / n if n and s else None
