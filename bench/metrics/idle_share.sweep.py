"""Share of the traced window in which no operation ran on the device."""


def read(obs: dict) -> float | None:
    return obs["trace"].idle_share
