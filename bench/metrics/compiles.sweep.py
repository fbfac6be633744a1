"""Programs compiled or loaded from the compile cache inside the window."""


def read(obs: dict) -> float | None:
    return float(obs["compiles"])
