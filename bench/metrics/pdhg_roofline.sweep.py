"""PDHG's share of its roofline: the least time the chip could take for
the iterations the schedules needed (bytes over HBM bandwidth; see
roofline.py) over the device time of the PDHG programs."""
import roofline

PROGRAM = "_pdhg_run_adaptive"


def read(obs: dict) -> float | None:
    s = obs["trace"].program_s(PROGRAM)
    if s <= 0 or not obs["bytes"]:
        return None
    bw = roofline.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * obs["bytes"] / bw / s
