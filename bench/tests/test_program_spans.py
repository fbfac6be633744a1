"""Idle gaps put down to the program's own spans (repro.trace), which
open inside the benchmark's wrapped calls and so are the shortest
annotation covering a gap in the host pack."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

HOST = "/host:CPU"
BENCH_NAMES = {"window", "step", "pack"}
PROGRAM_NAMES = {"lp.build", "pdhg.stack", "pdhg.run", "pdhg.unstack",
                 "pack.decompose", "pack.slots", "pack.evaluate"}


def _trace():
    """10 ms window; the device is busy in [1, 4) and [8, 9) ms; the
    bench's `pack` covers [4, 8) ms and, inside it, the program's
    `pack.decompose` [4, 5) and `pack.slots` [5, 7.5)."""
    ops = [("%fusion.65 = f32[8]{0} fusion(%a)", 1_000_000, 3_000_000),
           ("%fusion.66 = f32[8]{0} fusion(%b)", 8_000_000, 1_000_000)]
    notes = [("window", 0, 10_000_000), ("step", 0, 10_000_000),
             ("pack", 4_000_000, 4_000_000),
             ("pack.decompose", 4_000_000, 1_000_000),
             ("pack.slots", 5_000_000, 2_500_000)]
    return ([devtrace.Event("/device:TPU:0", "XLA Ops", n, s, d)
             for n, s, d in ops]
            + [devtrace.Event(HOST, "python3", n, s, d) for n, s, d in notes])


@pytest.mark.parametrize("names, want", [
    (BENCH_NAMES, {"step": 0.002, "pack": 0.004}),
    (BENCH_NAMES | PROGRAM_NAMES, {"step": 0.002, "pack": 0.0,
                                   "pack.slots": 0.004}),
])
def test_gap_takes_the_innermost_program_span(names, want):
    """The 4 ms gap's midpoint (6 ms) lies in `pack.slots`; once the
    program's names are passed, that span takes the whole gap."""
    gaps = devtrace.reduce(_trace(), annotations=names).gaps_s
    for label, s in want.items():
        assert gaps.get(label, 0.0) == pytest.approx(s), label
    assert sum(gaps.values()) == pytest.approx(0.006)
