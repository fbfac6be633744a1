"""From a JAX profiler trace to device busy time, per-program device time
and the idle gaps by what the host was doing.

`load(dir)` flattens the newest `.xplane.pb` under `dir` into `Event`s
(plane, line, name, start, duration; nanoseconds).  `reduce(events)`
works on that list alone, so a recorded list (bench/testdata) checks it
without a chip:

  * device events are those on planes named "/device:TPU:<n>"; an
    operation is an event on the plane's "XLA Ops" line, a program run
    one on its "XLA Modules" line;
  * busy time is the union of a device's operation intervals, averaged
    over the devices that ran any;
  * the window is the host annotation named `window` (the harness opens
    it around the measured steps), else the span of all device events;
  * an idle gap is a stretch of the window in which the device runs no
    operation; one under `SHORT_GAP_NS` is put down to "between ops"
    (the device's own stalls inside a program), a longer one to the
    shortest of the given host annotations, other than the window, that
    covers its midpoint ("host" if none does);
  * an operation is named by its HLO instruction, without its operands.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
SHORT_GAP_NS = 100_000


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def load(directory) -> list[Event]:
    """Every event of the newest xplane file under `directory`."""
    import jax

    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
    return out


def union_ns(intervals) -> list[tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    module_s: dict[str, float]        # program name -> device seconds
    op_s: dict[str, float]            # operation name -> device seconds
    gaps_s: dict[str, float]          # host annotation -> idle seconds

    @property
    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not self.op_s:
            return None
        return 100.0 * max(1.0 - self.busy_s / self.window_s, 0.0)

    def program_s(self, part: str) -> float:
        """Device seconds of the programs whose name contains `part`."""
        return sum(s for n, s in self.module_s.items() if part in n)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gaps_s)}


def op_name(name: str) -> str:
    """'%fusion.3 = f32[8]{0} fusion(...)' -> 'fusion.3'."""
    return name.split(" = ")[0].lstrip("%")


def reduce(events: list[Event], annotations=()) -> Reduced:
    dev = [e for e in events if e.plane.startswith(DEVICE_PREFIX)]
    ops = [e for e in dev if e.line == OPS_LINE]
    wins = [e for e in events if e.name == WINDOW
            and not e.plane.startswith(DEVICE_PREFIX)]
    if wins:
        w0 = min(e.start_ns for e in wins)
        w1 = max(e.end_ns for e in wins)
    elif dev:
        w0 = min(e.start_ns for e in dev)
        w1 = max(e.end_ns for e in dev)
    else:
        w0 = w1 = 0

    by_plane = collections.defaultdict(list)
    for e in ops:
        s, t = max(e.start_ns, w0), min(e.end_ns, w1)
        if t > s:
            by_plane[e.plane].append((s, t))
    unions = {p: union_ns(iv) for p, iv in by_plane.items()}
    busy = (sum(sum(t - s for s, t in u) for u in unions.values())
            / len(unions) if unions else 0)

    module_s = collections.Counter()
    for e in dev:
        if e.line == MODULES_LINE:
            module_s[e.name] += e.dur_ns / len(unions or [1]) * 1e-9
    op_s = collections.Counter()
    for e in ops:
        op_s[op_name(e.name)] += e.dur_ns / len(unions or [1]) * 1e-9

    notes = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)
             and e.name in annotations and e.name != WINDOW]
    gaps = collections.Counter()
    for u in unions.values():
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t <= s:
                continue
            if t - s < SHORT_GAP_NS:
                label = "between ops"
            else:
                mid = (s + t) // 2
                cover = [e for e in notes if e.start_ns <= mid < e.end_ns]
                label = (min(cover, key=lambda e: e.dur_ns).name if cover
                         else "host")
            gaps[label] += (t - s) / len(unions) * 1e-9
    return Reduced(busy_s=busy * 1e-9, window_s=(w1 - w0) * 1e-9,
                   module_s=dict(module_s), op_s=dict(op_s),
                   gaps_s=dict(gaps))
