"""Benchmark trend gate: fail CI when the sweep's solve path regresses.

Compares a freshly produced BENCH_solver.json against the committed
baseline at the repo root and fails if the aggregate sweep wall time
regressed by more than ``--max-regress`` (default 20%).

Raw wall-clock numbers are only comparable on the same machine with the
same benchmark arguments, so the gate adapts:

  * **absolute mode** — when the candidate's ``sweep_bench`` args match
    the baseline's exactly, the ``sweep/aggregate`` wall_ms values are
    compared directly;
  * **normalized mode** (the CI case: different seeds/topos, different
    runner hardware) — each file's aggregate *batch/loop ratio* is
    compared instead.  The per-instance loop runs the same PDHG work
    through the same machine in the same process, so it cancels both
    hardware speed and benchmark scale; a >20% increase of the ratio
    means the batched sweep path itself got slower relative to the
    floor, which is exactly the regression we care about.  Cells are
    matched by record name, so only cells present in both files count.
    The ratio does NOT cancel the iteration budget (fixed per-solve
    overhead is a larger fraction of short runs), so normalized mode
    additionally requires the two runs' `iters`/`tol` knobs to match —
    a mismatch is reported and skipped rather than mis-gated.

Exit code 1 on regression, 0 otherwise (including "nothing comparable",
which is reported but does not fail — a brand-new benchmark section has
no baseline yet).

Run:  python tools/check_bench_trend.py --candidate /tmp/BENCH_solver.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _records(doc: dict, bench: str) -> dict[str, dict]:
    sec = doc.get("benches", {}).get(bench)
    if not sec:
        return {}
    return {r["name"]: r for r in sec.get("records", [])}


def _pair_totals(base: dict[str, dict], cand: dict[str, dict],
                 suffix_a: str, suffix_b: str) -> tuple[float, float,
                                                        float, float, int]:
    """Sum wall_ms over cells whose /loop and /batch rows exist in both
    files; returns (base_a, base_b, cand_a, cand_b, n_cells)."""
    cells = []
    for name in base:
        if name.endswith(suffix_a):
            stem = name[: -len(suffix_a)]
            if (stem + suffix_b in base and name in cand
                    and stem + suffix_b in cand):
                cells.append(stem)
    ba = sum(base[c + suffix_a]["wall_ms"] for c in cells)
    bb = sum(base[c + suffix_b]["wall_ms"] for c in cells)
    ca = sum(cand[c + suffix_a]["wall_ms"] for c in cells)
    cb = sum(cand[c + suffix_b]["wall_ms"] for c in cells)
    return ba, bb, ca, cb, len(cells)


def check_sweep(base_doc: dict, cand_doc: dict, max_regress: float) -> int:
    base = _records(base_doc, "sweep_bench")
    cand = _records(cand_doc, "sweep_bench")
    if not base or not cand:
        print("trend: no sweep_bench section to compare — skipping")
        return 0
    base_args = base_doc["benches"]["sweep_bench"].get("args", {})
    cand_args = cand_doc["benches"]["sweep_bench"].get("args", {})

    if base_args == cand_args:
        failed = 0
        for name, rec in base.items():
            if "/aggregate" not in name or name not in cand:
                continue
            old, new = rec["wall_ms"], cand[name]["wall_ms"]
            regress = new / max(old, 1e-9) - 1.0
            status = "FAIL" if regress > max_regress else "ok"
            print(f"trend[absolute] {name}: {old:.1f} -> {new:.1f} ms "
                  f"({regress:+.1%}) {status}")
            failed += status == "FAIL"
        return 1 if failed else 0

    for knob in ("iters", "tol"):
        if base_args.get(knob) != cand_args.get(knob):
            print(f"trend: baseline and candidate ran with different "
                  f"{knob!r} ({base_args.get(knob)} vs "
                  f"{cand_args.get(knob)}) — the batch/loop ratio is "
                  f"not comparable across budgets, skipping")
            return 0
    ba, bb, ca, cb, n = _pair_totals(base, cand, "/loop", "/batch")
    if not n:
        print("trend: no common sweep cells between baseline and "
              "candidate — skipping")
        return 0
    old_ratio = bb / max(ba, 1e-9)        # batch / loop: lower is better
    new_ratio = cb / max(ca, 1e-9)
    regress = new_ratio / max(old_ratio, 1e-9) - 1.0
    status = "FAIL" if regress > max_regress else "ok"
    print(f"trend[normalized, {n} common cells] aggregate batch/loop "
          f"ratio: {old_ratio:.3f} -> {new_ratio:.3f} ({regress:+.1%}) "
          f"{status}")
    if status == "FAIL":
        print(f"FAIL: the batched sweep path slowed down >"
              f"{max_regress:.0%} relative to the per-instance loop "
              f"floor (machine-speed independent)")
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default=str(REPO / "BENCH_solver.json"),
                    help="committed baseline (repo root by default)")
    ap.add_argument("--candidate", required=True,
                    help="freshly produced BENCH_solver.json")
    ap.add_argument("--max-regress", type=float, default=0.20,
                    help="maximum tolerated fractional wall regression")
    args = ap.parse_args(argv)
    base_doc = json.loads(pathlib.Path(args.baseline).read_text())
    cand_doc = json.loads(pathlib.Path(args.candidate).read_text())
    return check_sweep(base_doc, cand_doc, args.max_regress)


if __name__ == "__main__":
    sys.exit(main())
