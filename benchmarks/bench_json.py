"""Shared machine-readable benchmark reporter.

Every benchmark prints human-readable ``name,ms,derived`` CSV rows; this
module adds the machine side: a single ``BENCH_solver.json`` at the repo
root that accumulates one section per benchmark, so the perf trajectory
of the solver stack is trackable across commits (CI uploads the file as
a workflow artifact; docs/REPRODUCING.md documents the schema).

Schema (one file, merged across benchmarks):

    {
      "schema": 1,
      "git_sha": "<HEAD at last update>",
      "benches": {
        "<bench name>": {
          "git_sha": "<HEAD when this bench last ran>",
          "args": {...},                  # the CLI knobs that shaped the run
          "records": [
            {"name": "...",               # the printed CSV row's name
             "topology": "...", "objective": "...",
             "wall_ms": float,
             "iterations": float | null,  # mean PDHG iters/instance
             "derived": "..."}            # the printed CSV row's comment
          ]
        }
      }
    }

Records are flat and append-only within a run so downstream tooling can
diff two files field-by-field without knowing any benchmark's layout.
"""
from __future__ import annotations

import json
import pathlib
import subprocess

DEFAULT_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_solver.json"


def git_sha() -> str:
    """HEAD commit of the enclosing repo, or "unknown" outside git."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=pathlib.Path(__file__).resolve().parent, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def record(name: str, *, topology: str | None = None,
           objective: str | None = None, wall_ms: float,
           iterations: float | None = None, derived: str = "") -> dict:
    """One benchmark measurement in the shared flat schema."""
    return {"name": name, "topology": topology, "objective": objective,
            "wall_ms": round(float(wall_ms), 3),
            "iterations": (None if iterations is None
                           else round(float(iterations), 1)),
            "derived": derived}


def finish_comparison(bench: str, prefix: str, ref: float, meas: float,
                      records: list[dict], *, total_label: str,
                      speed_label: str, json_out: str, run_args: dict,
                      min_speedup: float) -> int:
    """Shared tail of the comparison benchmarks: the aggregate row, the
    BENCH_solver.json merge, and the min-speedup gate.

    `ref` and `meas` are wall-time totals in seconds; speedup =
    ref / meas.  Returns the process exit code."""
    speed = ref / meas
    print(f"{prefix}/aggregate,{meas*1e3:.1f},"
          f"{speed:.2f}x speedup ({total_label} {ref*1e3:.1f} ms)")
    records.append(record(f"{prefix}/aggregate", wall_ms=meas * 1e3,
                          derived=f"{speed:.2f}x {speed_label}"))
    if json_out:
        path = update(bench, records, path=json_out, args=run_args)
        print(f"{prefix}/json,0.0,records merged into {path}")
    if speed < min_speedup:
        print(f"FAIL: aggregate speedup {speed:.2f}x < {min_speedup}x")
        return 1
    print(f"OK: aggregate speedup {speed:.2f}x >= {min_speedup}x")
    return 0


def update(bench: str, records: list[dict], *, args: dict | None = None,
           path: pathlib.Path | str | None = None) -> pathlib.Path:
    """Merge one benchmark's records into BENCH_solver.json (replacing
    that benchmark's previous section, preserving the others)."""
    path = pathlib.Path(path) if path is not None else DEFAULT_PATH
    doc: dict = {"schema": 1, "benches": {}}
    if path.exists():
        try:
            prev = json.loads(path.read_text())
            if isinstance(prev, dict) and isinstance(prev.get("benches"),
                                                     dict):
                doc["benches"] = prev["benches"]
        except (ValueError, OSError):
            pass                      # corrupt file: rebuild from scratch
    sha = git_sha()
    doc["git_sha"] = sha
    doc["benches"][bench] = {"git_sha": sha, "args": args or {},
                             "records": records}
    doc["benches"] = dict(sorted(doc["benches"].items()))
    path.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    return path
