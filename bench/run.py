"""The co-flow scheduler's chip benchmark: one cell, one run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on and needs the TPUs the cell asks
for (`chips` in BENCHMARK.json); without them it exits 2 and prints no
result.  Set-up (TPU start, fabric build, warm-up of the cell's own
shapes; JAX's compile cache lives in `$JAX_COMPILATION_CACHE_DIR` where
that is set, else in `<checkout>/.jax_cache`) is timed
as `setup_s`; then whole steps run until `--seconds` have passed.  With
`--trace 0` the result line holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window and from host spans.  After the window the answers are compared
with the plain reference (reference.py); every number compared is
printed beside its limit, as the last lines of standard error and
under "checks", the last key of the result line, which is the last line
of standard output.

Adding to the benchmark takes new files only (see harness.py for where
each is found):

  * a configuration: `configs/<name>.json` (copy one and change it; its
    `fingerprint` is `reference.Fabric.fingerprint()` of the fabric the
    reference builder makes) and, for a new fabric family,
    `fabrics/<builder>.py` with `build(**kwargs) -> Fabric`;
  * a traffic mix: `mixes/<name>.json`; a mix of a kind no driver has
    yet brings `drivers/<kind>.py` with the `Sweep` class's methods;
  * a cell: its entry in BENCHMARK.json and `limits/<cell>.json`, set
    from readings of `calibrate.py` on the chip;
  * a per-layer metric: its entry in BENCHMARK.json and
    `metrics/<name>.py` with `read(obs) -> float | None`, returning None
    where there is nothing to read.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    spec = harness.load_json(harness.SPEC)
    chips = harness.workload(spec, args.workload)["chips"]
    harness.enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2
    line = harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T0)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
