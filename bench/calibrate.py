"""Readings the limits of a cell's check are set from (run on the chip).

    python bench/calibrate.py --workload <name> --seeds <s1,s2,...> \
        [--out <file.jsonl>]

One process sets the cell up once and then, for each seed, runs one
step of the cell's traffic (with its retries) and prints, as a JSON line, the numbers the check compares for the program
("program", the lower readings) and for the control ("control": each
layer's output replaced by the reference computed one precision below
the configuration's; the upper readings).  limits/<cell>.json is set
between the two.
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))


def readings(driver, seeds):
    from spans import Spans

    for seed in seeds:
        driver.seed = seed
        obs = driver.window(0.0, Spans())
        yield {"seed": seed, "program": driver.check(obs),
               "control": driver.control(obs),
               "failed": driver.counts(obs)[1],
               "iterations": [it for *_, it in driver.lp_sizes(obs)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import harness

    harness.enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    driver, _, _ = harness.prepare(args.workload, seeds[0])
    driver.warm_up()
    out = open(args.out, "a") if args.out else None
    try:
        for r in readings(driver, seeds):
            print(json.dumps(r), flush=True)
            if out:
                out.write(json.dumps(r) + "\n")
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
