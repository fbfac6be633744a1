"""Run one benchmark cell: set-up, warm-up, a measured window, the check.

Everything a cell needs is found by name under `bench/`:

  BENCHMARK.json          the cells (`workloads`) and metrics;
  configs/<config>.json   a deployment: fabric builder and its kwargs, the
                          fabric's fingerprint, the shuffle, scheduling and
                          solver settings, source, assumed, reduced;
  fabrics/<builder>.py    the plain reference of that fabric (`build`);
  mixes/<traffic>.json    how traffic is offered; `kind` names the driver;
  drivers/<kind>.py       the driver of that kind of mix;
  limits/<workload>.json  the limit of every number the check compares;
  metrics/<metric>.py     one reader per per-layer metric (`read`).

`run` never looks for a chip; `run.py` does, before calling it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(1, str(CHECKOUT / "src"))

import reference as ref  # noqa: E402
import roofline  # noqa: E402
import devtrace  # noqa: E402
from spans import Spans  # noqa: E402

# JAX's persistent compilation cache, at a fixed path inside the checkout
# unless the machine names one
CACHE_DIR = CHECKOUT / ".jax_cache"
SPEC = CHECKOUT / "BENCHMARK.json"
DATA = BENCH            # where configs/, mixes/ and limits/ are found


def load_json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, name: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries a workload reports."""
    def mine(m):
        return name in m.get("workloads", [name])
    return ([m for m in spec["end_to_end"] if mine(m)],
            [m for m in spec["per_layer"] if mine(m)])


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for every program,
    however small or quick to build: in `$JAX_COMPILATION_CACHE_DIR`
    where the machine sets it, else at the fixed `CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def fabric_of(topo) -> ref.Fabric:
    """The program's Topology as plain arrays, for the fingerprint."""
    import numpy as np

    codes = {"server": ref.SERVER, "switch": ref.SWITCH,
             "passive": ref.PASSIVE}
    sigma = np.full(topo.n_vertices, np.inf)
    for v, s in topo.switch_sigma.items():
        sigma[v] = s
    return ref.Fabric(
        kind=np.array([codes[d.kind] for d in topo.devices]),
        p_max=np.array([d.p_max for d in topo.devices], float),
        eps=np.array([d.eps for d in topo.devices], float), sigma=sigma,
        edges=np.asarray(topo.edges), cap=np.asarray(topo.cap),
        slot_s=topo.slot_duration, server_relay=topo.server_relay,
        one_wavelength_tx=topo.one_wavelength_tx,
        awgr_in=np.asarray(topo.awgr_in_ports, np.int64),
        task_servers=np.asarray(topo.task_servers, np.int64))


class CompileCounter:
    """Counts programs compiled or loaded from the cache while `on`."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kwargs) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def prepare(name: str, seed: int):
    """Cell `name` set up for `seed`: (driver, limits, metric entries).

    Builds the program's fabric and the reference's and checks that both
    match the configuration's fingerprint."""
    spec = load_json(SPEC)
    w = workload(spec, name)
    cfg = load_json(DATA / "configs" / f"{w['config']}.json")
    mix = load_json(DATA / "mixes" / f"{w['traffic']}.json")
    limits = load_json(DATA / "limits" / f"{name}.json")

    from repro.core import topology

    fab_cfg = cfg["fabric"]
    topo = topology.build(fab_cfg["builder"], **fab_cfg["kwargs"])
    fabric = load_module(BENCH / "fabrics" / f"{fab_cfg['builder']}.py"
                         ).build(**fab_cfg["kwargs"])
    prints = {"config": cfg["fingerprint"], "reference": fabric.fingerprint(),
              "program": fabric_of(topo).fingerprint()}
    if len(set(prints.values())) != 1:
        raise SystemExit(f"bench: fabric fingerprints differ: {prints}")
    driver = getattr(load_module(BENCH / "drivers" / f"{mix['kind']}.py"),
                     mix["kind"].capitalize())(cfg, mix, fabric, topo, seed)
    return driver, limits, metrics_for(spec, name)


def run(name: str, seed: int, seconds: float, traced: bool,
        t_start: float) -> dict:
    """One run of cell `name`; returns the result line's object.

    `t_start` is the host clock at process start, where set-up begins."""
    t_prep = time.perf_counter()
    driver, limits, (e2e_defs, layer_defs) = prepare(name, seed)
    t_built = time.perf_counter()
    compiles = CompileCounter()
    driver.warm_up()
    spans = Spans(annotate=traced)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir, \
            contextlib.ExitStack() as stack:
        if traced:
            import jax

            for attr in ("path_decompose", "temporal_pack", "evaluate"):
                stack.enter_context(spans.wrap(driver.solver, attr, "pack"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
        setup_s = time.perf_counter() - t_start
        print(f"setup: start {t_prep - t_start:.3f} s, build "
              f"{t_built - t_prep:.3f} s, warm-up "
              f"{setup_s - (t_built - t_start):.3f} s", file=sys.stderr)
        compiles.on = True
        with spans.span("window"):
            obs = driver.window(seconds, spans)
        compiles.on = False
        stack.close()
        device = device_info()
        events = devtrace.load(tdir) if traced else None

    attempted, failed = driver.counts(obs)
    if traced:
        red = devtrace.reduce(events, annotations=set(spans.seconds))
        obs.update(spans=spans.seconds, trace=red, compiles=compiles.count,
                   bytes=roofline.pdhg_bytes(driver.lp_sizes(obs)),
                   device_kind=device["kind"])
        metrics = {}
        for m in layer_defs:
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    else:
        got = driver.end_to_end(obs)
        metrics = {m["name"]: {"value": got[m["name"]][0], "unit": m["unit"]}
                   for m in e2e_defs if m["name"] in got}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    checks = driver.check(obs)
    correct = all(checks[k] <= limits[k]["limit"] for k in limits) and \
        set(checks) == set(limits)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if traced:
        line["breakdown"] = red.breakdown()
    line["checks"] = {k: {"value": checks[k], "limit": limits[k]["limit"]}
                      for k in checks}
    return line
