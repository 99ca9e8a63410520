"""Run one cell of the BLADE-FL benchmark once, on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``driver`` names the loop that
drives the engine (``bench/drivers/<driver>.py``); each metric is read by
``bench/metrics/<metric>.py``, and the limits that decide ``correct`` are
in ``bench/limits/<cell>.json``.

A run: set-up (import and backend, inputs from the seed, warm-up of every
shape the window uses), then the window, a closed loop of driver calls
until the first call that ends ``--seconds`` after the window opened. With
``--trace 1`` the window's first calls run under the profiler with host
spans on, and the per-layer metrics are read from them. Then the program's
outputs are compared with the plain reference. The last line of standard
output is the result; the numbers compared, each beside its limit, are
the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse        # noqa: E402
import importlib       # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402
import types           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(Exception):
    """This host cannot run the cell."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name):
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", name + ".json")
    return bench, cell, cfg, traffic, limits


def metrics_of(bench, cell, kind):
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) this cell
    reports: those listing it, and those without a list whose ``moves``
    metric it reports."""
    name = cell["name"]
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", []) or (
                "workloads" not in m and m["moves"] in e2e)]


def require_devices(chips):
    """The devices of the cell; a TPU with at least ``chips`` chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX sees "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


class CompileMeter:
    """JAX's compile events: seconds in backend compiles (a persistent
    cache load counts as one), their number, and persistent-cache hits."""

    def __init__(self):
        import jax
        self.secs, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.secs, self.count, self.hits)

    def since(self, mark):
        return {"compile_s": self.secs - mark[0],
                "compiles": self.count - mark[1],
                "cache_hits": self.hits - mark[2]}


def read_metric(name, run):
    mod = importlib.import_module(f"metrics.{name}")
    return mod.read(run)


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window(run, driver, seconds, trace):
    """The measured loop: calls until the first that ends ``seconds`` after
    the window opened. With ``trace`` the first ``trace_calls`` calls run
    under the profiler, inside a ``bench.window`` span, with host spans
    on. Returns the window record."""
    import jax
    rec = {"calls": 0, "rounds": 0, "walls_s": []}
    t_open = time.perf_counter()

    def step():
        t0 = time.perf_counter()
        with run.spans.span("call"):
            rec["rounds"] += driver.call(rec["calls"])
        t1 = time.perf_counter()
        rec["calls"] += 1
        rec["walls_s"].append(t1 - t0)
        rec["span_s"] = t1 - t_open
        return rec["span_s"] >= seconds

    done = False
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python-function tracer: it would trace every call of the
        # host loop; the bench.* annotations are kept by the host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        run.spans.on = True
        with driver.traced(), jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(run.traffic["trace_calls"]):
                done = step()
        run.spans.on = False
        run.rounds_traced = rec["rounds"]
        jax.profiler.stop_trace()
    while not done:
        done = step()
    return rec


def use_checkout():
    """Point the compile cache at the checkout, whatever the environment
    says (two checkouts then share nothing), keep the TPU runtime from
    logging to a fixed path under /tmp, and put the program and the
    benchmark on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (os.path.join(ROOT, "src"), HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()


def new_run(cell, cfg, traffic, seed, devices):
    from spans import Spans
    dev = devices[0]
    return types.SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic, seed=seed, spans=Spans(),
        chips=cell["chips"], devices=devices, trace=None, rounds_traced=0,
        device={"platform": dev.platform, "kind": dev.device_kind,
                "count": len(devices)})


def line(run, name, **fields):
    """An earlier line of standard output, naming the device."""
    print(f"{name}: " + json.dumps({**fields, "device": run.device},
                                   default=str), flush=True)


def set_up(run, meter, t_start):
    """Inputs from the seed, the driver, and its warm-up. Returns the
    driver; ``run.setup_s`` counts from ``t_start``."""
    import jax

    import inputs
    t0 = time.perf_counter()
    run.inputs = inputs.build(run.cfg, run.seed)
    jax.block_until_ready(run.inputs)
    t_inputs = time.perf_counter() - t0
    driver = importlib.import_module(
        f"drivers.{run.traffic['driver']}").Driver(run)
    mark = meter.mark()
    t0 = time.perf_counter()
    driver.warm_up()
    warm = {"warmup_s": time.perf_counter() - t0, **meter.since(mark)}
    run.setup_s = time.perf_counter() - t_start
    line(run, "setup", setup_s=run.setup_s, inputs_s=t_inputs, **warm)
    return driver


def measure(run, driver, meter, seconds, trace):
    """The window, the memory peak, and the program's outputs brought to
    the host for the reference."""
    import numpy as np
    mark = meter.mark()
    run.window = window(run, driver, seconds, trace)
    run.compiles_in_window = meter.since(mark)["compiles"]
    run.memory_peak = memory_peak(run.devices)
    line(run, "window", calls=run.window["calls"],
         rounds=run.window["rounds"], span_s=run.window["span_s"],
         compiles_in_window=run.compiles_in_window,
         memory_peak_bytes=run.memory_peak)
    run.host_inputs = {"params": {n: np.asarray(v, np.float64)
                                  for n, v in run.inputs["params"].items()}}
    if "batch" in run.inputs:
        run.host_inputs.update(x=run.inputs["batch"]["x"],
                               y=run.inputs["batch"]["y"])
    driver.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic, limits = find_cell(args.workload)
    use_checkout()
    import compare
    meter = CompileMeter()
    try:
        devices = require_devices(cell["chips"])
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    run = new_run(cell, cfg, traffic, args.seed, devices)
    line(run, "start", import_and_backend_s=time.perf_counter() - T_START)
    driver = set_up(run, meter, T_START)
    measure(run, driver, meter, args.seconds, args.trace)
    t0 = time.perf_counter()
    checks, ok = compare.judged(driver.check(), limits)
    line(run, "counters", check_s=time.perf_counter() - t0,
         **driver.counters())

    result = {"correct": bool(ok), "attempted": run.window["calls"],
              "failed": driver.failed}
    if args.trace:
        import trace_reduce
        run.trace = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(TRACE_DIR)))
        line(run, "trace", **{k: v for k, v in run.trace.items()
                              if k not in ("device_ops", "idle_gaps")},
             rounds_traced=run.rounds_traced,
             host_span_s={n: run.spans.seconds(n) for n in
                          sorted({r[0] for r in run.spans.records})})
    metrics = {}
    for m in metrics_of(bench, cell,
                        "per_layer" if args.trace else "end_to_end"):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(run.device, memory_peak_bytes=run.memory_peak)
    if args.trace and run.trace.get("n_devices"):
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result.update(metrics=metrics, device=device, checks=checks)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
