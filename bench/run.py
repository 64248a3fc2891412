"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are read from
BENCHMARK.json and from files under bench/ found by their names:
bench/configs/<config>.json, bench/traffic/<traffic>.json (which names
its driver in bench/drivers/), bench/metrics/<metric>.py and
bench/limits/<cell>.json.  Set-up builds the inputs on the device from
the seed and warms up every program the window runs; the window runs
for `--seconds`; the check then compares what the window produced with
the plain reference (bench/checks.py).  With `--trace 1` the window is
traced and the line carries the per-layer metrics and a breakdown;
otherwise it carries the end-to-end metrics.  A traced window lasts at
most TRACED_WINDOW_S: writing the device trace takes about 18 s a
second of serving, and a traced run has to end within 360 s.

The last line of standard output is one JSON object; the last lines of
standard error give each number compared beside its limit.  The run
exits non-zero, and prints no result, without a TPU or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


TRACED_WINDOW_S = 10.0


class NoChip(RuntimeError):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_metric(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, bench: dict | None = None) -> dict:
    """Everything one cell names, read from its files."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    return {"cell": cell, "conf": conf, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:n]


def enable_compile_cache():
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR
    where set, else a fixed .jax_cache/ in the checkout; every program
    is cached, however quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts compilations and cache loads while active."""

    def __init__(self):
        from jax._src import monitoring
        self.active, self.compiles, self.cache_hits = False, 0, 0

        def on_duration(event, duration, **_):
            if self.active and event.endswith("backend_compile_duration"):
                self.compiles += 1

        def on_event(event, **_):
            if self.active and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def traced(window, seconds: float, log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            rec = window(seconds)
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
    rec["trace_stop_s"] = time.perf_counter() - t0
    return rec


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        cache: bool = True,
        need_chip: bool = True, spec: dict | None = None,
        patches=()) -> dict:
    """One run of a cell; returns the result object.  `patches` are
    context managers entered around the whole run (the fault and
    control runs of bench/calibrate.py and bench/tests)."""
    import jax
    if trace:
        seconds = min(seconds, TRACED_WINDOW_S)
    spec = spec or cell_spec(workload)
    cell, conf = spec["cell"], spec["conf"]
    chips = cell["chips"]
    if need_chip:
        devices = require_chips(chips)
    else:
        devices = jax.devices()[:chips]
    if cache:
        enable_compile_cache()
    peaks_table = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if need_chip and kind not in peaks_table:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    peaks = peaks_table.get(kind)
    counter = CompileCounter()
    driver_mod = importlib.import_module(
        f"bench.drivers.{spec['traffic']['driver']}")
    with contextlib.ExitStack() as stack:
        for p in patches:
            stack.enter_context(p)
        drv = driver_mod.Driver(conf, spec["traffic"], seed, chips)
        drv.setup(seconds)
        setup_s = time.perf_counter() - T_START
        log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        try:
            counter.active = True
            if trace:
                rec = traced(drv.window, seconds, log_dir)
            else:
                with jax.profiler.TraceAnnotation("bench.window"):
                    rec = drv.window(seconds)
            counter.active = False
            if "failed" not in rec:
                rec["failed"] = drv.failed()
            mem = memory_peak(devices)
            reduced = None
            if trace:
                from bench import trace_reduce
                t0 = time.perf_counter()
                reduced = trace_reduce.Reduced(trace_reduce.load(
                    trace_reduce.find_xplane(log_dir)))
                rec["trace_read_s"] = time.perf_counter() - t0
        finally:
            if log_dir:
                shutil.rmtree(log_dir, ignore_errors=True)
        t0 = time.perf_counter()
        numbers = drv.check()
        rec["check_s"] = time.perf_counter() - t0
    from bench import checks
    ok, compared = checks.judge(numbers, checks.load_limits(workload))
    ok = ok and rec["failed"] == 0
    context = drv.context()
    ctx = dict(rec, setup_s=setup_s, kind=drv.kind, trace=reduced,
               chips=chips, peaks=peaks, n_topics=conf["n_topics"],
               notes=context.pop("notes", {}), **context)
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    result["notes"] = dict(ctx["notes"], compiles_in_window=counter.compiles,
                           cache_loads_in_window=counter.cache_hits,
                           setup_parts=ctx.get("setup_parts"),
                           **{k: rec[k] for k in ("trace_stop_s",
                                                  "trace_read_s", "check_s")
                              if k in rec})
    result["checks"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    notes = result.pop("notes")
    print(json.dumps({"notes": notes}), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
