"""One run of one cell: set up, measure, check, print the result line.

Everything a cell needs is found by name: the cell's configuration file
(``BENCHMARK.json``), the entry module the configuration names
(``bench/entries``), the mix file named by the cell's traffic
(``bench/mixes``) and the generator it names (``bench/traffic``), the
cell's limits (``bench/limits``) and one reader per metric
(``bench/metrics/<metric>.py``).  A new cell, mix or metric is new files
and entries; nothing here changes.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
#: Run-time state inside the checkout, at fixed paths: the compile cache
#: and plan store are keyed by their path, so a moving path never hits.
STATE = os.path.join(BENCH, ".state")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str, bench: dict | None = None) -> dict:
    """Everything one workload needs, resolved by name."""
    bench = bench or spec()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(BENCH, "mixes", w["traffic"] + ".json"))

    def wanted(m: dict) -> bool:
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if wanted(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if wanted(m) and m["moves"] in names]
    return {"workload": w, "config": config, "mix": mix,
            "limits": load_json(os.path.join(BENCH, "limits",
                                              name + ".json")),
            "end_to_end": e2e, "per_layer": per_layer}


def entry_module(config: dict):
    return importlib.import_module(f"bench.entries.{config['entry']}")


def traffic_module(mix: dict):
    return importlib.import_module(f"bench.traffic.{mix['generator']}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run) -> float | None``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def state_dir(*parts: str) -> str:
    path = os.path.join(STATE, *parts)
    os.makedirs(path, exist_ok=True)
    return path


def prepare_environment() -> None:
    """Before JAX is imported: the compile cache and the program's own
    state live inside the checkout, and the program is importable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = state_dir("jax_cache")
    # the solver prices with a host's measured profile when one is cached
    # under this directory; none is, so every run prices alike
    os.environ["REPRO_CALIBRATION_DIR"] = state_dir("calibration")
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the directory that
    ``prepare_environment`` chose, for every program, however small."""
    from repro.codegen import enable_compile_cache as enable
    enable()


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform} "
                         f"({dev.device_kind}); this benchmark runs on the "
                         "chip only")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX finds "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "used": devices[:chips]}


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts compilations and compile-cache reads while ``active``: a
    warm-up that missed a shape shows as a count in the window."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.compiles = 0
        self.cache_reads = 0
        mon.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if not self.active:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_reads += 1


class Run:
    """What metric readers see of one run."""

    def __init__(self, c: dict, seed: int, seconds: float, trace: bool,
                 device: dict):
        self.cell = c
        self.workload = c["workload"]["name"]
        self.config = c["config"]
        self.mix = c["mix"]
        self.seed = seed
        self.seconds = seconds
        self.traced = trace
        self.device = device
        self.setup_s = 0.0
        self.record = None
        self.entry = None
        self.counters: dict = {}     # program counters: window deltas
        self.trace = None            # bench.harness.xplane.Reduced

    @property
    def peaks(self) -> dict:
        from bench.harness.peaks import peaks
        return peaks(self.device["kind"])

    def annotate(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def read_metrics(run: Run, metrics: list) -> dict:
    """Each metric's reader; a reader that finds nothing returns None and
    the metric is left out of the line, with a line on stderr that names
    it: every metric a cell lists is one its readers expect to find."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"])(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run; left "
                "out of the result")
        else:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(checks: dict) -> bool:
    """``correct``: every number compared lies within its limit."""
    return bool(checks) and all(ch["value"] <= ch["limit"]
                                for ch in checks.values())


def measure(run: Run, t_start: float) -> dict:
    """Set up, warm, measure one window, free the program, check it.
    Returns the result object (without printing it)."""
    import jax
    c = run.cell
    entry = entry_module(run.config).Entry(run.config, run.mix, run.seed,
                                           run)
    run.entry = entry
    gen = traffic_module(run.mix)
    counter = CompileCounter()
    entry.setup()
    before = entry.counters()
    trace_dir = None
    if run.traced:
        trace_dir = os.path.join(state_dir(), "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        import jax.profiler as jp
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        run.setup_s = time.perf_counter() - t_start
        jp.start_trace(trace_dir, profiler_options=opts)
    else:
        run.setup_s = time.perf_counter() - t_start
    counter.active = True
    with run.annotate("bench.window"):
        rec = gen.drive(entry, run.mix, run.seed, run.seconds, run.annotate)
    counter.active = False
    counter.close()
    if run.traced:
        jax.profiler.stop_trace()
    after = entry.counters()
    run.counters = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    entry.settle(rec, run.counters)
    run.record = rec
    log(f"window {rec.window_s:.3f}s: attempted {rec.attempted}, completed "
        f"{rec.completed}, optimized {rec.optimized}, failed {rec.failed}; "
        f"compiles in window {counter.compiles}, compile-cache reads "
        f"{counter.cache_reads}")
    for k in sorted(run.counters):
        if run.counters[k]:
            log(f"counter {k} +{run.counters[k]}")
    if rec.lateness:
        from bench.harness.stats import percentile
        log(f"generator lateness p50 "
            f"{percentile(rec.lateness, 50) * 1e3:.4f} ms, p95 "
            f"{percentile(rec.lateness, 95) * 1e3:.4f} ms")
    peak = memory_peak(run.device["used"])
    if not run.traced:
        metrics = read_metrics(run, c["end_to_end"])
    entry.close()
    gc.collect()
    checks = entry.check(rec)
    result = {"correct": judge(checks), "attempted": rec.attempted, "failed": rec.failed}
    if run.traced:
        from bench.harness import xplane
        run.trace = xplane.reduce_dir(trace_dir, len(run.device["used"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = read_metrics(run, c["per_layer"])
    result["metrics"] = metrics
    result["device"] = {"platform": run.device["platform"],
                        "kind": run.device["kind"],
                        "count": run.device["count"],
                        "memory_peak_bytes": peak}
    if run.traced:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checked"] = checks
    return result


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"cannot resolve workload {args.workload!r}: {e}")
        return 2
    prepare_environment()
    try:
        device = device_info(c["workload"]["chips"])
    except SystemExit as e:
        log(str(e))
        return 2
    enable_compile_cache()
    run = Run(c, args.seed, args.seconds, bool(args.trace), device)
    result = measure(run, t_start)
    for name, ch in result["checked"].items():
        log(f"check {name} = {ch['value']!r} (limit {ch['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
