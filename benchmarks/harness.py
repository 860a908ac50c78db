"""The harness: resolves a cell's files by name, runs its runner, reads
its metrics through their readers and builds the contract's result
object.  Nothing here knows a cell, a model or a metric by name."""
import contextlib
import importlib
import json
import os
import sys
import time
import types

T_START = time.perf_counter()      # run.py overwrites it with its own
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, "out")            # git-ignored: traces

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest():
    return load_json(ROOT, "BENCHMARK.json")


def resolve(manifest, workload):
    """Everything a cell names, found by name: the cell, its config and
    traffic files, and the runner and family modules."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic,
        family=importlib.import_module(
            f"benchmarks.families.{config['family']}"),
        runner=importlib.import_module(
            f"benchmarks.runners.{traffic['runner']}"))


def metric_specs(manifest, kind, workload):
    """The manifest's metrics of one kind (``end_to_end`` or
    ``per_layer``) that this cell reports, each with its own file."""
    folder = "end_to_end" if kind == "end_to_end" else "layer_metrics"
    specs = []
    for m in manifest[kind]:
        if workload not in m.get("workloads", [workload]):
            continue
        spec = load_json(HERE, folder, m["name"] + ".json")
        specs.append((m, spec))
    return specs


def read_metrics(specs, run):
    """Each metric through its reader.  A reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for m, spec in specs:
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Compiles:
    """Counts XLA backend compilations (chip_smoke.py's listener)."""

    def __init__(self):
        self.n, self.seconds = 0, 0.0

    def listen(self):
        import jax

        def on_compile(event, duration, **_):
            if event == _BACKEND_COMPILE:
                self.n += 1
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(on_compile)
        return self


class Context:
    """What a runner gets: the cell's data, the seed, the clock, the
    compile counter and the tracing switches."""

    def __init__(self, resolved, seed, seconds, trace, tag):
        self.cell, self.config = resolved.cell, resolved.config
        self.traffic, self.family = resolved.traffic, resolved.family
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.clock = time.perf_counter
        self.compiles = Compiles().listen()
        self.trace_dir = os.path.join(OUT_DIR, tag)

    def setup_done(self):
        """Call when the window opens: returns ``setup_s``."""
        return self.clock() - T_START

    @staticmethod
    def span(name):
        """A host span on the profiler's clock (``bench:<name>``)."""
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name)

    @contextlib.contextmanager
    def device_trace(self):
        """Profile what runs inside; afterwards ``self.trace_file`` is
        the ``.xplane.pb``.  The traced stretch is wrapped in a
        ``bench:window`` span, which the reduction takes as its window."""
        import glob
        import shutil

        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        try:
            with self.span("window"):
                yield
        finally:
            jax.profiler.stop_trace()
        (self.trace_file,) = glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))


def device_object():
    """The contract's ``device``, as JAX reports it.  The peak is that
    of the fullest chip: its live buffers at their highest
    (``peak_bytes_in_use``) plus what the runtime reserved for loaded
    programs' temporaries (``peak_bytes_reserved``), which the first
    figure leaves out (PERF.md section 6, PR 23).  ``memory`` holds the
    runtime's own figures and is popped into a free-form line."""
    import jax
    devs = jax.devices()

    def peak(stats):
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)
    stats = max(((d.memory_stats() or {}) for d in devs), key=peak)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak(stats)),
            "memory": stats}


def summary(values):
    """A list of samples in one free-form line."""
    import numpy as np
    lo, p5, p50, p95, hi = np.percentile(values, [0, 5, 50, 95, 100])
    return {"n": len(values), "min": lo, "p5": p5, "p50": p50,
            "p95": p95, "max": hi}


def require_tpu(chips):
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"the benchmark measures a TPU; JAX found "
                 f"{dev.platform!r} ({dev.device_kind})")
    if jax.device_count() != chips:
        sys.exit(f"the cell asks for {chips} chip(s); JAX sees "
                 f"{jax.device_count()}")
    peaks = load_json(HERE, "peaks.json")
    if dev.device_kind not in peaks:
        sys.exit(f"no peaks on record for {dev.device_kind!r}: add them "
                 "to benchmarks/peaks.json with their source")


def run_cell(workload, seed, seconds, trace, shrink=None):
    """Run one cell and return the contract's result object (and print
    the free-form lines).  ``shrink`` is the tests' entry, and the
    command has no way to it: ``{"config": {...}, "traffic": {...}}``
    laid over the cell's files for a tiny model on the CPU, and no
    device check."""
    manifest = load_manifest()
    resolved = resolve(manifest, workload)
    if shrink is None:
        require_tpu(resolved.cell["chips"])
    else:
        resolved.config = {**resolved.config, **shrink.get("config", {})}
        resolved.traffic = {**resolved.traffic, **shrink.get("traffic", {})}
    from paddle_tpu.device.compile_cache import ensure_compile_cache
    ensure_compile_cache()         # JAX_COMPILATION_CACHE_DIR, else .jax_cache/
    ctx = Context(resolved, seed, seconds, trace, f"{workload}.{seed}")
    run = resolved.runner.run(ctx)
    run["peaks"] = {**load_json(HERE, "peaks.json"),
                    **(shrink or {}).get("peaks", {})}
    run["config"], run["family"] = resolved.config, resolved.family
    run["traffic"] = resolved.traffic
    device = run["device"]
    if trace:
        from benchmarks import trace_reduce
        reduced = trace_reduce.reduce(ctx.trace_file)
        run["trace"] = reduced
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    kind = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(metric_specs(manifest, kind, workload), run)
    for line in run.get("info", []) + [
            {"memory": device.pop("memory")},
            {"samples": {k: summary(v) for k, v in run["samples"].items()
                         if isinstance(v, list) and v}}]:
        print(json.dumps(line), flush=True)
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]),
              "metrics": metrics, "device": device,
              "workload": workload, "seed": seed}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    return result


