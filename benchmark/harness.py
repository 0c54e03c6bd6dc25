"""The harness: one cell of ``BENCHMARK.json``, run and reported.

Driven by data. A cell names a configuration and a traffic mix;
everything that belongs to one of them, to one model family, one kind
of driver or one per-layer metric is a file found by name under the
benchmark's directory (``home``, the first of ``paths``):

    configs/<configuration>.json     sizes, as run (the entry's "file")
    traffic/<traffic>.json           parameters of the load
    layer_metrics/<metric>.json      layer, reader and arguments
    builders/<family>.py             configuration -> system under test
    drivers/<driver>.py              traffic -> measured window
    readers/<reader>.py              spans, counters, trace -> a number
    reference/<family>.py            the plain float32 reference

so a later PR adds cells, metrics, families and drivers as new files
plus entries in ``BENCHMARK.json`` and edits nothing that is here.
"""

import glob
import importlib.util
import json
import os
import time


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(home, kind, name):
    """``<home>/<kind>/<name>.py`` as a module, by path."""
    path = os.path.join(home, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            "no %s %r: %s is not there" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace("-", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Benchmark(object):
    """``BENCHMARK.json`` of the checkout at ``root``."""

    def __init__(self, root):
        self.root = root
        self.spec = load_json(root, "BENCHMARK.json")
        self.home = os.path.join(root, self.spec["paths"][0])

    def cell(self, name):
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError("no workload %r in BENCHMARK.json (have %s)" % (
            name, ", ".join(c["name"] for c in self.spec["workloads"])))

    def config(self, cell):
        for entry in self.spec["configs"]:
            if entry["name"] == cell["config"]:
                return load_json(self.root, entry["file"])
        raise KeyError("no configuration %r" % cell["config"])

    def traffic(self, cell):
        return load_json(self.home, "traffic", cell["traffic"] + ".json")

    def metrics(self, kind, cell):
        """The cell's metrics of ``kind`` (end_to_end or per_layer)."""
        return [m for m in self.spec[kind]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def peaks(self, device_kind):
        """The peak table's row for ``device_kind``, or None: a device
        that is not in the table is an error, never a default."""
        return load_json(self.home, "peaks.json")["devices"].get(
            device_kind)


class CompileCounter(object):
    """Counts the programs JAX builds (compiled, or fetched from the
    persistent cache) and the cache's hits and misses."""

    BUILT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.built = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == self.BUILT:
            self.built += 1

    def _event(self, event, **kwargs):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


def configure_compile_cache(root):
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), for every program
    however quick to compile, so that a second run of a cell finds all
    of them. ``JAX_COMPILATION_CACHE_DIR`` wins where it is set. The
    program (``backends._enable_persistent_compile_cache``) takes the
    directory it finds configured."""
    import jax
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(root, ".veles_cache", "benchmark_xla"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def cache_entries(cache_dir):
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def run_cell(bench, cell_name, seed, seconds, trace, devices, t0,
             log=print):
    """Run one cell on ``devices`` and return the contract's object.
    ``t0`` is the process's start on ``time.time``; ``log`` takes the
    lines that go before the last."""
    import jax

    cell = bench.cell(cell_name)
    config, traffic = bench.config(cell), bench.traffic(cell)
    builder = load_module(bench.home, "builders", config["family"])
    reference = load_module(bench.home, "reference", config["family"])
    driver = load_module(bench.home, "drivers", traffic["driver"])
    devices = list(devices)[:cell["chips"]]
    first = devices[0]
    peaks = bench.peaks(first.device_kind)
    counter = CompileCounter()
    cache_dir = jax.config.jax_compilation_cache_dir
    entries_before = cache_entries(cache_dir) if cache_dir else 0

    trace_dir = None
    if trace:
        trace_dir = os.path.join(
            bench.root, ".veles_cache", "benchmark_trace", cell["name"])
        for stale in glob.glob(os.path.join(
                trace_dir, "**", "*.xplane.pb"), recursive=True):
            os.remove(stale)

    system = builder.build(config, traffic, seed, devices, reference, log)
    record = driver.run(system, traffic, seconds, trace_dir,
                        lambda: counter.built, reference, log)
    setup_s = record["window_start"] - t0
    log("set-up: %.1f s; programs built %d (cache hits %d, misses %d); "
        "cache entries %d -> %d in %s" % (
            setup_s, counter.built, counter.hits, counter.misses,
            entries_before, cache_entries(cache_dir) if cache_dir else 0,
            cache_dir))

    checks = dict(record["checks"])
    checks["platform_is_tpu"] = first.platform == "tpu"
    checks["device_in_peak_table"] = peaks is not None
    checks["chips_as_cell"] = len(devices) == cell["chips"]
    log("checks: %s" % json.dumps(checks, sort_keys=True))

    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    result = {"correct": all(checks.values()),
              "attempted": record["attempted"],
              "failed": record["failed"]}
    if not trace:
        declared = bench.metrics("end_to_end", cell)
        values = dict(record["end_to_end"], setup_s=setup_s)
    else:
        declared = bench.metrics("per_layer", cell)
        t_reduce = time.perf_counter()
        from benchmark import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir)
        context = {
            "counters": dict(
                record["counters"],
                peak_flops_per_s=(peaks or {}).get("bf16_flops_per_s", 0.0)
                * len(devices)),
            "trace": reduced, "traced": record["traced"],
            "config": config, "peaks": peaks, "chips": len(devices),
            "log": log}
        values = {}
        for metric in declared:
            spec = load_json(bench.home, "layer_metrics",
                             metric["name"] + ".json")
            reader = load_module(bench.home, "readers", spec["reader"])
            value = reader.read(context, **spec["args"])
            if value is not None:
                values[metric["name"]] = value
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            result["breakdown"] = reduced.breakdown()
        log("trace reduced in %.1f s" % (time.perf_counter() - t_reduce))
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in declared if m["name"] in values}
    result["device"] = device
    return result
