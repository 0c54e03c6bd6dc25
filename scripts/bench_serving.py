#!/usr/bin/env python3
"""Serving benchmarks: batching engine, elastic scaling, cache, QoS.

``--scenario`` picks the regime (ISSUE 14 acceptance bars in bold):

* ``baseline`` (default) — the ISSUE 3 contract: at >= 32 concurrent
  HTTP clients the batched engine must deliver **>= 3x** the legacy
  sequential single-request throughput on the MNIST FC forward, and
  under 2x sustained capacity the overload path must 503 (never
  deadlock). Cache OFF so the engine itself is measured.
* ``burst`` — a **10x arrival-rate burst** against an autoscaling
  pool (min 1, max 4): sustained p95 must stay bounded, **zero
  clients hang**, and the autoscale reaction time (breach -> warmed
  replica serving) is measured from the registry histogram.
* ``diurnal`` — a ramp up/down client wave: the pool must grow with
  the wave and drain back down after it, zero hung clients.
* ``cache`` — repeat-heavy traffic (16 hot inputs) with the result
  cache on vs off: **>= 5x throughput** on the same traffic, and the
  cached responses are **bit-identical** to computed ones.
* ``multitenant`` — a greedy tenant (24 closed-loop clients) against
  a light tenant (2 clients) with equal weights: the greedy tenant
  sheds onto itself; the light tenant's requests keep flowing with a
  far lower shed rate.

The load generator always runs in a CHILD process (its own GIL; an
in-process generator would steal the server's interpreter lock and
measure itself). The child reads a JSON spec on stdin — phases of
``{seconds, clients, bodies, headers, path}`` — and prints per-phase
``{counts, elapsed, p50_ms, p95_ms}``; concurrent tenant groups are
separate child processes.

Usage: python scripts/bench_serving.py [--scenario S] [--quick] ...
Prints a markdown row + JSON blob (recorded in docs/PERF.md). Asks for
the TPU by name and fails without one; CI's structural legs pass
``--backend cpu`` (every figure recorded so far is from such a run).
"""

import argparse
import base64
import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

#: backend asked for by name (``--backend``): never ``auto``, so a
#: run without a chip fails instead of timing the CPU
BACKEND = "tpu"


def _build_model(layers=(4096, 4096)):
    """A serving-scale MNIST MLP (784 -> 4096 -> 4096 -> 10).

    The config-1 topology's 784x100 forward is ~0.2 ms — at that size
    any HTTP benchmark measures the Python request plumbing, not the
    engine. The wide variant's batch-1 forward is a few ms (real
    per-request model work to amortize), and XLA releases the GIL
    while it runs, so request handling overlaps compute exactly as in
    production."""
    import numpy

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.datasets import golden_digits
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistWorkflow
    from veles_tpu.serving.model_store import ServeableModel
    prng.get().seed(1234)
    prng.get("loader").seed(1235)
    wf = MnistWorkflow(DummyLauncher(),
                       provider=golden_digits(n_train=600, n_valid=120),
                       layers=tuple(layers), minibatch_size=100,
                       max_epochs=1)
    wf.initialize(device=Device(backend=BACKEND))
    sample = numpy.zeros(wf.loader.minibatch_data.shape[1:],
                         numpy.float32).ravel()
    return ServeableModel.from_workflow(wf, name="mnist-fc"), sample


def _b64_body(sample, rid=None):
    body = {"input": base64.b64encode(
        sample.astype("float32").tobytes()).decode(),
        "codec": "base64", "shape": [sample.size], "type": "float32"}
    if rid is not None:
        body["id"] = rid
    return json.dumps(body)


def _hot_bodies(sample, n=16):
    """n distinct hot inputs: deterministic perturbations of the
    probe sample, so repeat-heavy traffic has a small key space."""
    import numpy
    rng = numpy.random.RandomState(7)
    return [_b64_body(sample + rng.rand(sample.size)
                      .astype(numpy.float32))
            for _ in range(n)]


# -- the child-process load generator ---------------------------------------


class _Client(object):
    """Persistent keep-alive client (what any real load driver uses —
    a fresh TCP connect per request would measure the kernel's SYN
    queue, not the serving engine)."""

    def __init__(self, port, timeout=60):
        import http.client
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)
        self.port = port
        self.timeout = timeout

    def post(self, body, path="/api", headers=None):
        try:
            h = {"Content-Type": "application/json"}
            if headers:
                h.update(headers)
            self.conn.request("POST", path, body=body, headers=h)
            resp = self.conn.getresponse()
            resp.read()
            return resp.status
        except Exception:
            try:
                self.conn.close()
            except Exception:
                pass
            import http.client as hc
            self.conn = hc.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.timeout)
            return -1

    def close(self):
        self.conn.close()


def _client_worker(port):
    """Load-generator body — runs inside a CHILD process (its own
    GIL). Reads the phase spec from stdin, prints per-phase results."""
    import collections
    import random

    spec = json.loads(sys.stdin.read())
    out = []
    for phase in spec["phases"]:
        bodies = phase["bodies"]
        path = phase.get("path", "/api")
        headers = phase.get("headers") or {}
        outcomes = collections.Counter()
        latencies = []
        lock = threading.Lock()
        stop = threading.Event()

        def worker(seed):
            rng = random.Random(seed)
            client = _Client(port)
            while not stop.is_set():
                body = bodies[rng.randrange(len(bodies))] \
                    if len(bodies) > 1 else bodies[0]
                t0 = time.perf_counter()
                status = client.post(body, path=path, headers=headers)
                dt = (time.perf_counter() - t0) * 1000.0
                with lock:
                    outcomes[status] += 1
                    latencies.append(dt)
            client.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(phase["clients"])]
        start = time.time()
        for t in threads:
            t.start()
        time.sleep(phase["seconds"])
        stop.set()
        for t in threads:
            t.join(timeout=90)
        elapsed = time.time() - start
        latencies.sort()

        def pct(q):
            if not latencies:
                return 0.0
            return latencies[min(len(latencies) - 1,
                                 int(q / 100.0 * len(latencies)))]

        out.append({"counts": {str(k): v
                               for k, v in outcomes.items()},
                    "elapsed": elapsed, "p50_ms": round(pct(50), 2),
                    "p95_ms": round(pct(95), 2)})
    print(json.dumps(out))


def _spawn(port, phases):
    """Start the load child; returns the Popen (stdin already fed)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--client-worker",
         str(port)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    proc.stdin.write(json.dumps({"phases": phases}).encode())
    proc.stdin.close()
    return proc


def _collect(proc, timeout):
    out = proc.stdout.read()
    rc = proc.wait(timeout=timeout)
    if rc != 0:
        raise RuntimeError("load child exited %d" % rc)
    return json.loads(out)


def _run_phases(port, phases):
    total = sum(p["seconds"] for p in phases)
    return _collect(_spawn(port, phases), timeout=total + 120)


def _qps(phase_result, status=200):
    return phase_result["counts"].get(str(status), 0) / \
        phase_result["elapsed"]


def _hung(phase_results):
    return sum(r["counts"].get("-1", 0) for r in phase_results)


# -- scenario: baseline (the PR 3 contract) ---------------------------------


def _start_legacy_service(model):
    """The pre-serving stack this engine replaces: RESTfulAPI +
    RestfulLoader with the reference's one-request-one-dispatch
    contract, serving the SAME weights — the honest baseline for the
    ISSUE 3 >= 3x bar."""
    import threading as _threading

    import numpy

    from veles_tpu.accelerated_units import AcceleratedWorkflow
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.restful import RestfulLoader
    from veles_tpu.nn.all2all import All2AllSoftmax, All2AllTanh
    from veles_tpu.plumbing import Repeater
    from veles_tpu.restful_api import RESTfulAPI

    wf = AcceleratedWorkflow(DummyLauncher())
    repeater = Repeater(wf)
    repeater.link_from(wf.start_point)
    loader = RestfulLoader(wf, sample_shape=model.sample_shape,
                           feed_timeout=60)
    loader.link_from(repeater)
    prev, prev_attr = loader, "minibatch_data"
    units = []
    for i, (_, params) in enumerate(model.layers):
        width = params["weights"].shape[1]
        cls = All2AllSoftmax if i == len(model.layers) - 1 else All2AllTanh
        unit = cls(wf, output_sample_shape=(width,), name="l%d" % i)
        unit.link_from(prev)
        unit.link_attrs(prev, ("input", prev_attr))
        # serve the same trained weights the engine serves
        unit.weights.reset(numpy.array(params["weights"]))
        if "bias" in params:
            unit.bias.reset(numpy.array(params["bias"]))
        units.append(unit)
        prev, prev_attr = unit, "output"
    api = RESTfulAPI(wf, port=0, response_timeout=60)
    api.link_from(prev)
    api.link_attrs(prev, ("input", "output"))
    api.feed = loader.feed
    repeater.link_from(api)
    wf.initialize(device=Device(backend=BACKEND))
    thread = _threading.Thread(target=wf.run, daemon=True)
    thread.start()

    def stop():
        loader.finish()
        thread.join(timeout=30)
        api.stop()

    return api.address[1], stop


def run_baseline(quick=False, clients=32, replicas=1, max_batch=64,
                 window_ms=2.0):
    from veles_tpu.serving.frontend import ServingFrontend

    model, sample = _build_model()
    body = _b64_body(sample)
    seconds = 2.0 if quick else 8.0
    # baseline: the legacy one-request-one-dispatch service (its
    # natural mode is a sequential client; concurrency only queues
    # inside it) serving the same weights
    legacy_port, legacy_stop = _start_legacy_service(model)
    try:
        legacy = _run_phases(legacy_port, [
            {"seconds": 0.5, "clients": 1, "bodies": [body]},   # warm
            {"seconds": seconds, "clients": 1, "bodies": [body]}])[1]
    finally:
        legacy_stop()
    # cache OFF: this scenario measures the batching engine itself
    frontend = ServingFrontend(
        model, port=0, replicas=replicas, max_batch_size=max_batch,
        batch_timeout_ms=window_ms, max_queue=max(4 * clients, 128),
        response_timeout=60, cache_mb=0).start()
    try:
        results = _run_phases(frontend.port, [
            {"seconds": 0.5, "clients": 1, "bodies": [body]},   # warm
            {"seconds": seconds, "clients": 1, "bodies": [body]},
            {"seconds": seconds, "clients": clients, "bodies": [body]}])
        seq, conc = results[1], results[2]
        snap = frontend.metrics.snapshot()
    finally:
        frontend.stop()
    # overload regime: the admission bound is SMALLER than the burst
    # (that is when 503-shedding must engage), one replica so the
    # backlog builds under 2x+ sustained offered load
    overload_queue = 16
    overload_fe = ServingFrontend(
        model, port=0, replicas=1, max_batch_size=max_batch,
        batch_timeout_ms=window_ms, max_queue=overload_queue,
        response_timeout=60, warm=False, cache_mb=0).start()
    try:
        over = _run_phases(overload_fe.port, [
            {"seconds": max(seconds / 2, 2.0),
             "clients": 2 * overload_queue, "bodies": [body]}])[0]
    finally:
        overload_fe.stop()
    counts = {int(k): v for k, v in over["counts"].items()}
    ok, shed = counts.get(200, 0), counts.get(503, 0)
    hung = counts.get(-1, 0)
    total = sum(counts.values())
    overload = {"offered": total, "ok": ok, "shed_503": shed,
                "other": total - ok - shed - hung, "hung": hung}
    legacy_qps = _qps(legacy)
    result = {
        "scenario": "baseline",
        "legacy_sequential_qps": round(legacy_qps, 1),
        "sequential_qps": round(_qps(seq), 1),
        "concurrent_qps": round(_qps(conc), 1),
        "clients": clients,
        "speedup": round(_qps(conc) / max(legacy_qps, 1e-9), 2),
        "engine_speedup_vs_own_sequential": round(
            _qps(conc) / max(_qps(seq), 1e-9), 2),
        "replicas": replicas,
        "max_batch_size": max_batch,
        "batch_timeout_ms": window_ms,
        "mean_batch_size": snap["batches"]["mean_size"],
        "p95_ms": snap["endpoints"]["/api"]["p95_ms"],
        "overload": overload,
    }
    result["pass_speedup_3x"] = result["speedup"] >= 3.0
    result["pass_overload"] = (overload["shed_503"] > 0 and
                               overload["hung"] == 0 and
                               overload["other"] == 0)
    result["pass"] = result["pass_speedup_3x"] and result["pass_overload"]
    return result


# -- scenario: burst (10x arrival-rate step, autoscaling pool) --------------


def _autoscaled_frontend(model, max_queue=512, max_replicas=4,
                         fast_down=False):
    from veles_tpu.serving.frontend import ServingFrontend
    fe = ServingFrontend(
        model, port=0, replicas=1, max_batch_size=32,
        batch_timeout_ms=2.0, max_queue=max_queue, response_timeout=60,
        cache_mb=0, min_replicas=1, max_replicas=max_replicas,
        autoscale_interval_s=0.1)
    for entry in fe.entries.values():
        scaler = entry.autoscaler
        # the Python HTTP layer caps closed-loop qps well below the
        # engine's service rate on a CPU CI box, so the engine queue
        # stays shallow even under a 10x burst — the bench threshold
        # sits between the base (~1 outstanding) and burst (~4-6
        # outstanding) regimes instead of the production default
        scaler.up_queue_per_replica = 3.0
        scaler.up_for_s = 0.2           # bursts scale up FAST
        scaler.up_cooldown_s = 0.5
        if fast_down:                   # diurnal bench wants to SEE
            scaler.down_idle_for_s = 2.0   # the shrink inside its
            scaler.down_cooldown_s = 2.0   # measurement window
    return fe.start()


def _reaction_stats():
    from veles_tpu.telemetry.registry import get_registry
    hist = get_registry().get("veles_autoscale_reaction_s")
    if hist is None:
        return None
    series = hist.series()
    if not series or not any(c.count for _, c in series):
        return None
    child = max((c for _, c in series), key=lambda c: c.count)
    return {"count": child.count,
            "mean_s": round(child.sum / child.count, 3),
            "p95_s": round(child.percentile(95), 3)}


def run_burst(quick=False, base_clients=2, burst_factor=10):
    model, sample = _build_model()
    body = _b64_body(sample)
    base_s = 3.0 if quick else 8.0
    # the burst phase must OUTLAST the scale-up reaction: the new
    # replica warms every bucket before serving (the honest cold-start
    # cost the reaction metric exists to measure — ~seconds for the
    # wide model on CPU), so a burst shorter than that never observes
    # the grown pool
    burst_s = 10.0 if quick else 15.0
    fe = _autoscaled_frontend(model)
    try:
        phases = [
            {"seconds": 1.0, "clients": 1, "bodies": [body]},   # warm
            {"seconds": base_s, "clients": base_clients,
             "bodies": [body]},
            {"seconds": burst_s, "clients":
             base_clients * burst_factor, "bodies": [body]},
            {"seconds": max(base_s / 2, 2.0), "clients": base_clients,
             "bodies": [body]},
        ]
        results = _run_phases(fe.port, phases)
        # a scale-up committed during the burst may still be warming
        # (on a CPU CI box the wide model's bucket sweep takes longer
        # than the burst; on a real accelerator it lands in-burst) —
        # let it finish so the reaction time is recorded, but bail
        # fast when the burst never tripped the scaler at all
        deadline = time.monotonic() + 45.0
        scaler = fe.autoscaler
        while time.monotonic() < deadline and fe.pool.size() < 2:
            if scaler._breach_since is None and scaler._last_up is None:
                break               # nothing pending
            time.sleep(0.2)
        peak_replicas = fe.pool.size()
        reaction = _reaction_stats()
    finally:
        fe.stop()
    base, burst, after = results[1], results[2], results[3]
    result = {
        "scenario": "burst",
        "burst_factor": burst_factor,
        "base_qps": round(_qps(base), 1),
        "base_p95_ms": base["p95_ms"],
        "burst_qps": round(_qps(burst), 1),
        "burst_p95_ms": burst["p95_ms"],
        "after_p95_ms": after["p95_ms"],
        "burst_shed_503": burst["counts"].get("503", 0),
        "hung": _hung(results),
        "replicas_at_peak": peak_replicas,
        "autoscale_reaction": reaction,
    }
    # bounded: the burst p95 must stay within an order of magnitude of
    # the base p95 (closed-loop clients mean the queue can't run away;
    # what kills you without scaling is p95 exploding to the timeout)
    result["pass_p95_bounded"] = (
        burst["p95_ms"] <= max(10.0 * max(base["p95_ms"], 1.0), 500.0))
    result["pass_zero_hung"] = result["hung"] == 0
    result["pass_scaled_up"] = peak_replicas > 1 and reaction is not None
    result["pass"] = (result["pass_p95_bounded"] and
                      result["pass_zero_hung"] and
                      result["pass_scaled_up"])
    return result


# -- scenario: diurnal (ramp up, ramp down, pool follows) -------------------


def run_diurnal(quick=False):
    model, sample = _build_model()
    body = _b64_body(sample)
    dwell = 2.0 if quick else 5.0
    wave = [1, 4, 12, 20, 12, 4, 1]
    fe = _autoscaled_frontend(model, fast_down=True)
    try:
        sizes = []
        stop = threading.Event()

        def sampler():
            while not stop.wait(0.25):
                sizes.append(fe.pool.size())

        thread = threading.Thread(target=sampler, daemon=True)
        thread.start()
        results = _run_phases(fe.port, [
            {"seconds": dwell, "clients": n, "bodies": [body]}
            for n in wave])
        # the quiet tail: first wait out any scale-up still warming
        # (committed mid-wave, finishing after it on a CPU box), then
        # give the (bench-tuned) scale-down window a chance to drain
        # the pool back toward min
        deadline = time.monotonic() + 90.0
        scaler = fe.autoscaler
        while time.monotonic() < deadline and fe.pool.size() < 2:
            if scaler._breach_since is None and scaler._last_up is None:
                break               # the wave never tripped the scaler
            time.sleep(0.2)
        sizes.append(fe.pool.size())
        while time.monotonic() < deadline and fe.pool.size() > 1:
            time.sleep(0.5)
        stop.set()
        thread.join(timeout=5)
        final_replicas = fe.pool.size()
        peak_replicas = max(sizes + [final_replicas]) if sizes else 1
        reaction = _reaction_stats()
    finally:
        fe.stop()
    result = {
        "scenario": "diurnal",
        "wave_clients": wave,
        "qps_per_phase": [round(_qps(r), 1) for r in results],
        "p95_per_phase_ms": [r["p95_ms"] for r in results],
        "hung": _hung(results),
        "replicas_peak": peak_replicas,
        "replicas_final": final_replicas,
        "autoscale_reaction": reaction,
    }
    result["pass_zero_hung"] = result["hung"] == 0
    result["pass_scaled_up"] = peak_replicas > 1
    result["pass_scaled_down"] = final_replicas < peak_replicas
    result["pass"] = (result["pass_zero_hung"] and
                      result["pass_scaled_up"] and
                      result["pass_scaled_down"])
    return result


# -- scenario: cache (repeat-heavy traffic, on vs off) ----------------------


def _engine_throughput(model, rows, clients, seconds, cache):
    """Closed-loop submit/wait directly against the DynamicBatcher —
    the layer the cache actually removes work from. (On a CPU CI box
    the Python ``http.server`` frontend caps out near a few hundred
    qps regardless of compute, which HIDES the cache win behind
    request plumbing; the HTTP legs below are still reported so the
    end-to-end effect stays visible.)"""
    from veles_tpu.serving.engine import DynamicBatcher, EngineOverloaded
    from veles_tpu.serving.replica import ReplicaPool
    # warm=True: every bucket compiles through the staging-ring sweep
    # BEFORE the window — a cold bucket compiling mid-measurement
    # (seconds for the wide model) would swamp either leg
    pool = ReplicaPool(model, n_replicas=1, max_batch_size=32,
                       warm=True)
    batcher = DynamicBatcher(pool, batch_timeout_ms=2.0,
                             max_queue=max(4 * clients, 128),
                             cache=cache)
    import random
    done = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def worker(seed):
        rng = random.Random(seed)
        while not stop.is_set():
            try:
                batcher.submit(rows[rng.randrange(len(rows))]) \
                    .result(timeout=60)
            except EngineOverloaded:
                continue
            with lock:
                done[0] += 1

    try:
        # settle: pay every bucket's compile before the timed window
        for row in rows:
            batcher.submit(row).result(timeout=120)
        if cache is not None:
            cache.invalidate()          # the timed window re-earns hits
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=90)
        elapsed = time.perf_counter() - t0
    finally:
        batcher.stop()
        pool.stop()
    return done[0] / elapsed


def run_cache(quick=False, clients=16, hot_inputs=16):
    import numpy

    from veles_tpu.serving.cache import ResultCache
    from veles_tpu.serving.engine import DynamicBatcher
    from veles_tpu.serving.frontend import ServingFrontend
    from veles_tpu.serving.replica import ReplicaPool

    model, sample = _build_model()
    bodies = _hot_bodies(sample, n=hot_inputs)
    rng = numpy.random.RandomState(7)
    rows = [sample + rng.rand(sample.size).astype(numpy.float32)
            for _ in range(hot_inputs)]    # the same hot set, decoded
    seconds = 2.0 if quick else 8.0

    # headline: engine-level throughput on the same repeat-heavy
    # traffic, cache off vs on — what the accelerator is spared
    engine_off = _engine_throughput(model, rows, clients, seconds,
                                    cache=None)
    on_cache = ResultCache(model="cache-bench")
    engine_on = _engine_throughput(model, rows, clients, seconds,
                                   cache=on_cache)
    engine_stats = on_cache.stats()

    # end-to-end: the same traffic through the HTTP frontend
    def measure_http(cache_mb):
        fe = ServingFrontend(
            model, port=0, replicas=1, max_batch_size=32,
            batch_timeout_ms=2.0, max_queue=max(4 * clients, 128),
            response_timeout=60, cache_mb=cache_mb).start()
        try:
            _run_phases(fe.port, [{"seconds": 0.5, "clients": 1,
                                   "bodies": bodies}])        # warm
            phase = _run_phases(fe.port, [
                {"seconds": seconds, "clients": clients,
                 "bodies": bodies}])[0]
        finally:
            fe.stop()
        return phase

    http_off = measure_http(cache_mb=0)
    http_on = measure_http(cache_mb=64)

    # bit-identity: the cached answer IS the computed answer — submit
    # the same row twice through a live engine and compare raw arrays
    pool = ReplicaPool(model, n_replicas=1, max_batch_size=8,
                       warm=False)
    batcher = DynamicBatcher(pool, batch_timeout_ms=1, max_queue=32,
                             cache=ResultCache(model="cache-bit"))
    try:
        x = sample + 0.25
        computed = batcher.submit(x).result(timeout=60)
        cached = batcher.submit(x).result(timeout=60)
        bit_identical = bool(numpy.array_equal(computed, cached))
    finally:
        batcher.stop()
        pool.stop()
    result = {
        "scenario": "cache",
        "clients": clients,
        "hot_inputs": hot_inputs,
        "engine_qps_cache_off": round(engine_off, 1),
        "engine_qps_cache_on": round(engine_on, 1),
        "speedup": round(engine_on / max(engine_off, 1e-9), 2),
        "engine_hit_ratio": engine_stats["hit_ratio"],
        "http_qps_cache_off": round(_qps(http_off), 1),
        "http_qps_cache_on": round(_qps(http_on), 1),
        "http_speedup": round(_qps(http_on) /
                              max(_qps(http_off), 1e-9), 2),
        "http_p95_off_ms": http_off["p95_ms"],
        "http_p95_on_ms": http_on["p95_ms"],
        "bit_identical": bit_identical,
        "hung": _hung([http_off, http_on]),
    }
    result["pass_speedup_5x"] = result["speedup"] >= 5.0
    result["pass_http_improves"] = (
        _qps(http_on) >= _qps(http_off) and
        http_on["p95_ms"] <= http_off["p95_ms"] * 1.1)
    result["pass"] = (result["pass_speedup_5x"] and bit_identical and
                      result["pass_http_improves"] and
                      result["hung"] == 0)
    return result


# -- scenario: multitenant (greedy vs light, weighted fairness) -------------


def run_multitenant(quick=False, greedy_clients=24, light_clients=2):
    from veles_tpu.serving.frontend import ServingFrontend

    model, sample = _build_model()
    body = _b64_body(sample)
    seconds = 3.0 if quick else 8.0
    fe = ServingFrontend(
        model, port=0, replicas=1, max_batch_size=16,
        batch_timeout_ms=2.0, max_queue=32, response_timeout=60,
        cache_mb=0,
        tenants={"greedy": {"weight": 1.0},
                 "light": {"weight": 1.0, "qos": "interactive"}},
    ).start()
    try:
        _run_phases(fe.port, [{"seconds": 0.5, "clients": 1,
                               "bodies": [body],
                               "headers": {"X-Tenant": "light"}}])
        greedy_proc = _spawn(fe.port, [
            {"seconds": seconds, "clients": greedy_clients,
             "bodies": [body], "headers": {"X-Tenant": "greedy"}}])
        light_proc = _spawn(fe.port, [
            {"seconds": seconds, "clients": light_clients,
             "bodies": [body], "headers": {"X-Tenant": "light"}}])
        greedy = _collect(greedy_proc, timeout=seconds + 120)[0]
        light = _collect(light_proc, timeout=seconds + 120)[0]
        tenants = fe.engine.admission.stats()["tenants"]
    finally:
        fe.stop()

    def shed_rate(phase):
        ok = phase["counts"].get("200", 0)
        shed = phase["counts"].get("503", 0)
        return shed / max(ok + shed, 1)

    result = {
        "scenario": "multitenant",
        "greedy_clients": greedy_clients,
        "light_clients": light_clients,
        "greedy_qps": round(_qps(greedy), 1),
        "light_qps": round(_qps(light), 1),
        "greedy_shed_rate": round(shed_rate(greedy), 3),
        "light_shed_rate": round(shed_rate(light), 3),
        "light_p95_ms": light["p95_ms"],
        "hung": _hung([greedy, light]),
        "tenants": {name: {k: t[k] for k in
                           ("qos", "share", "admitted", "shed")}
                    for name, t in tenants.items()},
    }
    # the fairness bar: the light tenant keeps flowing — its shed rate
    # is a fraction of the greedy tenant's, and it actually got served
    result["pass_light_served"] = _qps(light) > 0
    result["pass_fair"] = (result["light_shed_rate"] <=
                           max(0.5 * result["greedy_shed_rate"], 0.05))
    result["pass_zero_hung"] = result["hung"] == 0
    result["pass"] = (result["pass_light_served"] and
                      result["pass_fair"] and result["pass_zero_hung"])
    return result


# -- driver ------------------------------------------------------------------


SCENARIOS = {
    "baseline": run_baseline,
    "burst": run_burst,
    "diurnal": run_diurnal,
    "cache": run_cache,
    "multitenant": run_multitenant,
}


def run(quick=False, clients=32, replicas=1, max_batch=64,
        window_ms=2.0):
    """Back-compat entry (bench_all.py): the baseline scenario."""
    return run_baseline(quick=quick, clients=clients, replicas=replicas,
                        max_batch=max_batch, window_ms=window_ms)


def markdown_row(r):
    scenario = r.get("scenario", "baseline")
    if scenario == "baseline":
        return ("| serving mnist-fc | %.0f legacy / %.0f engine seq | "
                "%.0f @%d clients | %.1fx | mean batch %.1f | p95 %.1f "
                "ms | 503s %d / hung %d |" %
                (r["legacy_sequential_qps"], r["sequential_qps"],
                 r["concurrent_qps"], r["clients"], r["speedup"],
                 r["mean_batch_size"], r["p95_ms"],
                 r["overload"]["shed_503"], r["overload"]["hung"]))
    if scenario == "burst":
        reaction = r["autoscale_reaction"] or {}
        return ("| serving burst %dx | %.0f -> %.0f qps | p95 %.1f -> "
                "%.1f ms | replicas %d | react %.2fs | hung %d |" %
                (r["burst_factor"], r["base_qps"], r["burst_qps"],
                 r["base_p95_ms"], r["burst_p95_ms"],
                 r["replicas_at_peak"], reaction.get("mean_s", -1),
                 r["hung"]))
    if scenario == "diurnal":
        return ("| serving diurnal %s | replicas peak %d final %d | "
                "p95 max %.1f ms | hung %d |" %
                ("/".join(str(n) for n in r["wave_clients"]),
                 r["replicas_peak"], r["replicas_final"],
                 max(r["p95_per_phase_ms"]), r["hung"]))
    if scenario == "cache":
        return ("| serving cache %d hot | engine %.0f -> %.0f qps "
                "(%.1fx, hit %.0f%%) | http %.0f -> %.0f qps | "
                "bit-identical %s | hung %d |" %
                (r["hot_inputs"], r["engine_qps_cache_off"],
                 r["engine_qps_cache_on"], r["speedup"],
                 100 * r["engine_hit_ratio"], r["http_qps_cache_off"],
                 r["http_qps_cache_on"], r["bit_identical"],
                 r["hung"]))
    if scenario == "multitenant":
        return ("| serving multitenant %d vs %d | greedy %.0f qps "
                "shed %.0f%% | light %.0f qps shed %.0f%% p95 %.1f ms "
                "| hung %d |" %
                (r["greedy_clients"], r["light_clients"],
                 r["greedy_qps"], 100 * r["greedy_shed_rate"],
                 r["light_qps"], 100 * r["light_shed_rate"],
                 r["light_p95_ms"], r["hung"]))
    return "| %s | (unknown scenario) |" % scenario


def main():
    global BACKEND
    if len(sys.argv) > 1 and sys.argv[1] == "--client-worker":
        _client_worker(int(sys.argv[2]))
        return 0
    parser = argparse.ArgumentParser()
    parser.add_argument("--scenario", default="baseline",
                        choices=sorted(SCENARIOS))
    parser.add_argument("--quick", action="store_true",
                        help="short windows (CI smoke)")
    parser.add_argument("--clients", type=int, default=32)
    parser.add_argument("--replicas", type=int, default=1,
                        help="1 by default: on small hosts two "
                             "replicas' XLA pools thrash each other; "
                             "raise on real accelerators")
    parser.add_argument("--max-batch", type=int, default=64)
    parser.add_argument("--window-ms", type=float, default=2.0)
    parser.add_argument("--backend", default=BACKEND,
                        choices=("tpu", "cpu"),
                        help="device backend, by name")
    args = parser.parse_args()
    BACKEND = args.backend
    if args.scenario == "baseline":
        result = run_baseline(quick=args.quick, clients=args.clients,
                              replicas=args.replicas,
                              max_batch=args.max_batch,
                              window_ms=args.window_ms)
    else:
        result = SCENARIOS[args.scenario](quick=args.quick)
    print(markdown_row(result))
    print(json.dumps(result, indent=2), file=sys.stderr)
    print("ACCEPTANCE: %s" % ("PASS" if result["pass"] else "FAIL"),
          file=sys.stderr)
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
