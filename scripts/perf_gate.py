#!/usr/bin/env python3
"""CI perf-regression gate (ISSUE 7 tentpole part 5).

Compares a machine-readable perf snapshot against a committed baseline
with per-metric tolerances, and exits non-zero when a hard-gated
metric regresses — the mechanism that stops "the refactor that quietly
doubled step time" from merging.

Three modes::

    perf_gate.py --capture SNAP.json     # run the probe, write snapshot
    perf_gate.py SNAP.json               # compare vs scripts/perf_baseline.json
    perf_gate.py SNAP.json --baseline F  # compare vs an explicit baseline
    perf_gate.py --update-baseline SNAP.json   # adopt snapshot values,
                                               # keeping each metric's policy

**The probe** is a seeded, CPU-deterministic tiny training run through
the real fused pipeline (FusedRunner + telemetry + cost attribution),
so the snapshot carries both *quality* metrics (final loss, epochs
completed — bit-stable across runs on one jaxlib) and *cost* metrics
(analytic segment FLOPs from ``Compiled.cost_analysis()``, measured
step/compile times, host RSS).

**The baseline** maps each metric to a policy::

    {"metrics": {"final_loss": {"value": 0.31, "tolerance": 0.25,
                                "direction": "lower", "gate": "hard"}}}

``direction`` says which way is good ("higher" = bigger is better);
a metric regresses when it moves the BAD way by more than
``tolerance`` (a fraction of the baseline value). ``gate: "hard"``
fails CI; ``gate: "report"`` only prints — the wall-clock throughput
metrics stay report-only until a TPU-attached bench round promotes
them (shared CI runners are too noisy to gate on milliseconds).

A hard metric MISSING from the snapshot also fails: a probe change
that silently drops a gated signal must not pass by omission.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

DEFAULT_BASELINE = os.path.join(HERE, "scripts", "perf_baseline.json")

#: probe geometry — small enough for seconds-long CPU CI, big enough
#: that the loss actually moves (so a broken optimizer regresses it)
SAMPLES = 120
BATCH = 20
EPOCHS = 4
SEED = 1234


def _probe_workflow():
    import numpy

    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.mnist import MnistWorkflow

    rng = numpy.random.RandomState(SEED)
    x = rng.rand(SAMPLES, 6, 6).astype(numpy.float32)
    y = (x.reshape(SAMPLES, -1).sum(1) > 18).astype(numpy.int32)
    split = SAMPLES - 2 * BATCH

    prng.get().seed(SEED)
    prng.get("loader").seed(SEED + 1)
    launcher = Launcher(graphics=False)
    wf = MnistWorkflow(
        launcher,
        provider=lambda: (x[:split], y[:split], x[split:], y[split:]),
        layers=(16,), minibatch_size=BATCH, learning_rate=0.1,
        max_epochs=EPOCHS)
    launcher.initialize()
    t0 = time.perf_counter()
    launcher.run()
    wall = time.perf_counter() - t0
    return wf, wall


def _input_pipeline_probe():
    """ISSUE 8 overlap guard: a tiny streamed (out-of-core) run with a
    throttled host ETL, synchronous vs prefetched. The waits are
    sleep-dominated so the ratio is structural, not machine-speed:
    if the pipeline silently degrades to the synchronous path the
    ratio collapses to ~1 and the hard gate fails."""
    import numpy

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader import prefetch
    from veles_tpu.models.mnist import MnistWorkflow
    from veles_tpu.telemetry.registry import get_registry
    from veles_tpu.train import FusedTrainer

    saved = {k: os.environ.get(k) for k in
             ("VELES_ETL_THROTTLE_MS", "VELES_SHARD_MB")}
    os.environ["VELES_ETL_THROTTLE_MS"] = "40"
    os.environ["VELES_SHARD_MB"] = "0.004"  # 1 minibatch per shard

    rng = numpy.random.RandomState(SEED)
    x = rng.rand(200, 6, 6).astype(numpy.float32)
    y = (x.reshape(200, -1).sum(1) > 18).astype(numpy.int32)

    def run(depth, workers):
        hist = get_registry().get("veles_step_input_wait_ms")
        if hist is not None:
            hist.reset()
        prng.get().seed(SEED)
        prng.get("loader").seed(SEED + 1)
        wf = MnistWorkflow(
            DummyLauncher(),
            provider=lambda: (x[:160], y[:160], x[160:], y[160:]),
            layers=(16,), minibatch_size=20, max_epochs=1)
        wf.initialize(device=Device(backend=None))
        trainer = FusedTrainer(wf, stream=True, prefetch_depth=depth,
                               prefetch_workers=workers)
        trainer.train()
        child = get_registry().get("veles_step_input_wait_ms").labels()
        return child.sum

    try:
        sync_ms = run(0, 1)
        deep_ms = run(4, 4)
    finally:
        prefetch.shutdown_all()
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
    return {"step_input_wait_ms": deep_ms,
            "input_wait_overlap_ratio": sync_ms / max(deep_ms, 1e-9)}


def _offload_probe():
    """ISSUE 17 overlap guard: a tiny host-offloaded run (several
    layer groups per step) with a throttled interconnect, synchronous
    vs double-buffered ring. Sleep-dominated like the input probe, so
    the ratio is structural and gates HARD — if the ring silently
    degrades to inline transfers it collapses to ~1. A second,
    unthrottled pair measures the offloaded-vs-in-core step overhead
    (report-only: real wall time, noisy on shared runners)."""
    import numpy

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistWorkflow
    from veles_tpu.train import FusedTrainer
    from veles_tpu.train import offload

    saved = {k: os.environ.get(k) for k in
             ("VELES_OFFLOAD_THROTTLE_MS", "VELES_OFFLOAD_GROUP_MB")}
    os.environ["VELES_OFFLOAD_GROUP_MB"] = "0.001"

    rng = numpy.random.RandomState(SEED)
    x = rng.rand(200, 6, 6).astype(numpy.float32)
    y = (x.reshape(200, -1).sum(1) > 18).astype(numpy.int32)

    def run(offloaded, depth, workers, throttle_ms):
        os.environ["VELES_OFFLOAD_THROTTLE_MS"] = str(throttle_ms)
        prng.get().seed(SEED)
        prng.get("loader").seed(SEED + 1)
        wf = MnistWorkflow(
            DummyLauncher(),
            provider=lambda: (x[:160], y[:160], x[160:], y[160:]),
            layers=(16, 12), minibatch_size=20, max_epochs=1)
        wf.initialize(device=Device(backend=None))
        trainer = FusedTrainer(wf, offload=offloaded,
                               offload_depth=depth,
                               offload_workers=workers)
        assert trainer.offloaded == offloaded
        t0 = time.perf_counter()
        trainer.train()
        return trainer.offload_wait_s * 1e3, time.perf_counter() - t0

    try:
        sync_ms, _ = run(True, 0, 1, 40)
        double_ms, _ = run(True, 6, 2, 40)
        _, incore_s = run(False, 0, 1, 0)
        _, off_s = run(True, 6, 2, 0)
    finally:
        offload.shutdown_all()
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
    return {"offload_overlap_ratio": sync_ms / max(double_ms, 1e-9),
            "offload_step_overhead_ratio": off_s / max(incore_s, 1e-9)}


def _federation_probe(n_series=100, beats=50, rounds=3):
    """ISSUE 9 overhead guard (report-only): heartbeat round-trip with
    vs. without the federation snapshot piggyback, over a real
    loopback coordinator pair with a ~2x``n_series``-series slave
    registry whose series half-churn every beat — a realistic worst
    case (steady state deltas are far smaller). The ratio keeps the
    observability plane's cost visible in the perf baseline."""
    from veles_tpu.parallel.coordinator import (CoordinatorClient,
                                                CoordinatorServer)
    from veles_tpu.telemetry.federation import SnapshotEncoder
    from veles_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    hist = reg.histogram("probe_ms", labels=("op",))
    gauge = reg.gauge("probe_value", labels=("op",))
    for i in range(n_series):
        hist.labels(op="op%d" % i).observe(1.0)
        gauge.labels(op="op%d" % i).set(float(i))

    server = CoordinatorServer(checksum="fedprobe")
    try:
        client = CoordinatorClient(server.address, checksum="fedprobe",
                                   heartbeat_interval=3600.0,
                                   federate=False)
        client.connect()
        proto = client._hb_proto
        encoder = SnapshotEncoder(registry=reg)
        encoder.encode()  # prime: steady-state deltas, not full pushes

        def run_leg(with_telemetry):
            total = 0.0
            for i in range(beats):
                if with_telemetry:
                    # churn half the series so every delta is honest
                    for j in range(0, n_series, 2):
                        hist.labels(op="op%d" % j).observe(float(i))
                msg = {"cmd": "heartbeat", "power": 1.0, "rtt_ms": 1.0}
                t0 = time.perf_counter()
                if with_telemetry:
                    delta = encoder.encode()
                    if delta is not None:
                        msg["telemetry"] = delta
                proto.send(msg)
                proto.recv()
                total += time.perf_counter() - t0
            return total / beats

        run_leg(False)  # warm the path
        base = min(run_leg(False) for _ in range(rounds))
        fed = min(run_leg(True) for _ in range(rounds))
        client.close()
    finally:
        server.stop()
    return {"federation_overhead_ratio": fed / max(base, 1e-9)}


def _sched_federation_probe(n_series=200, beats=50, rounds=3):
    """ISSUE 19 overhead guard (report-only): the elastic-tier twin of
    :func:`_federation_probe` — heartbeat round-trip against a real
    :class:`RendezvousServer` with vs. without the SnapshotEncoder
    delta piggyback, from a 200-series worker registry whose series
    half-churn every beat. The delta rides the SAME beat the
    supervisor's liveness verdict depends on, so its encode+absorb
    cost stays pinned in the baseline."""
    from veles_tpu.parallel.elastic import (RendezvousClient,
                                            RendezvousServer)
    from veles_tpu.telemetry.federation import SnapshotEncoder
    from veles_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    gauge = reg.gauge("probe_value", labels=("op",))
    for i in range(n_series):
        gauge.labels(op="op%d" % i).set(float(i))

    server = RendezvousServer(min_workers=1, settle_s=0.05).start()
    try:
        client = RendezvousClient(server.address, "probe-worker")
        gen = client.join_wait(timeout_s=30.0)["gen"]
        encoder = SnapshotEncoder(registry=reg)
        encoder.encode()  # prime: steady-state deltas, not full pushes

        def run_leg(with_telemetry):
            total = 0.0
            for i in range(beats):
                if with_telemetry:
                    # churn half the series so every delta is honest
                    for j in range(0, n_series, 2):
                        gauge.labels(op="op%d" % j).set(float(i + j))
                t0 = time.perf_counter()
                telemetry = encoder.encode() if with_telemetry \
                    else None
                client.heartbeat_full(gen, telemetry=telemetry)
                total += time.perf_counter() - t0
            return total / beats

        run_leg(False)  # warm the path
        base = min(run_leg(False) for _ in range(rounds))
        fed = min(run_leg(True) for _ in range(rounds))
        client.close()
    finally:
        server.stop()
    return {"sched_federation_overhead_ratio": fed / max(base, 1e-9)}


def _recovery_probe():
    """ISSUE 12 recovery-time guard (report-only): a loopback
    coordinator pair where one slave takes a job and dies abruptly
    (socket closed, no result); measured is the wall time from the
    death to the requeued job's result arriving from the healthy
    sibling — the veles_recovery_ms{event="requeue"} path end to
    end. Report-only because shared CI runners make wall time noisy;
    the structural assertions live in tests/test_fault_tolerance.py."""
    from veles_tpu.parallel.coordinator import (CoordinatorClient,
                                                CoordinatorServer)

    server = CoordinatorServer(checksum="recovery",
                               heartbeat_timeout=0.5)
    try:
        server.submit(*[{"n": i} for i in range(4)])
        victim = CoordinatorClient(server.address,
                                   checksum="recovery").connect()
        victim.proto.send({"cmd": "job"})
        victim.proto.recv()  # job is now in-flight on the victim
        t0 = time.perf_counter()
        # abrupt: kill the raw channels (no goodbye — client.close()
        # would send the voluntary-exit bye and measure the CLEAN
        # disconnect instead of a death)
        victim._closed = True
        victim._hb_stop.set()
        victim.proto.close()
        victim._hb_proto.close()
        healthy = CoordinatorClient(server.address,
                                    checksum="recovery").connect()
        healthy.serve_forever(lambda job: job["n"], max_idle=20)
        server.wait(4, timeout=20)
        recovery_s = time.perf_counter() - t0
        healthy.close()
    finally:
        server.stop()
    return {"recovery_time_s": recovery_s}


def _spmd_recovery_probe():
    """ISSUE 13 recovery-time guard (report-only): the elastic SPMD
    supervision tier with jax-free stub workers — rendezvous anchor +
    two supervisors; one worker is SIGKILLed; measured is the server's
    break -> new-generation-formed time at world size 1 (detection +
    settle + re-rendezvous — the pure orchestration cost; checkpoint
    restore and XLA recompile ride on top in a real pod and are
    covered by `bench_distributed.py --chaos spmd-kill`). Report-only
    for the same reason as recovery_time_s: shared CI wall clocks are
    noisy; the structural assertions live in tests/test_elastic.py."""
    import signal
    import threading

    from veles_tpu.parallel.elastic import (ElasticSupervisor,
                                            RendezvousServer)

    server = RendezvousServer(expected=2, min_workers=1, settle_s=0.3,
                              heartbeat_timeout_s=2.0).start()
    stub = ("import os, time\n"
            "if os.environ.get('VELES_ELASTIC_GEN') == '0':\n"
            "    time.sleep(60)\n")
    argv = [sys.executable, "-c", stub]
    addr = "%s:%d" % server.address
    sups = [ElasticSupervisor(addr, argv, member="p%d" % i,
                              max_restarts=0, poll_s=0.05)
            for i in range(2)]
    rcs = [None, None]
    threads = [threading.Thread(target=lambda i=i: rcs.__setitem__(
        i, sups[i].run()), daemon=True) for i in range(2)]
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not (
                server.phase == "running" and
                all(s.worker is not None for s in sups)):
            time.sleep(0.02)
        if server.phase != "running" or sups[1].worker is None:
            raise RuntimeError(
                "spmd recovery probe: generation 0 did not form "
                "(phase=%s)" % server.phase)
        time.sleep(0.1)
        os.kill(sups[1].worker.pid, signal.SIGKILL)
        for t in threads:
            t.join(timeout=30)
        recovery = server.last_recovery_s
    finally:
        for sup in sups:
            sup._kill_worker()
        server.stop()
    if rcs[0] != 0 or recovery is None:
        raise RuntimeError("spmd recovery probe failed: rcs=%r" % rcs)
    return {"spmd_recovery_time_s": recovery}


_GSPMD_PROBE = r"""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("VELES_TPU_BACKEND", "cpu")
sys.path.insert(0, %(repo)r)
import jax
import jax.numpy as jnp
import numpy

from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.mnist import MnistWorkflow
from veles_tpu.parallel import wire
from veles_tpu.parallel.gspmd import BATCH_AXIS, GSPMDTrainer, gspmd_mesh
from veles_tpu.parallel.mesh import named_sharding
from veles_tpu.train import FusedTrainer

SEED = %(seed)d


def build_wf():
    rng = numpy.random.RandomState(SEED)
    x = rng.rand(160, 6, 6).astype(numpy.float32)
    y = (x.reshape(160, -1).sum(1) > 18).astype(numpy.int32)
    prng.get().seed(SEED)
    prng.get("loader").seed(SEED + 1)
    wf = MnistWorkflow(
        DummyLauncher(),
        provider=lambda: (x[:128], y[:128], x[128:], y[128:]),
        layers=(16,), minibatch_size=32, learning_rate=0.1,
        max_epochs=3)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def curve(history):
    return [(h["epoch"], h["validation"]["loss"],
             h["validation"]["normalized"], h["train"]["loss"],
             h["train"]["normalized"]) for h in history]


fused = curve(FusedTrainer(build_wf()).train())
gspmd = curve(GSPMDTrainer(build_wf()).train())
# reported losses of two differently partitioned programs: each
# compiler picks its own order for the sum over a batch, so a few
# float32 ULP, not bit-equal (tests/test_gspmd.py holds the weights
# bit-equal)
parity = 1.0 if numpy.allclose(gspmd, fused, rtol=4e-7, atol=0) else 0.0

# exchange-cycle ratio: the shm wire's oob encode/copy/decode vs the
# jitted psum merge, same mid-size tree (sleep-free, so report-only)
rng = numpy.random.RandomState(SEED)
tree = {"w0": rng.randn(512, 1024).astype(numpy.float32),
        "b0": rng.randn(1024).astype(numpy.float32),
        "w1": rng.randn(1024, 512).astype(numpy.float32)}
mesh = gspmd_mesh()
n = mesh.shape[BATCH_AXIS]
parts = {k: jax.device_put(numpy.broadcast_to(v, (n,) + v.shape),
                           named_sharding(mesh, BATCH_AXIS))
         for k, v in tree.items()}
merge = jax.jit(lambda t: {k: jnp.sum(v, axis=0) for k, v in t.items()},
                out_shardings=named_sharding(mesh))
jax.block_until_ready(merge(parts))


def best(fn, cycles=5):
    out = None
    for _ in range(cycles):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        out = dt if out is None or dt < out else out
    return out


def wire_cycle():
    blob = wire.encode_chunks(tree).join()
    decoded = wire.decode(bytes(blob))
    for arr in decoded.values():
        arr.ravel()[0]


merge_s = best(lambda: jax.block_until_ready(merge(parts)))
wire_s = best(wire_cycle)
print(json.dumps({"gspmd_loss_parity": parity,
                  "gspmd_exchange_speedup": wire_s / merge_s}))
"""


def _gspmd_probe():
    """ISSUE 15 gate: loss parity of the GSPMD path vs the fused
    single-device path (HARD: the reported curves agree to a few
    float32 ULP, rtol 4e-7), plus the shm-wire-vs-psum exchange
    cycle ratio (report-only: wall-clock on a shared-core virtual
    mesh). Runs in a subprocess because the mesh needs the forced
    8-device CPU platform, which must be set before jax imports."""
    import subprocess
    import tempfile

    script = _GSPMD_PROBE % {"repo": HERE, "seed": SEED}
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(script)
        path = f.name
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    try:
        out = subprocess.run(
            [sys.executable, path], env=env, capture_output=True,
            text=True, timeout=600)
    finally:
        os.unlink(path)
    if out.returncode != 0:
        raise RuntimeError("gspmd probe failed:\n%s" % out.stderr[-3000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


class _ProbePool(object):
    """A replica-pool stand-in with a fixed host-side service delay
    per batch: the serving probes below are SLEEP-dominated (like the
    input-pipeline probe) so their ratios are structural, not
    machine-speed. Results are computed with real numpy so the cache
    bit-identity contract stays honest."""

    def __init__(self, weights, delay_s=0.004, max_batch_size=8):
        import queue as _queue
        import threading as _threading
        self.max_batch_size = max_batch_size
        self._w = weights
        self._delay = delay_s
        self._queue = _queue.Queue()
        self._busy = 0
        self._stop = _threading.Event()
        self._thread = _threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

        class _Model(object):
            name = "probe"
            version = 1
            sample_shape = (weights.shape[0],)

        self.model = _Model()

    def _loop(self):
        import numpy
        while not self._stop.is_set():
            try:
                batch, on_done = self._queue.get(timeout=0.05)
            except Exception:
                continue
            self._busy = 1
            time.sleep(self._delay)          # the "forward"
            on_done(numpy.tanh(batch @ self._w), batch.shape[0], None)
            self._busy = 0

    def any_idle(self):
        return self._busy == 0 and self._queue.empty()

    def submit(self, batch, on_done):
        self._queue.put((batch, on_done))

    def stats(self):
        return [{"load": self._busy}]

    def size(self):
        return 1

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def _serving_cache_probe(requests=200, hot=8, delay_s=0.004):
    """ISSUE 14 cache guard (hard): repeat-heavy traffic (``hot``
    distinct inputs, ``requests`` total) through the dynamic batcher
    with the result cache on vs off, against a fixed-delay service.
    Cache-off pays the delay per request; cache-on pays it ``hot``
    times — the ratio is ~requests/hot by construction, and collapses
    to ~1 if the consult-before-admission path silently breaks."""
    import numpy

    from veles_tpu.serving.cache import ResultCache
    from veles_tpu.serving.engine import DynamicBatcher

    rng = numpy.random.RandomState(SEED)
    weights = rng.rand(16, 4).astype(numpy.float32)
    rows = [rng.rand(16).astype(numpy.float32) for _ in range(hot)]

    def measure(cache):
        pool = _ProbePool(weights, delay_s=delay_s)
        batcher = DynamicBatcher(pool, batch_timeout_ms=0.0,
                                 max_queue=64, cache=cache)
        try:
            t0 = time.perf_counter()
            for i in range(requests):
                batcher.submit(rows[i % hot]).result(timeout=60)
            return time.perf_counter() - t0
        finally:
            batcher.stop()
            pool.stop()

    t_off = measure(None)
    t_on = measure(ResultCache(model="perf-gate"))
    return {"serving_cache_hit_speedup": t_off / max(t_on, 1e-9)}


def _sched_probe():
    """ISSUE 18 gate: the gang-scheduler contention bench in quick
    shape — a prod job preempts a preemptible research gang on a
    pool of one slot (checkpoint + SIGKILL + resume). The resumed
    job's loss curve vs the uninterrupted baseline is
    ``sched_loss_parity`` (HARD at exactly 1.0 — the determinism
    chain from ISSUE 12/13 checkpointing rests on it); the measured
    displacement time is ``sched_preempt_resume_s`` (report-only:
    sleep-paced but still wall-clock on a shared runner). Runs as a
    subprocess because the bench spawns its own worker gangs."""
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json",
                                     delete=False) as f:
        path = f.name
    try:
        out = subprocess.run(
            [sys.executable,
             os.path.join(HERE, "scripts", "sched_bench.py"),
             "--quick", "--json", path],
            capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            raise RuntimeError("sched probe failed:\n%s"
                               % out.stderr[-3000:])
        with open(path) as f:
            summary = json.load(f)
    finally:
        os.unlink(path)
    return {"sched_preempt_resume_s":
            float(summary["sched_preempt_resume_s"]),
            "sched_loss_parity": float(summary["sched_loss_parity"])}


def _sched_restart_probe():
    """ISSUE 20 (report-only): the durable-scheduler chaos leg —
    SIGKILL a `sched serve --state-dir` subprocess mid-contention and
    restart it on the same dir. The bench hard-fails unless the
    surviving gang is adopted and both loss curves stay bit-equal to
    uninterrupted baselines; what the gate tracks is the measured
    restart -> serving-again wall time (journal replay + pid probe +
    adoption), which carries real python startup cost on a shared
    runner and so only reports."""
    import subprocess
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json",
                                     delete=False) as f:
        path = f.name
    try:
        out = subprocess.run(
            [sys.executable,
             os.path.join(HERE, "scripts", "sched_bench.py"),
             "--quick", "--chaos", "sched-kill", "--json", path],
            capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            raise RuntimeError("sched restart probe failed:\n%s"
                               % out.stderr[-3000:])
        with open(path) as f:
            summary = json.load(f)
    finally:
        os.unlink(path)
    return {"sched_restart_recovery_s":
            float(summary["sched_restart_recovery_s"])}


def _sched_journal_probe(n_jobs=200):
    """ISSUE 20 (report-only): what the fsync'd write-ahead journal
    costs on the scheduler's bookkeeping path. Submits N jobs into a
    scheduler whose pool is fully blocked (placement never spawns —
    pure submit + journal-append work), with and without a state
    dir, and reports the wall ratio. Report-only: fsync latency is
    the filesystem's to decide on a shared runner."""
    import tempfile

    from veles_tpu.sched import JobSpec, Scheduler

    def measure(state_dir):
        sched = Scheduler(1, tick_s=3600.0, state_dir=state_dir)
        sched.pool.hold("blocker", 0, sched.pool.size)
        t0 = time.perf_counter()
        for i in range(n_jobs):
            sched.submit(JobSpec(
                name="journal-probe-%d" % i,
                argv=[sys.executable, "-c", "pass"],
                tenant="bench"))
        wall = time.perf_counter() - t0
        sched.stop()
        return wall

    t_memory = measure(None)
    with tempfile.TemporaryDirectory(prefix="sched-journal-") as d:
        t_journal = measure(d)
    return {"sched_journal_overhead_ratio":
            t_journal / max(t_memory, 1e-9)}


def _serving_elastic_probe(delay_s=0.01, backlog=120):
    """ISSUE 14 autoscale guard (report-only): a real replica pool on
    a tiny jitted model, flooded so the queue breaches; measured are
    the p95 of request completion under the burst and the autoscaler's
    breach -> warmed-replica reaction time. Report-only: both carry
    real compile/wall time and shared CI runners are noisy; the
    structural assertions live in tests/test_serving_elastic.py."""
    import numpy

    from veles_tpu.serving.autoscale import Autoscaler
    from veles_tpu.serving.engine import DynamicBatcher
    from veles_tpu.serving.model_store import ServeableModel
    from veles_tpu.serving.replica import ReplicaPool
    from veles_tpu.telemetry.registry import MetricsRegistry

    rng = numpy.random.RandomState(SEED)
    weights = rng.rand(64, 8).astype(numpy.float32)

    def apply(params, x):
        import jax.numpy as jnp
        return jnp.tanh(jnp.dot(x.reshape((x.shape[0], -1)),
                                params["w"]))

    model = ServeableModel([(apply, {"w": weights})], (64,),
                           name="probe")

    class _Slow(ServeableModel):
        def forward_fn(self):
            inner = ServeableModel.forward_fn(self)

            def forward(x):
                time.sleep(delay_s)     # traced once per bucket; the
                return inner(x)         # backlog outlives every trace

            return forward

    slow = _Slow(model.layers, model.sample_shape, name="probe")
    registry = MetricsRegistry()
    pool = ReplicaPool(slow, n_replicas=1, max_batch_size=4, warm=False)
    batcher = DynamicBatcher(pool, batch_timeout_ms=0.0, max_queue=1024)
    scaler = Autoscaler(pool, batcher, min_replicas=1, max_replicas=2,
                        up_queue_per_replica=8.0, up_for_s=0.05,
                        up_cooldown_s=0.0, interval_s=0.02,
                        registry=registry)
    try:
        xs = rng.rand(backlog, 64).astype(numpy.float32)
        t0 = time.perf_counter()
        futures = [batcher.submit(x) for x in xs]
        scaler.start()
        done_ms = []
        for f in futures:
            f.result(timeout=120)
            done_ms.append((time.perf_counter() - t0) * 1e3)
        hist = registry.get("veles_autoscale_reaction_s")
        child = hist.labels(model="default")
        reaction = child.sum / child.count if child.count else -1.0
    finally:
        scaler.stop()
        batcher.stop()
        pool.stop()
    done_ms.sort()
    return {"serving_burst_p95_ms":
            done_ms[int(0.95 * (len(done_ms) - 1))],
            "autoscale_reaction_s": reaction}


def capture():
    """Run the probe and return the snapshot dict."""
    from veles_tpu.telemetry import profiler
    from veles_tpu.telemetry.registry import get_registry

    wf, wall = _probe_workflow()
    history = wf.decision.epoch_history
    samples = sum(h["train"]["samples"] + h["validation"]["samples"]
                  for h in history)
    metrics = {
        "final_loss": float(history[-1]["validation"]["normalized"]),
        "epochs_completed": float(len(history)),
        "samples_per_sec": samples / wall if wall > 0 else 0.0,
    }
    cost = profiler.get_cost_book().cost("train_segment")
    if cost and cost.get("flops"):
        metrics["train_segment_gflop"] = cost["flops"] / 1e9
    step = get_registry().get("veles_step_ms")
    if step is not None:
        summary = {labels.get("phase"): child.summary()
                   for labels, child in step.series()}
        train = summary.get("train") or {}
        if train.get("p50") is not None:
            metrics["step_p50_ms"] = float(train["p50"])
    phases = profiler.phase_report()
    if phases.get("compile"):
        metrics["compile_ms"] = float(phases["compile"])
    rss = profiler.host_rss_bytes()
    if rss:
        metrics["host_rss_gb"] = rss / 2.0 ** 30
    metrics.update(_input_pipeline_probe())
    metrics.update(_offload_probe())
    metrics.update(_gspmd_probe())
    metrics.update(_federation_probe())
    metrics.update(_sched_federation_probe())
    metrics.update(_recovery_probe())
    metrics.update(_spmd_recovery_probe())
    metrics.update(_serving_cache_probe())
    metrics.update(_serving_elastic_probe())
    metrics.update(_sched_probe())
    metrics.update(_sched_restart_probe())
    metrics.update(_sched_journal_probe())
    return {"schema": "veles-perf-snapshot/1",
            "probe": {"samples": SAMPLES, "batch": BATCH,
                      "epochs": EPOCHS, "seed": SEED},
            "metrics": metrics}


def compare(snapshot, baseline):
    """``(failures, lines)``: hard regressions + the full report."""
    lines = []
    failures = []
    snap = snapshot.get("metrics", {})
    base = baseline.get("metrics", {})
    for name in sorted(base):
        policy = base[name]
        ref = float(policy["value"])
        tol = float(policy.get("tolerance", 0.1))
        direction = policy.get("direction", "higher")
        hard = policy.get("gate", "hard") == "hard"
        tag = "hard" if hard else "report"
        if name not in snap:
            line = "MISSING  %-22s baseline %.4g [%s]" % (name, ref, tag)
            if hard:
                failures.append(line)
            lines.append(line)
            continue
        new = float(snap[name])
        if direction == "higher":
            bound = ref * (1.0 - tol)
            regressed = new < bound
        else:
            bound = ref * (1.0 + tol)
            regressed = new > bound
        delta = (new - ref) / ref * 100.0 if ref else 0.0
        status = "REGRESS" if regressed else "ok"
        line = ("%-8s %-22s %.4g vs %.4g (%+.1f%%, %s is better, "
                "tol %.0f%%) [%s]"
                % (status, name, new, ref, delta, direction,
                   tol * 100.0, tag))
        lines.append(line)
        if regressed and hard:
            failures.append(line)
    for name in sorted(set(snap) - set(base)):
        lines.append("new      %-22s %.4g (no baseline policy)"
                     % (name, float(snap[name])))
    return failures, lines


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("snapshot", nargs="?",
                        help="snapshot JSON to compare (from --capture)")
    parser.add_argument("--capture", metavar="OUT",
                        help="run the probe and write the snapshot here")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="baseline policy file (default %(default)s)")
    parser.add_argument("--update-baseline", metavar="SNAP",
                        help="rewrite the baseline's values from this "
                             "snapshot, keeping each metric's policy")
    args = parser.parse_args(argv)

    if args.capture:
        snapshot = capture()
        with open(args.capture, "w") as f:
            json.dump(snapshot, f, indent=1, sort_keys=True)
        print("perf snapshot -> %s" % args.capture)
        for name, value in sorted(snapshot["metrics"].items()):
            print("  %-22s %.4g" % (name, value))
        return 0

    if args.update_baseline:
        with open(args.update_baseline) as f:
            snapshot = json.load(f)
        with open(args.baseline) as f:
            baseline = json.load(f)
        for name, policy in baseline["metrics"].items():
            if name in snapshot["metrics"]:
                policy["value"] = round(
                    float(snapshot["metrics"][name]), 6)
        with open(args.baseline, "w") as f:
            json.dump(baseline, f, indent=1, sort_keys=True)
            f.write("\n")
        print("baseline values updated from %s -> %s"
              % (args.update_baseline, args.baseline))
        return 0

    if not args.snapshot:
        parser.error("need a snapshot to compare "
                     "(or --capture / --update-baseline)")
    with open(args.snapshot) as f:
        snapshot = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    failures, lines = compare(snapshot, baseline)
    print("perf gate: %s vs %s" % (args.snapshot, args.baseline))
    for line in lines:
        print("  " + line)
    if failures:
        print("PERF GATE FAILED: %d hard regression(s)" % len(failures))
        return 1
    print("perf gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
