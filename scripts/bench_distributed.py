#!/usr/bin/env python3
"""Distributed-overhead benchmark: master + slave fused segments vs
standalone (VERDICT r4 next #1 — BASELINE config 5's single-host
analog; reference protocol ``veles/server.py:659`` / ``client.py:405``,
``manualrst_veles_distributed_training.rst:14-27``).

The protocol's distributed cost per job is ONE weight push (master →
slave), the segment's compute, and ONE delta pull (slave → master);
the shm fast path makes both exchanges a pickle-encode + memcpy on
the same host. Whether that is ≤5% of a step therefore depends on the
ratio of exchange bytes/s to compute samples/s — so this script
measures the pieces separately and honestly:

* ``--cpu-protocol`` — master + 1 and 2 CPU slaves vs CPU standalone
  on a conv config whose weights are small: isolates SCHEDULING +
  framing + shm machinery overhead (the ≤5% protocol claim, and the
  2-slave leg shows scheduler overhead does not grow).
* ``shmbench`` — wire-encode + decode + memcpy of the REAL AlexNet-227
  parameter set (the per-job exchange payload) on this host: the
  numerator of the exchange-cost ratio on ANY same-host deployment.
  Reports three codecs side by side — the r5 full-pickle baseline,
  the out-of-band array framing (this repo's default shm path), and
  the ``--exchange-dtype bfloat16`` delta push — with per-phase times
  and the speedup vs pickle (docs/PERF.md r6).
* default (chip) — standalone vs master+1 slave on the chip with the
  MNIST-FC config (config 1; weights 0.32 MB). Not run on a chip
  since before PR 2; the flagship's distributed-vs-standalone ratio
  on a chip is not measured.

Methodology: every leg timestamps each epoch as its stats land (10 Hz
poll of ``decision.epoch_history``); throughput is over epochs 2..N so
epoch 1 absorbs the XLA compile identically everywhere.
"""

import json
import logging
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

logging.disable(logging.WARNING)

EPOCHS = int(os.environ.get("VELES_DIST_EPOCHS", 12))
SEGMENT = int(os.environ.get("VELES_DIST_SEGMENT", 64))
CONFIG = os.environ.get("VELES_DIST_CONFIG", "fc")
PRECISION = os.environ.get("VELES_BENCH_PRECISION", "bfloat16")


def _build(launcher):
    from veles_tpu import prng
    from veles_tpu.nn.precision import set_policy
    set_policy(PRECISION)
    prng.get().seed(42)
    prng.get("loader").seed(43)
    if CONFIG == "fc":
        from veles_tpu.datasets import golden_digits
        from veles_tpu.models.mnist import MnistWorkflow
        # VELES_DIST_MB: the GSPMD e2e pair overrides the minibatch to
        # one the 8-way batch axis divides (512); both of its legs use
        # the same value so the comparison stays fair
        mb = int(os.environ.get("VELES_DIST_MB", "0") or 0) or 500
        return MnistWorkflow(
            launcher, provider=golden_digits(n_train=12000,
                                             n_valid=500),
            layers=(100,), minibatch_size=mb, max_epochs=EPOCHS)
    if CONFIG == "smallconv":
        from veles_tpu.models.alexnet import (AlexNetWorkflow,
                                              SyntheticImageLoader,
                                              small_alexnet_layers)
        return AlexNetWorkflow(
            launcher,
            loader_factory=lambda w: SyntheticImageLoader(
                w, n_train=2048, n_valid=128, side=64, n_classes=100,
                minibatch_size=128, dtype="bfloat16"),
            layers=small_alexnet_layers(n_classes=100),
            max_epochs=EPOCHS)
    raise SystemExit("unknown VELES_DIST_CONFIG %r" % CONFIG)


def _samples_per_epoch():
    return {"fc": 12500, "smallconv": 2176}[CONFIG]


def _timed_run(launcher, wf):
    stamps = []
    t0 = time.time()
    done = threading.Event()

    def poll():
        seen = 0
        while not done.is_set():
            n = len(wf.decision.epoch_history)
            now = time.time() - t0
            while seen < n:
                stamps.append(now)
                seen += 1
            done.wait(0.1)
        n = len(wf.decision.epoch_history)
        while seen < n:
            stamps.append(time.time() - t0)
            seen += 1

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    launcher.run()
    done.set()
    poller.join(timeout=5)
    return time.time() - t0, stamps


def _steady_rate(stamps, samples_per_epoch):
    """samples/s over epochs 2..N (epoch 1 absorbs the compile)."""
    if len(stamps) < 3:
        raise RuntimeError("need >=3 epochs for a steady window: %s"
                           % stamps)
    dt = stamps[-1] - stamps[0]
    return (len(stamps) - 1) * samples_per_epoch / dt


def run_standalone():
    from veles_tpu.launcher import Launcher
    launcher = Launcher(graphics=False)
    wf = _build(launcher)
    launcher.initialize()
    elapsed, stamps = _timed_run(launcher, wf)
    rate = _steady_rate(stamps, _samples_per_epoch())
    print("standalone[%s]: %d epochs in %.1fs, stamps %s (mode=%s)"
          % (CONFIG, len(stamps), elapsed,
             " ".join("%.1f" % s for s in stamps),
             launcher.run_mode_used), file=sys.stderr)
    print(json.dumps({
        "leg": "standalone", "config": CONFIG,
        "elapsed_s": round(elapsed, 2), "epochs": len(stamps),
        "samples_per_sec": round(rate, 1)}))


def run_master(n_slaves, port=0):
    from veles_tpu.launcher import Launcher
    chaos = os.environ.get("VELES_DIST_CHAOS")
    launcher = Launcher(
        listen_address="127.0.0.1:%d" % port, graphics=False,
        segment_size=SEGMENT,
        heartbeat_timeout=float(os.environ.get("VELES_DIST_HBT", 10.0)))
    _build(launcher)
    launcher.initialize()
    # auto-resume (VELES_AUTO_RESUME) may have replaced the built
    # workflow with the restored one — the launcher's is authoritative
    wf = launcher.workflow
    print("PORT=%d" % launcher._server.address[1], file=sys.stderr,
          flush=True)
    if launcher._resumed_from:
        print("EVENT resumed t=%.6f n=%d" %
              (time.time(), len(wf.decision.epoch_history)),
              file=sys.stderr, flush=True)
    deadline = time.time() + 900
    while len(launcher._server.snapshot_slaves()) < n_slaves:
        if time.time() > deadline:
            raise RuntimeError("slaves did not connect within 900s")
        time.sleep(0.2)
    if chaos:
        _start_chaos_watchers(launcher, chaos)
    elapsed, stamps = _timed_run(launcher, wf)
    epochs = len(wf.decision.epoch_history)
    print("master[%s, %d slaves]: %d epochs in %.1fs, stamps %s"
          % (CONFIG, n_slaves, epochs, elapsed,
             " ".join("%.1f" % s for s in stamps)), file=sys.stderr)
    out = {"leg": "distributed_%d_slave" % n_slaves, "config": CONFIG,
           "elapsed_s": round(elapsed, 2), "epochs": epochs}
    if not chaos:
        # bench legs NEED the steady rate (the orchestrators index
        # it); _steady_rate raises its clear >=3-epochs error here
        # instead of a downstream KeyError
        out["samples_per_sec"] = round(
            _steady_rate(stamps, _samples_per_epoch()), 1)
    elif len(stamps) >= 3:
        out["samples_per_sec"] = round(
            _steady_rate(stamps, _samples_per_epoch()), 1)
    print(json.dumps(out))


def _counter_total(name):
    from veles_tpu.telemetry.registry import get_registry
    family = get_registry().get(name)
    if family is None:
        return 0.0
    return sum(child.value for _, child in family.series())


def _hist_count(name, **labels):
    from veles_tpu.telemetry.registry import get_registry
    family = get_registry().get(name)
    if family is None:
        return 0
    total = 0
    for series_labels, child in family.series():
        if all(series_labels.get(k) == v for k, v in labels.items()):
            total += child.count
    return total


def _start_chaos_watchers(launcher, kind):
    """Announce chaos-relevant transitions on stderr, timestamped with
    the shared wall clock so the parent can compute time-to-X against
    the moment it injected the fault."""

    def watch_straggler():
        scorer = launcher._server.health
        while True:
            for sid, row in scorer.table().items():
                if row["state"] == "straggler":
                    print("EVENT straggler sid=%s t=%.6f score=%.2f"
                          % (sid, time.time(), row["score"]),
                          file=sys.stderr, flush=True)
                    return
            time.sleep(0.05)

    def watch_kill():
        # a SIGKILL'd slave's sockets close from the kernel: the drop
        # surfaces on the drops counter (the _serve finally classifies
        # a no-goodbye mid-run disconnect as a death even if the kill
        # landed on an idle instant), recovery as the first resolved
        # result after the requeue (veles_recovery_ms{event=requeue})
        drops_base = _counter_total("veles_slave_drops_total")
        requeue_base = _counter_total("veles_jobs_requeued_total")
        drop_seen = None
        while True:
            now = time.time()
            if drop_seen is None and \
                    _counter_total("veles_slave_drops_total") > drops_base:
                print("EVENT drop t=%.6f" % now,
                      file=sys.stderr, flush=True)
                drop_seen = now
            if drop_seen is not None and (
                    _hist_count("veles_recovery_ms", event="requeue") > 0
                    or (_counter_total("veles_jobs_requeued_total") ==
                        requeue_base and now - drop_seen > 0.5)):
                # still-zero requeues a beat AFTER the drop (the drop
                # counter increments before the requeue accounting, so
                # a same-poll read could race it) = the victim held
                # nothing: recovery is trivially immediate
                print("EVENT recovered t=%.6f" % now,
                      file=sys.stderr, flush=True)
                return
            time.sleep(0.02)

    def watch_epochs():
        seen = 0
        while True:
            n = len(launcher.workflow.decision.epoch_history)
            while seen < n:
                seen += 1
                print("EVENT epoch n=%d t=%.6f" % (seen, time.time()),
                      file=sys.stderr, flush=True)
            time.sleep(0.05)

    def watch_state():
        # periodic one-line scheduler state: when a chaos leg wedges,
        # THIS is the line that says which side is withholding
        while True:
            try:
                wf = launcher.workflow
                loader, decision = wf.loader, wf.decision
                slaves = launcher._server.snapshot_slaves()
                print("EVENT state t=%.6f ep=%s off=%s open=%s "
                      "buckets=%s failed=%d pending=%s inflight=%s "
                      "hist=%d hasdata=%s nomore=%s" %
                      (time.time(), loader.epoch_number,
                       loader._global_offset,
                       getattr(decision, "_next_close_epoch_", None),
                       sorted(getattr(decision, "_epoch_buckets_",
                                      None) or ()),
                       len(loader.failed_minibatches),
                       {s: len(j)
                        for s, j in dict(loader._pending_).items()},
                       {s.id: len(s.jobs_in_flight) for s in slaves},
                       len(decision.epoch_history),
                       decision.has_data_for_slave,
                       launcher._server.no_more_jobs),
                      file=sys.stderr, flush=True)
            except Exception:
                # racing live dicts (no locks held on purpose): a torn
                # read must not kill the diagnostic stream
                pass
            time.sleep(2.0)

    print("EVENT running t=%.6f" % time.time(), file=sys.stderr,
          flush=True)
    watchers = {"straggler": [watch_straggler],
                "kill": [watch_kill, watch_epochs],
                "master-restart": [watch_epochs, watch_state]}[kind]
    for target in watchers:
        threading.Thread(target=target, daemon=True).start()


def run_slave(port):
    from veles_tpu.launcher import Launcher
    launcher = Launcher(master_address="127.0.0.1:%d" % port,
                        graphics=False,
                        heartbeat_interval=float(
                            os.environ.get("VELES_DIST_HB", 2.0)))
    _build(launcher)
    launcher.initialize()
    launcher.run()
    print(json.dumps({"leg": "slave", "ok": True}))


def _payload_shrink():
    """``VELES_DIST_PAYLOAD_SHRINK``: divide the large fc dims of the
    exchange payload by this factor (CI quick mode — the flagship
    249.5 MB set stacked 8-wide for the GSPMD merge leg would not fit
    a shared runner). Both the shm and the GSPMD legs read it, so the
    compared cycles always carry the SAME payload."""
    try:
        return max(1, int(os.environ.get("VELES_DIST_PAYLOAD_SHRINK",
                                         "1")))
    except ValueError:
        return 1


def _alexnet_payload(rng, scale=1.0):
    """The real AlexNet-227 stored parameter set (conv kernels + fc
    trunk), f32; conv1 is (ky, kx, 3, 96) — the s2d regrouping happens
    at apply time, never in the exchanged arrays."""
    import numpy
    shapes = [(11, 11, 3, 96), (96,), (5, 5, 96, 256), (256,),
              (3, 3, 256, 384), (384,), (3, 3, 384, 384), (384,),
              (3, 3, 384, 256), (256,), (9216, 4096), (4096,),
              (4096, 4096), (4096,), (4096, 1000), (1000,)]
    shrink = _payload_shrink()
    if shrink > 1:
        shapes = [tuple(d // shrink if d >= 1024 else d for d in s)
                  for s in shapes]
    return {"w%d" % i: (rng.randn(*s) * scale).astype(numpy.float32)
            for i, s in enumerate(shapes)}


def run_shmbench():
    """Per-job weight-exchange cost at FLAGSHIP scale on this host:
    encode the real AlexNet-227 parameter set, memcpy through ONE
    reused SharedMemory segment, copy out, decode — the full shm
    fast-path payload cycle, no device involved. Three codecs:

    * ``pickle``  — the r5 baseline (full pickle byte-string both ways);
    * ``oob``     — out-of-band framing: skeleton pickle + raw array
      buffers memcpy'd straight into the segment, decode =
      zero-copy ``frombuffer`` views (this PR's default shm path);
    * ``delta16`` — oob + ``--exchange-dtype bfloat16`` steady-state
      delta push (half the bytes; the first full push is excluded,
      it happens once per slave connection).

    The segment is allocated once and reused across cycles, like the
    Protocol's double-buffered segments in a real run. Reports the
    best-of-N cycle per codec and the speedups over pickle.
    """
    import pickle
    from multiprocessing import shared_memory

    import numpy

    from veles_tpu.parallel import wire

    cycles = int(os.environ.get("VELES_SHMBENCH_CYCLES", 5))
    rng = numpy.random.RandomState(0)
    payload = _alexnet_payload(rng)
    # a second weight state one SGD-sized step away, so delta cycles
    # encode a real nonzero delta every time
    stepped = {k: v + 0.001 * rng.randn(*v.shape).astype(numpy.float32)
               for k, v in payload.items()}
    total_mb = sum(a.nbytes for a in payload.values()) / 1e6

    def cycle_pickle(seg, tree):
        t0 = time.time()
        blob = wire.RAW + pickle.dumps(tree, protocol=4)
        t1 = time.time()
        seg.buf[:len(blob)] = blob
        t2 = time.time()
        out = bytes(seg.buf[:len(blob)])
        t3 = time.time()
        wire.decode(out)
        t4 = time.time()
        return (t1 - t0, t2 - t1, t3 - t2, t4 - t3), len(blob)

    def cycle_oob(seg, tree):
        t0 = time.time()
        chunks = wire.encode_chunks(tree)
        t1 = time.time()
        pos = 0
        for part in chunks.parts:
            seg.buf[pos:pos + len(part)] = part
            pos += len(part)
        t2 = time.time()
        out = bytes(seg.buf[:pos])
        t3 = time.time()
        tree = wire.decode(out)
        # touch one element per leaf so lazy views cannot hide work
        for arr in tree.values():
            arr.ravel()[0]
        t4 = time.time()
        return (t1 - t0, t2 - t1, t3 - t2, t4 - t3), chunks.nbytes

    def run_leg(fn, seg, trees):
        best, wire_bytes = None, 0
        for i in range(cycles):
            times, nbytes = fn(seg, trees[i % len(trees)])
            if best is None or sum(times) < sum(best):
                best, wire_bytes = times, nbytes
        return best, wire_bytes

    # pickle baseline sizing: tag + full pickle
    probe = wire.RAW + pickle.dumps(payload, protocol=4)
    seg = shared_memory.SharedMemory(create=True,
                                     size=len(probe) + (1 << 20))
    rows = {}
    try:
        rows["pickle"] = run_leg(cycle_pickle, seg, [payload, stepped])
        rows["oob"] = run_leg(cycle_oob, seg, [payload, stepped])

        enc = wire.DeltaEncoder(dtype="bfloat16")
        dec = wire.DeltaDecoder()
        # untimed first full push primes both codecs' bases to
        # ``payload``; starting the flip at ``stepped`` makes every
        # timed cycle carry a real full-size delta (starting at
        # ``payload`` would make cycle 0 an all-leaves-skipped no-op)
        dec.decode(wire.decode(wire.encode_chunks(
            enc.encode(payload)).join()))
        flip = [stepped, payload]

        def cycle_delta(seg, tree):
            t0 = time.time()
            chunks = wire.encode_chunks(enc.encode(tree))
            t1 = time.time()
            pos = 0
            for part in chunks.parts:
                seg.buf[pos:pos + len(part)] = part
                pos += len(part)
            t2 = time.time()
            out = bytes(seg.buf[:pos])
            t3 = time.time()
            dec.decode(wire.decode(out))
            t4 = time.time()
            return (t1 - t0, t2 - t1, t3 - t2, t4 - t3), chunks.nbytes

        rows["delta16"] = run_leg(cycle_delta, seg, flip)
    finally:
        seg.close()
        seg.unlink()

    report = {"leg": "shmbench", "payload_mb": round(total_mb, 1),
              "cycles": cycles}
    base = sum(rows["pickle"][0])
    for name, (times, wire_bytes) in rows.items():
        enc_s, in_s, out_s, dec_s = times
        cyc = sum(times)
        report[name] = {
            "encode_s": round(enc_s, 4), "shm_in_s": round(in_s, 4),
            "shm_out_s": round(out_s, 4), "decode_s": round(dec_s, 4),
            "full_cycle_s": round(cyc, 4),
            "wire_mb": round(wire_bytes / 1e6, 1),
            "mb_per_s": round(total_mb / cyc, 0),
            "speedup_vs_pickle": round(base / cyc, 2)}
    print(json.dumps(report))


def run_gspmd_merge():
    """The GSPMD gradient-merge cycle at exchange-payload scale
    (ISSUE 15): the same parameter set ``shmbench`` pushes through the
    PR 2 shm wire, but merged the launcher-SPMD way — every device of
    the 8-way CPU mesh holds its own full-size partial gradient (the
    per-slave delta of the coordinator protocol) and ONE jitted
    reduction, partitioned over the named ``batch`` axis, merges them
    with a compiler-inserted all-reduce. No pickling, no memcpy, no
    decode: the whole "exchange" is the collective. Reports the
    best-of-N blocked cycle plus the compiled program's
    collective-bytes estimate (the ISSUE 15 CostBook surface).

    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (the orchestrator forces it); numbers on a CPU mesh measure the
    machinery's overhead honestly — all 8 "devices" share the same
    cores — while on a real pod the same program rides ICI."""
    import numpy

    import jax
    import jax.numpy as jnp

    from veles_tpu.parallel.gspmd import BATCH_AXIS, gspmd_mesh
    from veles_tpu.parallel.mesh import named_sharding
    from veles_tpu.telemetry import profiler

    cycles = int(os.environ.get("VELES_SHMBENCH_CYCLES", 5))
    mesh = gspmd_mesh()
    n_dev = mesh.shape[BATCH_AXIS]
    rng = numpy.random.RandomState(0)
    payload = _alexnet_payload(rng, scale=0.001)
    total_mb = sum(a.nbytes for a in payload.values()) / 1e6
    part_spec = named_sharding(mesh, BATCH_AXIS)
    repl = named_sharding(mesh)

    def put_stacked(arr):
        # each device's shard of the stacked dim IS its local partial
        # gradient — a zero-copy broadcast view feeds the per-shard
        # slices, so host memory holds ONE copy however wide the mesh
        stacked = numpy.broadcast_to(arr, (n_dev,) + arr.shape)
        return jax.device_put(stacked, part_spec)

    parts = {k: put_stacked(v) for k, v in payload.items()}

    def merge(tree):
        return {k: jnp.sum(v, axis=0) for k, v in tree.items()}

    jit_merge = jax.jit(merge, out_shardings=repl)
    jax.block_until_ready(jit_merge(parts))  # compile outside the clock
    best = None
    for _ in range(cycles):
        t0 = time.time()
        jax.block_until_ready(jit_merge(parts))
        dt = time.time() - t0
        best = dt if best is None or dt < best else best
    coll = profiler.collective_bytes_estimate(
        jit_merge.lower(parts).compile()) or {}
    print(json.dumps({
        "leg": "gspmd_merge", "payload_mb": round(total_mb, 1),
        "devices": n_dev, "cycles": cycles,
        "full_cycle_s": round(best, 4),
        "mb_per_s": round(total_mb / best, 0),
        "collective_bytes_mb": round(coll.get("bytes", 0) / 1e6, 1),
        "collectives": coll.get("count", 0)}))


def orchestrate_gspmd():
    """``--gspmd`` (ISSUE 15): the exchange/merge-cycle comparison —
    the PR 2 shm wire codecs vs the compiler-inserted collective on
    the forced-8-device CPU mesh, same payload — plus (unless
    ``VELES_GSPMD_E2E=0``) an end-to-end standalone-vs-GSPMD training
    pair on the FC config so the whole launcher path stays exercised."""
    shrink = _payload_shrink()
    shm = _drain(_spawn("shmbench", tpu=False), "shmbench")
    merge = _drain(_spawn(
        "gspmd-merge", tpu=False,
        extra_env={"XLA_FLAGS":
                   "--xla_force_host_platform_device_count=8"}),
        "gspmd-merge")
    oob_s = shm["oob"]["full_cycle_s"]
    pickle_s = shm["pickle"]["full_cycle_s"]
    merge_s = merge["full_cycle_s"]
    table = {
        "mode": "gspmd", "config": CONFIG,
        "payload_mb": merge["payload_mb"],
        "payload_shrink": shrink,
        "shm_pickle_cycle_s": pickle_s,
        "shm_oob_cycle_s": oob_s,
        "shm_delta16_cycle_s": shm["delta16"]["full_cycle_s"],
        "gspmd_merge_cycle_s": merge_s,
        "gspmd_speedup_vs_oob": round(oob_s / merge_s, 2),
        "gspmd_speedup_vs_pickle": round(pickle_s / merge_s, 2),
        "collective_bytes_mb": merge["collective_bytes_mb"],
        "collectives": merge["collectives"],
    }
    if os.environ.get("VELES_GSPMD_E2E", "1") not in ("0", "off"):
        # both e2e legs under the SAME forced-8-device env: fused uses
        # one of the 8 virtual devices, GSPMD shards over all — on a
        # CPU mesh the ratio measures partitioning overhead (the
        # devices share cores), on a pod it measures scaling
        env = {"VELES_DIST_CONFIG": CONFIG, "VELES_DIST_MB": "512",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
        alone = _drain(_spawn("standalone", tpu=False, extra_env=env),
                       "standalone")
        gspmd = _drain(_spawn(
            "standalone", tpu=False,
            extra_env=dict(env, VELES_GSPMD="auto"), tag="gspmd"),
            "gspmd")
        table["standalone_samples_per_sec"] = alone["samples_per_sec"]
        table["gspmd_samples_per_sec"] = gspmd["samples_per_sec"]
        table["gspmd_vs_fused_ratio"] = round(
            gspmd["samples_per_sec"] / alone["samples_per_sec"], 3)
    print(json.dumps(table))


# -- orchestration ---------------------------------------------------------


#: overall ceiling on any single leg — a hung-but-alive subprocess
#: must fail the harness loudly instead of blocking it forever
LEG_TIMEOUT = float(os.environ.get("VELES_DIST_TIMEOUT", 1800))


def _spawn(mode, *args, tpu, extra_env=None, tag=None, argv=None):
    """Start a leg subprocess with BACKGROUND pipe pumps: stderr lines
    are forwarded (tagged) as they arrive and stdout lines collected —
    so a slave producing >64 KB of output can never fill its pipe and
    deadlock the harness against a blocked master. ``argv`` overrides
    the default ``python bench_distributed.py <mode>`` command (the
    spmd-kill leg launches elastic supervisors through the SAME pump
    machinery — EVENT lines land in ``proc.events`` either way)."""
    env = dict(os.environ)
    if not tpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["VELES_TPU_BACKEND"] = "cpu"
    env.update(extra_env or {})
    cmd = list(argv) if argv is not None else (
        [sys.executable, os.path.abspath(__file__), mode] +
        [str(a) for a in args])
    proc = subprocess.Popen(
        cmd,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    proc.tag = tag or mode
    proc.out_lines = []
    proc.port = None
    proc.port_seen = threading.Event()
    proc.events = []

    def pump_err():
        for line in proc.stderr:
            if line.startswith("PORT="):
                proc.port = int(line.split("=", 1)[1].strip())
                proc.port_seen.set()
            elif line.startswith("EVENT "):
                # "EVENT <name> k=v ..." announcements (chaos legs)
                parts = line.split()
                proc.events.append(
                    (parts[1], dict(p.split("=", 1) for p in parts[2:]
                                    if "=" in p)))
            sys.stderr.write("[%s] %s" % (proc.tag, line))
        proc.port_seen.set()  # EOF: unblock _wait_port on early death

    def pump_out():
        for line in proc.stdout:
            proc.out_lines.append(line)

    proc.pumps = [threading.Thread(target=pump_err, daemon=True),
                  threading.Thread(target=pump_out, daemon=True)]
    for t in proc.pumps:
        t.start()
    return proc


def _wait_port(proc, timeout=900):
    proc.port_seen.wait(timeout)
    if proc.port is None:
        if proc.poll() is None:
            # hung before binding: don't orphan it holding the device
            proc.kill()
            proc.wait()
        raise RuntimeError("master died or hung before binding")
    return proc.port


def _drain(proc, tag, timeout=None):
    """Wait for a leg (bounded), join its pumps, parse the last JSON
    stdout line. The pipe pumps already ran in the background, so this
    cannot deadlock on full pipes; the timeout covers a leg that hangs
    while alive."""
    try:
        proc.wait(timeout=LEG_TIMEOUT if timeout is None else timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError("%s leg hung; killed after %.0fs"
                           % (tag, LEG_TIMEOUT if timeout is None
                              else timeout))
    for t in proc.pumps:
        t.join(timeout=10)
    payload = None
    for line in proc.out_lines:
        try:
            payload = json.loads(line)
        except ValueError:
            sys.stderr.write("[%s] %s" % (tag, line))
    if proc.returncode != 0:
        raise RuntimeError("%s leg failed (rc=%d)"
                           % (tag, proc.returncode))
    return payload


def _one_round(n_slaves, tpu_slave, config):
    env = {"VELES_DIST_CONFIG": config}
    master = _spawn("master", n_slaves, tpu=False, extra_env=env)
    port = _wait_port(master)
    slaves = [_spawn("slave", port, tpu=tpu_slave, extra_env=env,
                     tag="slave%d" % i)
              for i in range(n_slaves)]

    # a slave dying at startup would leave the master waiting and the
    # parent blocked on it with the slave's stderr never surfaced —
    # watch the slaves and kill the master if one dies while it runs
    def watchdog():
        while master.poll() is None:
            for i, s in enumerate(slaves):
                if s.poll() not in (None, 0):
                    sys.stderr.write("slave%d died (rc=%s); killing "
                                     "the master leg\n"
                                     % (i, s.returncode))
                    master.kill()
                    return
            time.sleep(1.0)

    threading.Thread(target=watchdog, daemon=True).start()
    try:
        dist = _drain(master, "master")
    finally:
        # always surface slave output, even when the master leg failed
        for i, s in enumerate(slaves):
            if s.poll() is None and master.poll() is not None:
                s.kill()
            try:
                # slaves exit right after the master; anything still
                # alive here is wedged — bound the wait tightly
                _drain(s, "slave%d" % i, timeout=60)
            except RuntimeError as e:
                sys.stderr.write("%s\n" % e)
    return dist


def orchestrate_cpu_protocol():
    env = {"VELES_DIST_CONFIG": "smallconv"}
    alone = _drain(_spawn("standalone", tpu=False, extra_env=env),
                   "standalone")
    one = _one_round(1, tpu_slave=False, config="smallconv")
    two = _one_round(2, tpu_slave=False, config="smallconv")
    table = {
        "mode": "cpu_protocol", "config": "smallconv",
        "standalone_samples_per_sec": alone["samples_per_sec"],
        "distributed_1slave_samples_per_sec": one["samples_per_sec"],
        "distributed_2slave_samples_per_sec": two["samples_per_sec"],
        "overhead_1slave_pct": round(
            100 * (1 - one["samples_per_sec"] /
                   alone["samples_per_sec"]), 1),
        "segment_size": SEGMENT, "epochs": EPOCHS,
    }
    print(json.dumps(table))


def _wait_event(proc, name, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for event, attrs in list(proc.events):
            if event == name:
                return attrs
        if proc.poll() is not None:
            raise RuntimeError("%s died (rc=%s) before EVENT %s"
                               % (proc.tag, proc.returncode, name))
        time.sleep(0.02)
    raise RuntimeError("no EVENT %s within %.0fs" % (name, timeout))


def orchestrate_chaos_straggler():
    """``--chaos straggler`` (ROADMAP item 5's first chaos piece):
    master + 2 CPU slaves on the FC config; once the run is in steady
    state, SIGSTOP one slave mid-epoch and measure how long the
    master's health scorer takes to flag it as a straggler. The
    contract (ISSUE 9): detection within 3 heartbeat intervals (plus a
    0.75 s grace for signal delivery + evaluation cadence)."""
    import signal

    hb = float(os.environ.get("VELES_DIST_HB", 0.5))
    env = {"VELES_DIST_CONFIG": "fc", "VELES_DIST_HB": str(hb),
           "VELES_DIST_CHAOS": "straggler"}
    master = _spawn("master", 2, tpu=False, extra_env=env)
    try:
        port = _wait_port(master)
        slaves = [_spawn("slave", port, tpu=False, extra_env=env,
                         tag="slave%d" % i) for i in range(2)]
        _wait_event(master, "running", 900)
        # let the scorer learn each slave's beat cadence (gap EWMA
        # needs a few observed intervals) and the epoch get going
        time.sleep(max(4 * hb, 1.0))
        victim = slaves[1]
        t_pause = time.time()
        os.kill(victim.pid, signal.SIGSTOP)
        try:
            attrs = _wait_event(master, "straggler", 60)
        finally:
            os.kill(victim.pid, signal.SIGCONT)
        detect_s = float(attrs["t"]) - t_pause
        intervals = detect_s / hb
        budget_s = 3 * hb + 0.75
        report = {"mode": "chaos_straggler", "config": "fc",
                  "heartbeat_interval_s": hb,
                  "time_to_detection_s": round(detect_s, 3),
                  "heartbeat_intervals": round(intervals, 2),
                  "budget_s": budget_s,
                  "straggler": attrs.get("sid"),
                  "score": float(attrs.get("score", 0.0))}
        print(json.dumps(report))
        if detect_s > budget_s:
            raise SystemExit(
                "straggler detected after %.2fs (> %.2fs = 3 heartbeat "
                "intervals + grace)" % (detect_s, budget_s))
        print("chaos straggler leg PASSED: flagged %s in %.2fs "
              "(%.1f heartbeat intervals)"
              % (attrs.get("sid"), detect_s, intervals),
              file=sys.stderr)
    finally:
        # detection is the artifact; the paused epoch is not worth
        # waiting out — tear the legs down
        for proc in [master] + [s for s in locals().get("slaves", [])]:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def orchestrate_chaos_kill():
    """``--chaos kill`` (ISSUE 12): master + 2 CPU slaves on the FC
    config; once the run is in steady state, SIGKILL one slave
    MID-EPOCH. The master must requeue the dead slave's in-flight
    jobs onto the survivor and complete EVERY epoch; the leg measures
    time-to-drop (fault -> jobs requeued) and time-to-recovery
    (fault -> first post-fault result merged)."""
    import signal

    hb = float(os.environ.get("VELES_DIST_HB", 0.5))
    env = {"VELES_DIST_CONFIG": "fc", "VELES_DIST_HB": str(hb),
           "VELES_DIST_HBT": os.environ.get("VELES_DIST_HBT", "2.0"),
           "VELES_DIST_CHAOS": "kill"}
    master = _spawn("master", 2, tpu=False, extra_env=env)
    slaves = []
    try:
        port = _wait_port(master)
        slaves = [_spawn("slave", port, tpu=False, extra_env=env,
                         tag="slave%d" % i) for i in range(2)]
        _wait_event(master, "running", 900)
        # let the run reach steady state, then kill INSIDE an epoch
        # (epochs are served continuously, so any instant is mid-some-
        # epoch once the first job landed)
        _wait_event(master, "epoch", 900)
        victim = slaves[1]
        t_kill = time.time()
        os.kill(victim.pid, signal.SIGKILL)
        victim.wait()
        drop = _wait_event(master, "drop", 60)
        recovered = _wait_event(master, "recovered", 120)
        dist = _drain(master, "master")
        survivor = _drain(slaves[0], "slave0", timeout=60)
        detect_s = float(drop["t"]) - t_kill
        recovery_s = float(recovered["t"]) - t_kill
        report = {"mode": "chaos_kill", "config": "fc",
                  "heartbeat_interval_s": hb,
                  "time_to_drop_s": round(detect_s, 3),
                  "time_to_recovery_s": round(recovery_s, 3),
                  "epochs_completed": dist["epochs"],
                  "epochs_expected": EPOCHS,
                  "survivor_ok": bool(survivor and survivor.get("ok"))}
        print(json.dumps(report))
        if dist["epochs"] != EPOCHS:
            raise SystemExit(
                "kill-mid-epoch run completed %d/%d epochs — the "
                "recovery plane lost work" % (dist["epochs"], EPOCHS))
        print("chaos kill leg PASSED: drop in %.2fs, recovery in "
              "%.2fs, %d/%d epochs with the survivor"
              % (detect_s, recovery_s, dist["epochs"], EPOCHS),
              file=sys.stderr)
    finally:
        for proc in [master] + slaves:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def orchestrate_chaos_master_restart():
    """``--chaos master-restart`` (ISSUE 12): the master checkpoints
    every closed epoch into an auto-resume directory; after the first
    snapshot it is SIGKILL'd and a replacement master starts on the
    SAME port. The slaves must re-handshake through exponential
    backoff (VELES_RECONNECT_S) and the restored run must complete
    every remaining epoch — zero hung processes."""
    import signal
    import tempfile

    hb = float(os.environ.get("VELES_DIST_HB", 0.5))
    snapdir = tempfile.mkdtemp(prefix="veles_chaos_resume_")
    env = {"VELES_DIST_CONFIG": "fc", "VELES_DIST_HB": str(hb),
           "VELES_DIST_HBT": os.environ.get("VELES_DIST_HBT", "2.0"),
           "VELES_DIST_CHAOS": "master-restart",
           "VELES_AUTO_RESUME": snapdir}
    # the reconnect budget must cover the replacement master's startup
    # (~20 s CPU init) but stay BELOW the drain timeout: a slave whose
    # last job outlives the master's end-of-run drain grace redials
    # for the full budget before exiting
    slave_env = dict(env, VELES_RECONNECT_S="60")
    master1 = _spawn("master", 2, tpu=False, extra_env=env)
    master2 = None
    slaves = []
    try:
        port = _wait_port(master1)
        slaves = [_spawn("slave", port, tpu=False, extra_env=slave_env,
                         tag="slave%d" % i) for i in range(2)]
        _wait_event(master1, "running", 900)
        first = _wait_event(master1, "epoch", 900)
        # the snapshot lands in result_sink right after the close the
        # EVENT announced — wait for the artifact itself
        deadline = time.time() + 60
        while not any("_current" in name
                      for name in os.listdir(snapdir)):
            if time.time() > deadline:
                raise RuntimeError("no snapshot appeared in %s"
                                   % snapdir)
            time.sleep(0.1)
        t_kill = time.time()
        os.kill(master1.pid, signal.SIGKILL)
        master1.wait()
        master2 = _spawn("master", 2, port, tpu=False, extra_env=env,
                         tag="master2")
        resumed = _wait_event(master2, "resumed", 300)
        dist = _drain(master2, "master2")
        slave_oks = []
        for i, proc in enumerate(slaves):
            # > the 60 s reconnect budget: a slave whose final compile
            # outlived the master's drain grace exits within budget
            leg = _drain(proc, "slave%d" % i, timeout=120)
            slave_oks.append(bool(leg and leg.get("ok")))
        recovery_s = float(resumed["t"]) - t_kill
        report = {"mode": "chaos_master_restart", "config": "fc",
                  "heartbeat_interval_s": hb,
                  "epochs_before_kill": int(first["n"]),
                  "resumed_with_epochs": int(resumed["n"]),
                  "time_to_resume_s": round(recovery_s, 3),
                  "epochs_completed": dist["epochs"],
                  "epochs_expected": EPOCHS,
                  "slaves_reconnected": slave_oks}
        print(json.dumps(report))
        if dist["epochs"] != EPOCHS or not all(slave_oks):
            raise SystemExit(
                "master-restart run completed %d/%d epochs, slaves "
                "ok=%s" % (dist["epochs"], EPOCHS, slave_oks))
        print("chaos master-restart leg PASSED: resumed with %d "
              "epoch(s) in %.2fs, finished %d/%d, both slaves "
              "reconnected and exited cleanly"
              % (int(resumed["n"]), recovery_s, dist["epochs"],
                 EPOCHS), file=sys.stderr)
    finally:
        for proc in [master1, master2] + slaves:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()
        import shutil
        shutil.rmtree(snapdir, ignore_errors=True)


def orchestrate_chaos_spmd_kill():
    """``--chaos spmd-kill`` (ISSUE 13): the SPMD-mesh analog of
    ``--chaos kill``. A rendezvous anchor plus TWO supervised
    ``jax.distributed`` DP worker processes (4 virtual CPU devices
    each, one 8-way data mesh) train the demo config with per-epoch
    sharded checkpoints; once the first epoch's generation commits,
    rank 1's SUPERVISOR and worker are both SIGKILLed (a whole-host
    loss — detection is the kernel-closed rendezvous socket). The
    surviving supervisor must kill its wedged worker, re-form the
    mesh at world size 1, restore the last complete generation and
    finish EVERY epoch; measured are time-to-reform (kill -> new
    generation running) and the server's break->formed recovery."""
    import signal
    import tempfile

    from veles_tpu.parallel.elastic import RendezvousServer

    epochs = int(os.environ.get("VELES_DIST_EPOCHS", 6))
    workdir = tempfile.mkdtemp(prefix="veles_spmd_chaos_")
    snaps = os.path.join(workdir, "snaps")
    outs = [os.path.join(workdir, "h%d.json" % i) for i in range(2)]
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env_base["PYTHONPATH"] = HERE + (
        os.pathsep + env_base["PYTHONPATH"]
        if env_base.get("PYTHONPATH") else "")
    server = RendezvousServer(expected=2, min_workers=1, settle_s=0.5,
                              heartbeat_timeout_s=3.0).start()
    addr = "%s:%d" % server.address
    procs = []

    def worker_pid(proc, gen):
        for name, kv in proc.events:
            if name == "spmd_worker" and kv.get("gen") == str(gen):
                return int(kv["pid"])
        return None

    try:
        for i in range(2):
            cmd = [sys.executable, "-m",
                   "veles_tpu.parallel.elastic", "supervise",
                   "--rdzv", addr, "--member", "h%d" % i,
                   "--snapshots", snaps,
                   "--max-restarts", "3" if i == 0 else "0",
                   "--worker-env", "JAX_PLATFORMS=cpu",
                   "--worker-env",
                   "XLA_FLAGS=--xla_force_host_platform_device_count=4",
                   "--", sys.executable, "-m",
                   "veles_tpu.parallel.elastic", "worker-demo",
                   "--out", outs[i], "--epochs", str(epochs),
                   "--epoch-sleep", "0.5"]
            procs.append(_spawn("supervise", tpu=False,
                                extra_env=env_base,
                                tag="sup%d" % i, argv=cmd))
        # wait for the first post-epoch generation to COMMIT, so the
        # kill provably lands mid-run with a restorable checkpoint
        deadline = time.time() + 600
        while time.time() < deadline:
            done = [d for d in (os.listdir(snaps)
                                if os.path.isdir(snaps) else [])
                    if d.endswith(".shards") and
                    int(d.split(".")[-2]) >= 1 and
                    os.path.exists(os.path.join(snaps, d,
                                                "MANIFEST.json"))]
            if done:
                break
            if any(p.poll() is not None for p in procs):
                raise SystemExit("a supervisor died before the first "
                                 "checkpoint committed")
            time.sleep(0.1)
        else:
            raise SystemExit("no epoch-1 checkpoint within 600s")
        victim_worker = worker_pid(procs[1], 0)
        t_kill = time.time()
        os.kill(procs[1].pid, signal.SIGKILL)  # the "host" dies...
        if victim_worker:
            try:  # ...taking its worker process group with it
                os.killpg(victim_worker, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        procs[1].wait()
        print("EVENT spmd_kill t=%.6f" % t_kill, file=sys.stderr,
              flush=True)
        # survivor re-forms at world size 1
        while time.time() < deadline and not (
                server.generation >= 1 and server.phase in
                ("running", "done")):
            time.sleep(0.05)
        t_reform = time.time()
        rc0 = procs[0].wait(timeout=600)
        total_s = time.time() - t_kill
        history = json.load(open(outs[0]))
        report = {"mode": "chaos_spmd_kill", "epochs": epochs,
                  "time_to_reform_s": round(t_reform - t_kill, 3),
                  "reform_recovery_s":
                      round(server.last_recovery_s or -1, 3),
                  "kill_to_completion_s": round(total_s, 3),
                  "epochs_completed": len(history),
                  "world_after": server.world_size,
                  "participants_lost": server.lost_total,
                  "survivor_rc": rc0}
        print(json.dumps(report))
        if rc0 != 0:
            raise SystemExit("surviving supervisor exited rc=%d" % rc0)
        if len(history) != epochs:
            raise SystemExit(
                "spmd kill run completed %d/%d epochs — the recovery "
                "plane lost work" % (len(history), epochs))
        if server.world_size != 1 or server.lost_total < 1:
            raise SystemExit("mesh did not re-form at world size 1")
        print("chaos spmd-kill leg PASSED: re-formed at world 1 in "
              "%.2fs (server break->formed %.2fs), %d/%d epochs after "
              "restore" % (t_reform - t_kill,
                           server.last_recovery_s or -1,
                           len(history), epochs), file=sys.stderr)
    finally:
        server.stop()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        # orphaned workers die with their process groups
        for proc in procs:
            for gen in range(0, 8):
                pid = worker_pid(proc, gen)
                if pid:
                    try:
                        os.killpg(pid, signal.SIGKILL)
                    except (ProcessLookupError, PermissionError,
                            OSError):
                        pass


def orchestrate_chip():
    env = {"VELES_DIST_CONFIG": CONFIG}
    alone = _drain(_spawn("standalone", tpu=True, extra_env=env),
                   "standalone")
    dist = _one_round(1, tpu_slave=True, config=CONFIG)
    table = {
        "mode": "chip", "config": CONFIG,
        "standalone_samples_per_sec": alone["samples_per_sec"],
        "distributed_1slave_samples_per_sec": dist["samples_per_sec"],
        "overhead_pct": round(
            100 * (1 - dist["samples_per_sec"] /
                   alone["samples_per_sec"]), 1),
        "segment_size": SEGMENT, "epochs": EPOCHS,
    }
    print(json.dumps(table))


def main():
    if os.environ.get("VELES_DIST_DEBUG"):
        import faulthandler
        faulthandler.dump_traceback_later(
            int(os.environ.get("VELES_DIST_DEBUG")), repeat=True,
            file=sys.stderr)
    if len(sys.argv) < 2:
        orchestrate_chip()
    elif sys.argv[1] == "--cpu-protocol":
        orchestrate_cpu_protocol()
    elif sys.argv[1] == "--gspmd":
        orchestrate_gspmd()
    elif sys.argv[1] == "gspmd-merge":
        run_gspmd_merge()
    elif sys.argv[1] == "--chaos":
        kind = sys.argv[2] if len(sys.argv) > 2 else "straggler"
        if kind == "straggler":
            orchestrate_chaos_straggler()
        elif kind == "kill":
            orchestrate_chaos_kill()
        elif kind == "master-restart":
            orchestrate_chaos_master_restart()
        elif kind == "spmd-kill":
            orchestrate_chaos_spmd_kill()
        else:
            raise SystemExit("unknown chaos kind %r" % kind)
    elif sys.argv[1] == "standalone":
        run_standalone()
    elif sys.argv[1] == "master":
        run_master(int(sys.argv[2]) if len(sys.argv) > 2 else 1,
                   int(sys.argv[3]) if len(sys.argv) > 3 else 0)
    elif sys.argv[1] == "slave":
        run_slave(int(sys.argv[2]))
    elif sys.argv[1] == "shmbench":
        run_shmbench()
    else:
        raise SystemExit("unknown mode %r" % sys.argv[1])


if __name__ == "__main__":
    sys.exit(main())
