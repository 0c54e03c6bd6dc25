#!/usr/bin/env python3
"""Profile the fused AlexNet train step and print per-op attribution
(VERDICT r4 next #4: pin the MFU story from a trace, not ablations).

Captures a ``jax.profiler`` trace of steady-state compiled segments
(same discipline as bench.py's timed window: warm first, then trace),
parses the xplane protobuf, and aggregates the device plane's
synchronous op line ('XLA Ops', exclusive durations) three ways:

* top ops by device time;
* by SOURCE LINE (XLA carries ``source=veles_tpu/nn/<file>:<line>``
  per op — the repo's own layer attribution, no guessing);
* achieved FLOP/s and HBM GB/s per source bucket from the ``flops`` /
  ``bytes_accessed`` stats — the direct test of the bandwidth-floor
  claim in docs/PERF.md.

Usage: python scripts/profile_step.py [trace_dir] [--reuse]
                                      [--attribution]

``--attribution`` skips the xplane machinery entirely and reports from
the telemetry registry instead (ISSUE 7): drives warmed compiled
segments, harvests ``Compiled.cost_analysis()`` through the cost book,
and prints the per-op attribution table (analytic FLOPs/bytes,
arithmetic intensity, measured ms, achieved TFLOP/s, roofline bound
verdict), the step MFU, the startup-phase breakdown and a memory
sample — the same numbers ``/profile.json`` serves live. Under
``VELES_OFFLOAD=1`` the trainer runs out-of-core and the table grows
one ``offload:h2d/g<k>`` / ``offload:d2h/g<k>`` roofline row per
streamed layer group (bytes moved, p50 ms, achieved GB/s), followed
by a transfer-vs-compute verdict naming a transfer-bound step. Runs on
the chip only (``Device(backend="tpu")``); MFU and verdicts come from
the peak table in ``veles_tpu/telemetry/profiler.py``.

Env: VELES_PROFILE_SEGMENTS (default 2) — segments inside the trace.
"""

import collections
import glob
import logging
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

logging.disable(logging.WARNING)

N_TRAIN = int(os.environ.get("VELES_BENCH_NTRAIN", 2048))
BATCH = int(os.environ.get("VELES_BENCH_BATCH", 128))
SEGMENTS = int(os.environ.get("VELES_PROFILE_SEGMENTS", 2))
PRECISION = os.environ.get("VELES_BENCH_PRECISION", "bfloat16")
# flagship geometry by default; shrinkable so the CPU CI smoke can
# drive the identical code path in seconds instead of hours
SIDE = int(os.environ.get("VELES_BENCH_SIDE", 227))
CLASSES = int(os.environ.get("VELES_BENCH_CLASSES", 1000))


def build_trainer():
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.alexnet import (ALEXNET_LAYERS,
                                          AlexNetWorkflow,
                                          SyntheticImageLoader)
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.train import FusedTrainer

    set_policy(PRECISION)
    prng.get().seed(42)
    prng.get("loader").seed(43)
    wf = AlexNetWorkflow(
        DummyLauncher(),
        loader_factory=lambda w: SyntheticImageLoader(
            w, n_train=N_TRAIN, n_valid=BATCH, side=SIDE,
            n_classes=CLASSES, minibatch_size=BATCH, dtype="bfloat16"),
        layers=ALEXNET_LAYERS, max_epochs=1)
    wf.initialize(device=Device(backend="tpu"))
    return FusedTrainer(wf)


def capture(trace_dir):
    import jax

    import bench  # repo-root bench.py: shared warm-up discipline

    trainer = build_trainer()
    # compile + settle OUTSIDE the trace
    params, states, idx, keys = bench.prepare_segment_run(
        trainer, warm=2, seed=0)
    t0 = time.time()
    with jax.profiler.trace(trace_dir):
        for _ in range(SEGMENTS):
            params, states, losses, _ = trainer._train_segment(
                params, states, idx, keys)
        float(losses[-1])
    wall = time.time() - t0
    print("traced %d segments (%d steps) in %.2fs"
          % (SEGMENTS, SEGMENTS * idx.shape[0], wall), file=sys.stderr)
    return wall, SEGMENTS * idx.shape[0]


def _load_xplanes(trace_dir):
    try:
        from xprof.protobuf import xplane_pb2
    except ImportError:
        # this environment's xprof wheel ships no xplane proto; the
        # tensorflow bundle's tsl copy is the same message
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError("no xplane.pb under %s" % trace_dir)
    spaces = []
    for path in paths:
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        spaces.append(xs)
    return spaces


def _structural(name):
    # umbrella ops that CONTAIN the real work on the same line:
    # counting them would double every child
    return (name.startswith("%while") or name.startswith("jit_")
            or name.isdigit() or name.startswith("%call"))


def op_records(trace_dir):
    """[{name, dur_s, source, category, flops, bytes}] from the device
    plane's 'XLA Ops' line (host '/host:CPU' fallback for CPU runs)."""
    spaces = _load_xplanes(trace_dir)

    def collect(plane, line_filter):
        stat_names = {mid: m.name
                      for mid, m in plane.stat_metadata.items()}
        metas = {}
        for mid, meta in plane.event_metadata.items():
            stats = {}
            for st in meta.stats:
                key = stat_names.get(st.metadata_id)
                stats[key] = (st.str_value or st.ref_value or
                              st.int64_value)
            metas[mid] = (meta.name, stats)
        per_op = {}
        for line in plane.lines:
            if line_filter is not None and line.name != line_filter:
                continue
            for ev in line.events:
                name, stats = metas.get(ev.metadata_id,
                                        (str(ev.metadata_id), {}))
                if _structural(name):
                    continue
                rec = per_op.setdefault(name, {
                    "name": name, "dur_s": 0.0,
                    "source": str(stats.get("source", "")),
                    "category": str(stats.get("hlo_category", "")),
                    "flops": int(stats.get("flops", 0) or 0),
                    "bytes": int(stats.get("bytes_accessed", 0) or 0),
                    "calls": 0})
                rec["dur_s"] += ev.duration_ps / 1e12
                rec["calls"] += 1
        return list(per_op.values())

    for tier, line_filter in (("device", "XLA Ops"), ("host", None)):
        best = None
        for xs in spaces:
            for plane in xs.planes:
                is_device = ("TPU" in plane.name or
                             "/device:" in plane.name)
                want = (is_device if tier == "device"
                        else "/host:CPU" in plane.name)
                if not want:
                    continue
                recs = collect(plane, line_filter)
                total = sum(r["dur_s"] for r in recs)
                if recs and (best is None or total > best[1]):
                    best = (plane.name, total, recs)
        if best is not None:
            return best
    raise RuntimeError("no plane with events found")


def per_op_table(trace_dir):
    """(plane, total_s, [(name, dur_s, pct)]) — compat summary."""
    plane, total, recs = op_records(trace_dir)
    rows = [(r["name"], r["dur_s"], 100.0 * r["dur_s"] / total)
            for r in sorted(recs, key=lambda r: -r["dur_s"])]
    return plane, total, rows


def _source_bucket(rec):
    src = rec["source"]
    if "veles_tpu" in src:
        # veles_tpu/nn/normalization.py:34 -> nn/normalization.py:34
        return src.split("veles_tpu/", 1)[1]
    if src:
        return os.path.basename(src)
    cat = rec["category"] or "uncategorized"
    return "<no source: %s>" % cat


def _fmt(value, spec="%.2f", missing="-"):
    return missing if value is None else spec % value


def attribution_main():
    """The registry-sourced attribution report (no xplane parsing)."""
    import bench  # repo-root bench.py: shared warm-up discipline

    from veles_tpu.telemetry import profiler

    book = profiler.get_cost_book()
    trainer = build_trainer()
    # compile + harvest happen inside the first (warm) call; the
    # timed calls below then observe steady-state segments
    params, states, idx, keys = bench.prepare_segment_run(
        trainer, warm=2, seed=0)
    for _ in range(SEGMENTS):
        t0 = time.perf_counter()
        params, states, losses, _ = trainer._train_segment(
            params, states, idx, keys)
        float(losses[-1])  # block: async dispatch time would be a lie
        elapsed = time.perf_counter() - t0
        book.observe_ms("train_segment", elapsed)
        book.record_step_mfu("train_segment", elapsed)

    report = profiler.profile_report()
    dev = report["device"]
    print("attribution (telemetry registry; %d batches/segment, "
          "batch %d, %s)" % (idx.shape[0], BATCH, PRECISION))
    print("device peaks: %s TFLOP/s, %s GB/s HBM (ridge %s FLOP/B)"
          % (_fmt(dev["peak_tflops"], "%.1f"),
             _fmt(dev["hbm_gbps"], "%.0f"),
             _fmt(dev["ridge_flops_per_byte"], "%.1f")))
    print()
    print("| op | GFLOP | MB | FLOP/B | calls | p50 ms | "
          "TFLOP/s | GB/s | bound |")
    print("|---|---|---|---|---|---|---|---|---|")
    for row in report["ops"]:
        print("| %s | %s | %s | %s | %d | %s | %s | %s | %s |" % (
            row["op"],
            _fmt(row.get("flops") and row["flops"] / 1e9, "%.2f"),
            _fmt(row.get("bytes") and row["bytes"] / 1e6, "%.1f"),
            _fmt(row.get("arithmetic_intensity"), "%.1f"),
            row.get("calls") or 0,
            _fmt(row.get("p50_ms"), "%.2f"),
            _fmt(row.get("achieved_tflops"), "%.2f"),
            _fmt(row.get("achieved_gbps"), "%.1f"),
            row.get("bound", "-")))
    print()
    off_rows = [r for r in report["ops"]
                if r["op"].startswith("offload:")]
    if off_rows:
        # out-of-core run (VELES_OFFLOAD=1): the CostBook carries one
        # roofline row per streamed group direction; name the verdict
        # the roofline table only implies — is the step transfer-bound?
        seg = next((r for r in report["ops"]
                    if r["op"] == "train_segment"), {})
        xfer_ms = sum((r.get("p50_ms") or 0.0) * (r.get("calls") or 0)
                      for r in off_rows) / max(SEGMENTS, 1)
        moved_mb = sum((r.get("bytes") or 0) * (r.get("calls") or 0)
                       for r in off_rows) / max(SEGMENTS, 1) / 1e6
        seg_ms = seg.get("p50_ms") or 0.0
        verdict = ("TRANSFER-bound" if seg_ms and xfer_ms > 0.5 * seg_ms
                   else "compute-bound")
        print("offload traffic: %.1f MB moved / %.1f ms transfer time "
              "per segment (%d h2d/d2h rows) vs segment p50 %.1f ms "
              "-> %s step" % (moved_mb, xfer_ms, len(off_rows),
                              seg_ms, verdict))
        print()
    mfu = report.get("step_mfu")
    print("step MFU: " + ("%.1f%%" % (mfu * 100.0) if mfu
                          else "n/a (no device peak known)"))
    print()
    print("startup phases:")
    phases = report["phases_ms"]
    total = sum(phases.values())
    for name, ms in phases.items():
        print("  %-18s %9.1f ms  %5.1f%%"
              % (name, ms, 100.0 * ms / total if total else 0.0))
    print("  %-18s %9.1f ms" % ("total", total))
    mem = report.get("memory") or {}
    for dev_label, m in sorted((mem.get("devices") or {}).items()):
        print("memory %s: live %.2f GB, peak %.2f GB, limit %.2f GB"
              % (dev_label, m.get("live_bytes", 0) / 2**30,
                 m.get("peak_bytes", 0) / 2**30,
                 m.get("limit_bytes", 0) / 2**30))
    if mem.get("host_rss_bytes"):
        print("memory host RSS: %.2f GB"
              % (mem["host_rss_bytes"] / 2**30))


def main():
    args = [a for a in sys.argv[1:]
            if a not in ("--reuse", "--attribution")]
    reuse = "--reuse" in sys.argv
    if "--attribution" in sys.argv:
        return attribution_main()
    trace_dir = (args[0] if args
                 else os.path.join("/tmp", "veles_profile_%d"
                                   % os.getpid()))
    if reuse:
        wall, steps = 0.0, SEGMENTS * (N_TRAIN // BATCH)
    else:
        wall, steps = capture(trace_dir)
    plane, total_s, recs = op_records(trace_dir)
    ms = 1e3 / steps  # per-step scale
    print("device plane: %s — %.3fs op time over %d steps "
          "(%.2f ms/step; wall %.2fs incl. host)"
          % (plane, total_s, steps, total_s * ms, wall))

    print()
    print("top ops (per step):")
    print("| op | source | ms/step | % | TFLOP/s | GB/s |")
    print("|---|---|---|---|---|---|")
    for r in sorted(recs, key=lambda r: -r["dur_s"])[:20]:
        # flops/bytes stats are per CALL; dur_s is summed over calls
        per_call = r["dur_s"] / max(r["calls"], 1)
        tf = r["flops"] / per_call / 1e12 if per_call else 0.0
        gb = r["bytes"] / per_call / 1e9 if per_call else 0.0
        print("| `%s` | %s | %.2f | %.1f%% | %.1f | %.0f |"
              % (r["name"].split(" = ")[0][:40],
                 _source_bucket(r), r["dur_s"] * ms,
                 100.0 * r["dur_s"] / total_s, tf, gb))

    print()
    print("by source line (layer attribution):")
    print("| source | ms/step | % | avg GB/s |")
    print("|---|---|---|---|")
    buckets = collections.defaultdict(lambda: [0.0, 0.0])
    for r in recs:
        b = buckets[_source_bucket(r)]
        b[0] += r["dur_s"]
        b[1] += r["bytes"] * r["calls"]
    for src, (secs, byts) in sorted(buckets.items(),
                                    key=lambda kv: -kv[1][0]):
        print("| %s | %.2f | %.1f%% | %.0f |"
              % (src, secs * ms, 100.0 * secs / total_s,
                 byts / secs / 1e9 if secs else 0.0))


if __name__ == "__main__":
    sys.exit(main())
