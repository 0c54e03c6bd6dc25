#!/usr/bin/env python3
"""Out-of-core model-state overlap bench (ISSUE 17): host-offloaded
param/optimizer groups, synchronous vs double-buffered transfers.

Every leg trains the SAME seeded workflow on a CPU-deterministic
model. The offload legs force the params out-of-core (``VELES_OFFLOAD=1``
+ a tiny ``VELES_OFFLOAD_GROUP_MB`` so several layer groups stream per
step) with a fixed per-transfer sleep injected (``--transfer-ms`` ->
``VELES_OFFLOAD_THROTTLE_MS``) — the "interconnect is the bottleneck"
scenario. Legs differ ONLY in ring shape:

* ``incore`` — ``VELES_OFFLOAD=0``: the resident baseline (bounds the
  offloaded step overhead);
* ``sync``   — depth 0: every H2D upload and D2H writeback inline on
  the step thread;
* ``double`` — depth 2, 2 workers: uploads prefetch ahead of compute
  and a writeback thread retires updated groups concurrently.

Per leg: step-thread transfer wait (``veles_offload_wait_ms`` sum /
p50), compute-overlap fraction, wall time and the final loss — which
must be IDENTICAL across legs (offload must not change the math; the
bench asserts it). Prints one JSON line per leg and a ``summary`` line
with the sync/double wait ratio — the perf gate's
``offload_overlap_ratio`` metric.

Usage (on the chip; it fails without one)::

    python scripts/offload_bench.py [--transfer-ms 12] [--epochs 2]
        [--min-ratio 1.5]

CI's structural leg names the CPU instead: ``JAX_PLATFORMS=cpu python
scripts/offload_bench.py --backend cpu ...``.
"""

import argparse
import json
import logging
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

logging.disable(logging.WARNING)

#: backend asked for by name (``--backend``): never ``auto``, so a
#: run without a chip fails instead of timing the CPU
BACKEND = "tpu"


def build_workflow(epochs):
    import numpy

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistWorkflow

    prng.get().seed(42)
    prng.get("loader").seed(43)
    rng = numpy.random.RandomState(7)

    def provider():
        x = rng.rand(2100, 12, 12).astype(numpy.float32)
        y = (x.reshape(len(x), -1).sum(1) > 72).astype(numpy.int32)
        return x[:2000], y[:2000], x[2000:], y[2000:]

    wf = MnistWorkflow(DummyLauncher(), provider=provider,
                       layers=(64, 48), minibatch_size=100,
                       learning_rate=0.05, max_epochs=epochs)
    wf.initialize(device=Device(backend=BACKEND))
    return wf


def run_leg(name, epochs, offload, depth, workers):
    from veles_tpu.telemetry.registry import get_registry
    from veles_tpu.train import FusedTrainer
    from veles_tpu.train import offload as offload_mod

    registry = get_registry()
    for metric in ("veles_offload_h2d_ms", "veles_offload_d2h_ms",
                   "veles_offload_wait_ms",
                   "veles_offload_compute_overlap_fraction"):
        family = registry.get(metric)
        if family is not None:
            family.reset()
    wf = build_workflow(epochs)
    trainer = FusedTrainer(wf, offload=offload, offload_depth=depth,
                           offload_workers=workers)
    assert trainer.offloaded == offload, "leg residency mismatch"
    start = time.time()
    history = trainer.train()
    wall = time.time() - start
    # offload_wait_s is the canonical step-thread transfer wait: the
    # pipeline waits PLUS the sync leg's inline writebacks (which the
    # wait histogram, by design, does not count)
    wait_s = trainer.offload_wait_s
    row = {
        "leg": name, "depth": depth, "workers": workers,
        "epochs": len(history),
        "wall_s": round(wall, 2),
        "final_loss": round(
            history[-1]["validation"]["normalized"], 6),
    }
    if offload:
        wait = registry.get("veles_offload_wait_ms").labels()
        gauge = registry.get("veles_offload_compute_overlap_fraction")
        overlap = {labels["phase"]: child.value
                   for labels, child in gauge.series()}.get("train")
        row.update({
            "groups": trainer._offload_engine.plan.n_groups,
            "transfers": wait.count,
            "offload_wait_ms": round(wait_s * 1e3, 1),
            "offload_wait_p50_ms": round(wait.percentile(50), 2),
            "train_overlap": round(overlap or 0.0, 3),
        })
    offload_mod.shutdown_all()
    print(json.dumps(row), flush=True)
    return row


def main():
    global BACKEND
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n")[0])
    parser.add_argument("--transfer-ms", type=float, default=12.0,
                        help="injected sleep per H2D/D2H group move")
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--group-mb", type=float, default=0.01,
                        help="forced per-group budget (keeps several "
                             "groups streaming per step)")
    parser.add_argument("--min-ratio", type=float, default=0.0,
                        help="fail unless sync/double wait ratio >= "
                             "this (the CI overlap guard)")
    parser.add_argument("--backend", default=BACKEND,
                        choices=("tpu", "cpu"),
                        help="device backend, by name")
    args = parser.parse_args()
    BACKEND = args.backend

    os.environ["VELES_OFFLOAD_THROTTLE_MS"] = str(args.transfer_ms)
    os.environ["VELES_OFFLOAD_GROUP_MB"] = str(args.group_mb)

    # the buffered leg stages a whole batch walk ahead (depth covers
    # the 2G-1 per-batch transfer tasks), two upload workers + the
    # writeback thread giving three concurrent transfer channels
    legs = [("incore", False, 0, 1), ("sync", True, 0, 1),
            ("double", True, 6, 2)]
    rows = [run_leg(name, args.epochs, offload, depth, workers)
            for name, offload, depth, workers in legs]

    losses = {r["final_loss"] for r in rows}
    if len(losses) != 1:
        raise SystemExit("offload changed the math: losses %r" % losses)
    incore, sync, double = rows
    ratio = sync["offload_wait_ms"] / max(double["offload_wait_ms"],
                                          1e-9)
    print(json.dumps({
        "leg": "summary", "transfer_ms": args.transfer_ms,
        "incore_wall_s": incore["wall_s"],
        "sync_wait_ms": sync["offload_wait_ms"],
        "double_wait_ms": double["offload_wait_ms"],
        "wait_ratio_sync_over_double": round(ratio, 2),
        "step_overhead_ratio": round(
            double["wall_s"] / max(incore["wall_s"], 1e-9), 2),
        "loss_match": True,
    }), flush=True)
    if args.min_ratio and ratio < args.min_ratio:
        raise SystemExit(
            "overlap regressed: sync/double offload-wait ratio "
            "%.2f < %.1f" % (ratio, args.min_ratio))
    return 0


if __name__ == "__main__":
    sys.exit(main())
