#!/usr/bin/env python3
"""Input-pipeline overlap bench (ISSUE 8): a deliberately throttled
loader, streamed out-of-core, synchronous vs prefetched.

Every leg trains the SAME seeded workflow with the dataset forced
out-of-core (tiny ``VELES_SHARD_MB``) and a fixed per-shard host-ETL
sleep injected (``--etl-ms`` -> ``VELES_ETL_THROTTLE_MS``) — the
"loader is the bottleneck" scenario. Legs differ ONLY in pipeline
shape:

* ``sync``   — ``VELES_PREFETCH=0``: ETL+transfer inline on the step
  thread (the pre-pipeline behavior);
* ``double`` — depth 2, 1 worker: the default double-buffer (ETL for
  shard N+1 hides behind shard N's compute);
* ``deep``   — depth 4, 4 workers: ETL parallelism on top, for when a
  single worker's ETL is slower than compute.

Per leg: step-thread input wait (``veles_step_input_wait_ms`` sum /
p50), starvation fraction, wall time and the final loss — which must
be IDENTICAL across legs (the pipeline must not change the math; the
bench asserts it). Prints one JSON line per leg and a ``summary`` line
with the sync/deep wait ratio — the committed docs/PERF.md r10 table.

Usage (on the chip; it fails without one)::

    python scripts/input_bench.py [--etl-ms 30] [--epochs 2]
        [--config fc|conv]

CI's structural leg names the CPU instead: ``JAX_PLATFORMS=cpu python
scripts/input_bench.py --backend cpu ...``.
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


def build_workflow(config, epochs):
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher

    prng.get().seed(42)
    prng.get("loader").seed(43)
    if config == "fc":
        import numpy
        from veles_tpu.models.mnist import MnistWorkflow

        rng = numpy.random.RandomState(7)

        def provider():
            x = rng.rand(4200, 12, 12).astype(numpy.float32)
            y = (x.reshape(len(x), -1).sum(1) > 72).astype(numpy.int32)
            return x[:4000], y[:4000], x[4000:], y[4000:]

        wf = MnistWorkflow(DummyLauncher(), provider=provider,
                           layers=(128,), minibatch_size=200,
                           learning_rate=0.05, max_epochs=epochs)
    elif config == "conv":
        from veles_tpu.models.alexnet import (AlexNetWorkflow,
                                              SyntheticImageLoader,
                                              small_alexnet_layers)
        wf = AlexNetWorkflow(
            DummyLauncher(),
            loader_factory=lambda w: SyntheticImageLoader(
                w, n_train=1024, n_valid=128, side=32, n_classes=10,
                minibatch_size=128),
            layers=small_alexnet_layers(n_classes=10),
            max_epochs=epochs)
    else:
        raise SystemExit("unknown --config %r" % config)
    wf.initialize(device=Device(backend=BACKEND))
    return wf


def run_leg(name, config, epochs, depth, workers):
    from veles_tpu.loader import prefetch
    from veles_tpu.telemetry.registry import get_registry
    from veles_tpu.train import FusedTrainer

    registry = get_registry()
    for metric in ("veles_step_input_wait_ms", "veles_prefetch_etl_ms",
                   "veles_prefetch_h2d_ms",
                   "veles_input_starvation_fraction"):
        family = registry.get(metric)
        if family is not None:
            family.reset()
    wf = build_workflow(config, epochs)
    trainer = FusedTrainer(wf, stream=True, prefetch_depth=depth,
                           prefetch_workers=workers)
    assert trainer.streaming, "leg must run out-of-core"
    start = time.time()
    history = trainer.train()
    wall = time.time() - start
    wait = registry.get("veles_step_input_wait_ms").labels()
    gauge = registry.get("veles_input_starvation_fraction")
    train_starve = {labels["phase"]: child.value
                    for labels, child in gauge.series()}.get("train")
    row = {
        "leg": name, "config": config, "depth": depth,
        "workers": workers, "epochs": len(history),
        "shards": wait.count,
        "input_wait_ms": round(wait.sum, 1),
        "input_wait_p50_ms": round(wait.percentile(50), 2),
        "train_starvation": round(train_starve or 0.0, 3),
        "wall_s": round(wall, 2),
        "final_loss": round(
            history[-1]["validation"]["normalized"], 6),
        "batches_per_shard": trainer._batches_per_shard,
    }
    prefetch.shutdown_all()
    print(json.dumps(row), flush=True)
    return row


def main():
    global BACKEND
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n")[0])
    parser.add_argument("--etl-ms", type=float, default=30.0,
                        help="injected host-ETL sleep per shard")
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--config", default="fc",
                        choices=("fc", "conv"))
    parser.add_argument("--shard-mb", type=float, default=0.25,
                        help="forced shard size (keeps it out-of-core)")
    parser.add_argument("--min-ratio", type=float, default=0.0,
                        help="fail unless sync/deep wait ratio >= this "
                             "(the CI overlap guard)")
    parser.add_argument("--backend", default=BACKEND,
                        choices=("tpu", "cpu"),
                        help="device backend, by name")
    args = parser.parse_args()
    BACKEND = args.backend

    os.environ["VELES_ETL_THROTTLE_MS"] = str(args.etl_ms)
    os.environ["VELES_SHARD_MB"] = str(args.shard_mb)

    legs = [("sync", 0, 1), ("double", 2, 1), ("deep", 4, 4)]
    rows = [run_leg(name, args.config, args.epochs, depth, workers)
            for name, depth, workers in legs]

    losses = {r["final_loss"] for r in rows}
    if len(losses) != 1:
        raise SystemExit("pipeline changed the math: losses %r" % losses)
    sync, deep = rows[0], rows[-1]
    ratio = sync["input_wait_ms"] / max(deep["input_wait_ms"], 1e-9)
    print(json.dumps({
        "leg": "summary", "etl_ms": args.etl_ms,
        "sync_wait_ms": sync["input_wait_ms"],
        "double_wait_ms": rows[1]["input_wait_ms"],
        "deep_wait_ms": deep["input_wait_ms"],
        "wait_ratio_sync_over_deep": round(ratio, 2),
        "loss_match": True,
    }), flush=True)
    if args.min_ratio and ratio < args.min_ratio:
        raise SystemExit(
            "overlap regressed: sync/deep input-wait ratio %.2f < %.1f"
            % (ratio, args.min_ratio))
    return 0


if __name__ == "__main__":
    sys.exit(main())
