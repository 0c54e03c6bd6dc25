#!/usr/bin/env python3
"""Measured perf for every BASELINE config + the beyond-parity units
(VERDICT r4 next #2 — the reference imposed the same discipline on
itself via DeviceBenchmark, ``veles/accelerated_units.py:706-824``).

One row per compute path: steady-state training samples/s on the chip
with bench.py's read-free timed-window discipline (warm segments pay
the compile, then chunked compiled segments with ONE forcing read per
chunk), plus analytic model TFLOP/s against the chip's measured
large-matmul peak (MFU). bench.py stays the driver's AlexNet contract;
this script is the breadth table committed in docs/PERF.md.

One process per chip: the rows that run in a child process (offload,
sched) go first, one at a time, and only then does this process touch
JAX and take the chip for the rest. The sched row's gang workers are
pinned to CPU devices by sched_bench.py itself. Exits non-zero when
any row failed.

MFU is matmul-FLOPs-only (the scaling-book convention bench.py uses):
configs dominated by tiny matmuls (FC-100, SOM 8x8) honestly report
single-digit MFU — they are latency/bandwidth bound, which is the
point of publishing them.

Usage: python scripts/bench_all.py [config ...]  (default: all)
Prints one markdown row per config on stdout, diagnostics on stderr.
"""

import json
import logging
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

logging.disable(logging.WARNING)

MIN_WINDOW_S = float(os.environ.get("VELES_BENCH_ALL_WINDOW", 10.0))
PRECISION = os.environ.get("VELES_BENCH_PRECISION", "bfloat16")


def _seed():
    from veles_tpu import prng
    prng.get().seed(1234)
    prng.get("loader").seed(1235)


def _bench_fused(wf):
    """Steady samples/s with bench.py's shared disciplines
    (prepare_segment_run pays compile + settle, then the timed
    window). Returns (samples_per_sec, (step_p50_ms, step_p95_ms)) —
    the step tail comes from the telemetry registry histogram the
    window feeds."""
    import bench

    from veles_tpu.telemetry.registry import get_registry
    from veles_tpu.train import FusedTrainer
    trainer = FusedTrainer(wf)
    params, states, idx, keys = bench.prepare_segment_run(
        trainer, warm=2, seed=0)
    step_hist = get_registry().histogram("veles_bench_step_ms")
    step_hist.reset()  # one config's tail must not leak into the next
    params, states, segs, elapsed, _ = bench.timed_segment_window(
        trainer, params, states, idx, keys, MIN_WINDOW_S)
    step = step_hist.labels()
    mb = trainer.workflow.loader.max_minibatch_size
    valid = (idx >= 0).sum() / idx.shape[0] / mb  # fill fraction
    return (segs * idx.shape[0] * mb * float(valid) / elapsed,
            (step.percentile(50), step.percentile(95)))


# -- config builders -------------------------------------------------------


def build_fc():
    from veles_tpu.datasets import golden_digits
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistWorkflow
    _seed()
    return MnistWorkflow(DummyLauncher(),
                         provider=golden_digits(n_train=12000,
                                                n_valid=2000),
                         layers=(100,), minibatch_size=500,
                         max_epochs=1)


def build_conv():
    from veles_tpu.datasets import golden_digits
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistLoader
    from veles_tpu.models.parity import CONV_LAYERS
    from veles_tpu.standard_workflow import StandardWorkflow
    _seed()
    return StandardWorkflow(
        DummyLauncher(),
        loader=lambda w: MnistLoader(
            w, provider=golden_digits(n_train=12000, n_valid=2000),
            flatten=False, minibatch_size=250),
        layers=CONV_LAYERS, loss="softmax", max_epochs=1)


def build_cifar():
    from veles_tpu.datasets import golden_objects
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.cifar import CifarWorkflow
    _seed()
    return CifarWorkflow(DummyLauncher(),
                         provider=golden_objects(n_train=10000,
                                                 n_valid=2000),
                         minibatch_size=250, max_epochs=1)


def build_ae():
    from veles_tpu.datasets import golden_digits
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist_ae import MnistAEWorkflow
    _seed()
    return MnistAEWorkflow(DummyLauncher(),
                           provider=golden_digits(n_train=12000,
                                                  n_valid=2000),
                           bottleneck=100, minibatch_size=500,
                           learning_rate=0.001, max_epochs=1)


def build_attention():
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.samples import (SequenceProvider,
                                          SequenceWorkflow)
    _seed()
    return SequenceWorkflow(
        DummyLauncher(),
        provider=SequenceProvider(n_train=4096, n_valid=256,
                                  seq=256, dim=256),
        minibatch_size=64, heads=8, max_epochs=1)


def build_moe():
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.samples import (SequenceProvider,
                                          SequenceWorkflow)
    _seed()
    return SequenceWorkflow(
        DummyLauncher(),
        provider=SequenceProvider(n_train=4096, n_valid=256,
                                  seq=128, dim=256),
        minibatch_size=64, heads=8, moe=True, n_experts=8,
        max_epochs=1)


def bench_som():
    """SOM has no GD chain: time the jitted batch update directly —
    that IS config 4's training compute path (nn/kohonen.py)."""
    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu.nn.kohonen import _make_grid, _som_update

    sx = sy = 8
    features = 784
    batch = 1024
    rng = numpy.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, features).astype(numpy.float32))
    codebook = jnp.asarray(
        rng.rand(sx * sy, features).astype(numpy.float32) * 0.2 - 0.1)
    grid = jnp.asarray(_make_grid(sx, sy))
    sigma, lr = numpy.float32(2.0), numpy.float32(0.1)

    codebook, win = _som_update(codebook, x, grid, sigma, lr)
    win.block_until_ready()  # compile
    steps = 0
    start = time.time()
    while True:
        for _ in range(50):
            codebook, win = _som_update(codebook, x, grid, sigma, lr)
        win.block_until_ready()
        steps += 50
        elapsed = time.time() - start
        if elapsed >= MIN_WINDOW_S:
            break
    rate = steps * batch / elapsed
    # two (batch x units x features) dots per update
    flops = 4.0 * sx * sy * features
    return rate, flops, "Kohonen 8x8 SOM (batch 1024)"


CONFIGS = {
    "fc": (build_fc, "MNIST FC 784-100-10 (config 1, batch 500)"),
    "conv": (build_conv,
             "MNIST conv 16c5-32c5 (config 2 analog, batch 250)"),
    "cifar": (build_cifar,
              "CIFAR cifar10-quick (config 2, batch 250)"),
    "ae": (build_ae, "MNIST AE 784-100-784 (config 4, batch 500)"),
    "attention": (build_attention,
                  "attention 2L seq=256 d=256 h=8 (batch 64)"),
    "moe": (build_moe,
            "attention+MoE 8 experts seq=128 d=256 (batch 64)"),
}


#: rows delegated to a child process: their own metric shape
#: (transfer-wait ratio, preempt->resume seconds and a loss-parity bit
#: — not samples/s), echoed as the child's summary line
DELEGATED = {
    "offload": ["offload_bench.py", "--transfer-ms", "12",
                "--epochs", "1"],
    "sched": ["sched_bench.py", "--quick"],
}


def run_delegated(name):
    """Run one delegated row in a child and echo its summary line.
    True when the child exited 0."""
    import subprocess
    t0 = time.time()
    script, *argv = DELEGATED[name]
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "scripts", script)] + argv,
        capture_output=True, text=True)
    summary = next(
        (line for line in proc.stdout.splitlines()[::-1]
         if '"summary"' in line), proc.stdout.strip())
    print(summary, flush=True)
    ok = proc.returncode == 0
    if not ok:
        print(proc.stderr[-2000:], file=sys.stderr)
    print("%s: %s in %.0fs total"
          % (name, "PASS" if ok else "FAIL", time.time() - t0),
          file=sys.stderr)
    return ok


def main():
    names = sys.argv[1:] or list(CONFIGS) + [
        "som", "serving", "serving-cache", "serving-burst", "offload",
        "sched"]
    # children first: a child that needs the chip fails or hangs once
    # this process has touched JAX and holds it
    failed = [name for name in names
              if name in DELEGATED and not run_delegated(name)]
    names = [name for name in names if name not in DELEGATED]
    if names:
        failed += run_in_process(names)
    if failed:
        print("FAILED rows: %s" % ", ".join(failed), file=sys.stderr)
        return 1
    return 0


def run_in_process(names):
    """The rows that share this process's chip. Returns the failed
    row names."""
    from veles_tpu.backends import Device
    from veles_tpu.nn.precision import set_policy

    import bench  # repo-root bench.py: shared matmul-peak measurement

    failed = []
    set_policy(PRECISION)
    device = Device(backend="tpu")  # the chip by name, or no table
    peak = bench.measured_matmul_peak_tflops()
    print("chip matmul peak: %.1f TF/s, policy=%s, window>=%.0fs"
          % (peak, PRECISION, MIN_WINDOW_S), file=sys.stderr)

    print("| Config | samples/s | model GFLOP/sample | eff TFLOP/s "
          "| MFU | step p50/p95 ms |")
    print("|---|---|---|---|---|---|")
    for name in names:
        t0 = time.time()
        if name == "serving" or name.startswith("serving-"):
            # the serving engine has its own metric shape (QPS vs the
            # legacy path, not samples/s vs MFU) — delegate and print
            # its row verbatim after the table. "serving" is the
            # ISSUE 3 baseline; "serving-{cache,burst,diurnal,
            # multitenant}" are the ISSUE 14 elastic-plane scenarios
            import bench_serving
            scenario = name[len("serving-"):] if "-" in name \
                else "baseline"
            result = bench_serving.SCENARIOS[scenario](quick=True)
            print(bench_serving.markdown_row(result), flush=True)
            print("%s: %s in %.0fs total"
                  % (name, "PASS" if result["pass"] else "FAIL",
                     time.time() - t0), file=sys.stderr)
            if not result["pass"]:
                failed.append(name)
            continue
        if name == "som":
            rate, flops, label = bench_som()
            step_tail = None  # no segment histogram on the SOM path
        else:
            build, label = CONFIGS[name]
            wf = build()
            wf.initialize(device=device)
            flops = bench.model_train_flops_per_sample(wf)
            rate, step_tail = _bench_fused(wf)
        eff = rate * flops / 1e12
        tail = ("%.1f / %.1f" % step_tail if step_tail else "—")
        print("| %s | **%s** | %.4f | %.2f | %.1f%% | %s |"
              % (label,
                 ("{:,.0f}".format(rate)), flops / 1e9, eff,
                 100.0 * eff / peak, tail), flush=True)
        print("%s: %.1f samples/s in %.0fs total"
              % (name, rate, time.time() - t0), file=sys.stderr)
    return failed


if __name__ == "__main__":
    sys.exit(main())
