#!/usr/bin/env python3
"""Chip smoke: the normal training path, once, on a real TPU.

    python chip_smoke.py [--gspmd 4x1]

One process. It refuses to start on anything but a TPU, then drives
``python -m veles_tpu``'s own entry (``veles_tpu.__main__.Main``) with
``-a tpu --precision bfloat16`` and THIS file as the workflow file: the
CLI imports it and calls :func:`run`, the ``run(load, main)`` contract
of every workflow file. The model is AlexNet at full width
(``ALEXNET_LAYERS``, 227x227x3, 1000 classes, batch 128); only the
sample count is small, and the data and weights are random from fixed
seeds. So the path is Launcher -> FusedRunner -> FusedTrainer, exactly
what a user's run takes.

Phase 1 (train) checks, by the repo's own means: the launcher took the
fused path (``run_mode_used``), the requested epochs closed
(``decision.epoch_history``), no detector of the flight recorder
tripped and every sweep's loss is finite, the untrained model's loss
is ln(1000) within 0.1, every parametrized layer's weights moved from
their initial values and live on a TPU device, the dataset is resident
and space-to-depth staged, and the peak table knows this device.

Phase 2 (kernels) compiles the Pallas entry point of
``veles_tpu/ops`` with ``interpret=False`` at one real shape, checks
that the lowered program really holds a Mosaic kernel (not the XLA
form the dispatch rule returns off the chip), and compares the result
with the XLA form.

``--gspmd MESH`` (a four-chip host) first takes the same run on one
chip as the reference, then repeats it through the launcher's
``--gspmd MESH`` and checks the mesh, the per-device memory, the
collective bytes of the partitioned step and the per-epoch losses
against the reference.

Times and sizes are printed as information, under no metric name. The
named checks are printed as one ``checks: {...}`` line; the last line
of stdout is one JSON object with exactly ``ok`` and ``device``
(platform, kind, count as JAX reports them). The exit code is 0 only
if every check held. A failure is a failure: no check is downgraded to
a warning.
"""

import argparse
import functools
import gc
import json
import math
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from veles_tpu.config import root  # noqa: E402
from veles_tpu.models.alexnet import (ALEXNET_LAYERS,  # noqa: E402
                                      AlexNetWorkflow,
                                      SyntheticImageLoader)

#: what the run asks the CLI for, and how the kernels are called. Both
#: are constants of the smoke; only a debugging session on a CPU sets
#: them otherwise, from outside, to rehearse the script before a chip
#: call (main() cannot: it leaves at require_tpu() first)
BACKEND = "tpu"
INTERPRET = False

BATCH = 128
N_TRAIN = 2048
N_VALID = 128
EPOCHS = 2
SIDE = 227
CLASSES = 1000
#: |untrained loss - ln(CLASSES)| bound: a fresh softmax head is near
#: uniform over the classes
FRESH_LOSS_BAND = 0.1
#: |GSPMD loss - one-chip loss| bound per sweep. Not bit-equal: the
#: partitioned step sums gradient partials over four shards in another
#: order than one chip sums the whole batch, under bf16 activations
GSPMD_LOSS_TOLERANCE = 2e-2


class SmokeWorkflow(AlexNetWorkflow):
    """AlexNet that keeps a host copy of its freshly initialized
    parameters, so the smoke can tell that training moved them."""

    hide_from_registry = True

    def initialize(self, **kwargs):
        import numpy
        result = super(SmokeWorkflow, self).initialize(**kwargs)
        self.initial_params = [
            {name: numpy.array(arr.map_read())
             for name, arr in fwd.param_arrays().items()}
            for fwd in self.forwards]
        return result


def run(load, main):
    """The workflow-file contract of the CLI (``__main__._run_regular``).
    Sizes arrive through the CLI's own ``root.x=value`` overrides."""
    size = root.chip_smoke
    load(SmokeWorkflow,
         loader_factory=lambda w: SyntheticImageLoader(
             w, n_train=size.n_train, n_valid=size.n_valid, side=SIDE,
             n_classes=CLASSES, minibatch_size=BATCH, dtype="bfloat16"),
         layers=ALEXNET_LAYERS, max_epochs=size.epochs)
    main()


# -- phase 0: the device -----------------------------------------------------


def require_tpu():
    """Exit non-zero, naming the platform, unless JAX runs on a TPU.
    Returns the ``device`` object of the final JSON line."""
    import jax
    import jaxlib
    platform = jax.default_backend()
    if platform != "tpu":
        sys.exit("chip_smoke: JAX default backend is %r, not 'tpu' "
                 "(JAX_PLATFORMS=%r) - no chip, no smoke"
                 % (platform, os.environ.get("JAX_PLATFORMS")))
    from importlib import metadata
    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    print("device: platform=%(platform)s device_kind=%(kind)s "
          "count=%(count)d" % device)
    print("versions: jax=%s jaxlib=%s libtpu=%s python=%s"
          % (jax.__version__, jaxlib.__version__,
             metadata.version("libtpu"), sys.version.split()[0]))
    return device


# -- phase 1: train through the CLI -----------------------------------------


def sweep_notes(since=0):
    """The flight recorder's per-sweep notes (phase, epoch, ms, last
    batch loss) written since note number ``since``."""
    from veles_tpu.telemetry import flight
    return [n for n in flight.get_recorder().notes()[since:]
            if n["kind"] == "step"]


def detector_trips():
    from veles_tpu.telemetry.registry import get_registry
    family = get_registry().get("veles_flight_detector_trips_total")
    if family is None:
        return 0
    return sum(child.value for _, child in family.series())


def cache_entries():
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    count = sum(len(files) for _, _, files in os.walk(cache_dir))
    return cache_dir, count


def train(gspmd=None, n_train=N_TRAIN, n_valid=N_VALID, epochs=EPOCHS):
    """One CLI run. Returns ``(checks, losses, cli)``: named boolean
    checks, the per-sweep losses in order, and the CLI object (its
    launcher and workflow) for mode-specific checks."""
    import jax
    import numpy

    from veles_tpu.__main__ import Main
    from veles_tpu.telemetry import flight, profiler

    mode = "gspmd" if gspmd else "fused"
    print("--- train (%s): AlexNet %dx%dx3, %d classes, batch %d, "
          "bfloat16, %d+%d samples, %d epochs"
          % (mode, SIDE, SIDE, CLASSES, BATCH, n_train, n_valid, epochs))
    notes_before = len(flight.get_recorder().notes())
    trips_before = detector_trips()
    argv = [os.path.abspath(__file__), "-a", BACKEND,
            "--precision", "bfloat16", "-s", "1234", "--no-graphics",
            "root.chip_smoke.n_train=%d" % n_train,
            "root.chip_smoke.n_valid=%d" % n_valid,
            "root.chip_smoke.epochs=%d" % epochs]
    if gspmd:
        argv += ["--gspmd", gspmd]
    cli = Main()
    code = cli.run(argv)
    launcher, workflow = cli.launcher, cli.workflow
    trainer = launcher.runner.trainer
    checks = {"cli_exit_0": code == 0,
              "run_mode_" + mode: launcher.run_mode_used == mode}

    history = workflow.decision.epoch_history
    checks["epochs_closed"] = len(history) == epochs and all(
        entry["train"]["samples"] == n_train and
        entry["validation"]["samples"] == n_valid for entry in history)

    notes = sweep_notes(notes_before)
    losses = [note["loss"] for note in notes]
    for note in notes:
        print("sweep: epoch %(epoch)s %(phase)-5s %(ms)10.1f ms  "
              "last-batch loss %(loss).4f" % note)
    checks["every_sweep_reported"] = len(notes) == 2 * epochs
    checks["losses_finite"] = bool(losses) and all(
        math.isfinite(loss) for loss in losses)
    checks["no_detector_tripped"] = detector_trips() == trips_before
    # epoch order is validation, then train: the first sweep of the
    # run measures the model nothing has trained yet
    fresh = losses[0] if losses else float("nan")
    print("untrained loss %.4f, ln(%d) = %.4f"
          % (fresh, CLASSES, math.log(CLASSES)))
    checks["fresh_loss_is_ln_classes"] = \
        abs(fresh - math.log(CLASSES)) <= FRESH_LOSS_BAND

    params, _ = trainer.pull_params()
    moved, on_tpu = [], []
    for fwd, before, layer in zip(workflow.forwards,
                                  workflow.initial_params, params):
        for name, value in layer.items():
            on_tpu.append({d.platform for d in value.devices()}
                          == {BACKEND})
            moved.append(not numpy.array_equal(
                numpy.asarray(value, numpy.float32),
                before[name].astype(numpy.float32)))
    checks["weights_moved"] = bool(moved) and all(moved)
    checks["params_on_" + BACKEND] = bool(on_tpu) and all(on_tpu)
    checks["dataset_resident"] = not trainer.streaming
    checks["dataset_s2d_staged"] = bool(trainer._staged_s2d)
    checks["peak_table_knows_device"] = \
        None not in profiler.device_spec(jax.devices()[0])

    print("dataset: %s, s2d staged: %s, donation: %s"
          % ("streamed" if trainer.streaming else "resident",
             trainer._staged_s2d, trainer.donate))
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        print("memory %s: in use %.1f MB, peak %.1f MB, limit %.1f MB"
              % (dev, stats.get("bytes_in_use", 0) / 1e6,
                 stats.get("peak_bytes_in_use", 0) / 1e6,
                 stats.get("bytes_limit", 0) / 1e6))
    phases = profiler.phase_report()
    print("phases (ms, cumulative in this process): %s"
          % json.dumps(phases))
    print("compile cache: %s, %d entries" % cache_entries())
    return checks, losses, cli


def gspmd_checks(cli, losses, reference):
    """What only the partitioned run can show."""
    import jax

    from veles_tpu.telemetry.registry import get_registry
    trainer = cli.launcher.runner.trainer
    checks = {"mesh_spans_all_devices":
              trainer.mesh.devices.size == len(jax.devices()) > 1}
    in_use = [(dev.memory_stats() or {}).get("bytes_in_use", 0)
              for dev in jax.local_devices()]
    print("per-device bytes in use: %s" % in_use)
    checks["memory_balanced_2x"] = min(in_use) > 0 and \
        max(in_use) <= 2 * min(in_use)
    gauge = get_registry().get("veles_op_collective_bytes")
    series = {} if gauge is None else {
        labels["op"]: child.value for labels, child in gauge.series()}
    print("collective bytes per step: %s" % series)
    checks["collective_bytes_positive"] = \
        series.get("gspmd_train_segment", 0) > 0
    diffs = [abs(a - b) for a, b in zip(losses, reference)]
    print("per-sweep |gspmd - one-chip| loss: %s (tolerance %g)"
          % (["%.2e" % d for d in diffs], GSPMD_LOSS_TOLERANCE))
    checks["losses_match_one_chip"] = \
        len(losses) == len(reference) > 0 and \
        max(diffs) <= GSPMD_LOSS_TOLERANCE
    return checks


# -- phase 2: the Pallas kernels, compiled -----------------------------------


def _holds_mosaic_kernel(fn, *args):
    """True when the lowered program contains a Mosaic (Pallas TPU)
    custom call — i.e. the kernel was not replaced by its XLA form."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _close(got, want, tol):
    """Largest error of ``got`` against ``want``, relative to the
    largest reference magnitude; and whether it is within ``tol``."""
    import numpy
    got = numpy.asarray(got, numpy.float64)
    want = numpy.asarray(want, numpy.float64)
    err = float(numpy.max(numpy.abs(got - want)) /
                max(float(numpy.max(numpy.abs(want))), 1e-30))
    return err, bool(numpy.isfinite(got).all()) and err <= tol


def kernel_cases():
    """``[(name, thunk)]``; a thunk returns ``(error, ok)`` and raises
    when the kernel does not compile. One kernel: the package's only
    Pallas entry point of its own, ``ops.gemm.pallas_kahan_gemm``,
    which ``kahan_matmul`` takes on a TPU (``gemm``'s precision level
    1)."""
    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu.ops.gemm import _kahan_matmul_loop, pallas_kahan_gemm

    rng = numpy.random.RandomState(0)

    def rand(shape, dtype):
        return jnp.asarray(rng.rand(*shape).astype(numpy.float32)
                           - 0.5).astype(dtype)

    def kahan(a, b):
        return pallas_kahan_gemm(a, b, interpret=INTERPRET)

    def compare(a, b, tol):
        if not _holds_mosaic_kernel(kahan, a, b):
            raise AssertionError("lowered without a Mosaic kernel: the "
                                 "XLA form was taken")
        return _close(jax.jit(kahan)(a, b),
                      jax.jit(_kahan_matmul_loop)(a, b), tol)

    cases = []
    m, k, n = 512, 1024, 1024
    for dtype in (jnp.bfloat16, jnp.float32):
        # f32 operands: XLA's default dot and the Mosaic dot may round
        # through a different number of bf16 passes; bf16 operands
        # multiply exactly and differ only in f32 summation order
        tol = 2e-2 if dtype == jnp.float32 else 1e-4
        cases.append(("pallas_kahan_gemm[%s]" % jnp.dtype(dtype).name,
                      functools.partial(compare, rand((m, k), dtype),
                                        rand((k, n), dtype), tol)))
    return cases


def kernels():
    """Compile and check every case; one failing does not hide the
    next, and each failure fails the smoke."""
    import jax
    print("--- kernels: Pallas entry points, interpret=%s" % INTERPRET)
    # These compilations are the check itself, so none may be written
    # to the persistent cache: the next start must compile each kernel
    # for real again, and must find the cache as the training path
    # left it. (JAX writes what took longer than this threshold.)
    option = "jax_persistent_cache_min_compile_time_secs"
    threshold = getattr(jax.config, option)
    jax.config.update(option, 1e9)
    checks = {}
    try:
        for name, thunk in kernel_cases():
            try:
                err, ok = thunk()
            except Exception:
                traceback.print_exc()
                print("kernel %-46s FAILED (see traceback on stderr)"
                      % name)
                checks[name] = False
                continue
            print("kernel %-46s %s  error %.2e"
                  % (name, "ok    " if ok else "WRONG ", err))
            checks[name] = ok
    finally:
        jax.config.update(option, threshold)
    return checks


# -- entry -------------------------------------------------------------------


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--gspmd", default=None, metavar="MESH",
        help="after the one-chip run, repeat it through the launcher's "
             "--gspmd MESH (e.g. 4x1 on a four-chip host)")
    args = parser.parse_args(argv)

    device = require_tpu()
    checks, losses, cli = train()
    if args.gspmd:
        reference = losses
        del cli
        gc.collect()  # the one-chip run's buffers leave device 0
        spmd, losses, cli = train(gspmd=args.gspmd)
        spmd.update(gspmd_checks(cli, losses, reference))
        checks.update(("gspmd:" + name, ok) for name, ok in spmd.items())
    checks.update(("kernel:" + name, ok)
                  for name, ok in kernels().items())

    print("compile cache at exit: %s, %d entries" % cache_entries())
    ok = all(checks.values())
    for name, passed in checks.items():
        if not passed:
            print("FAILED check: %s" % name)
    print("checks: %s" % json.dumps(checks))
    print(json.dumps({"ok": ok, "device": device}))
    sys.stdout.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
