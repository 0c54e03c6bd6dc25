"""Traffic driver ``epochs``: whole training epochs through the public
``trainer.run_epoch(params, states, epoch)``, the loop that elastic
workers and scheduled gangs run, until the window's seconds have
passed, ending on an epoch boundary. No private method of the trainer
is called.

The program has no span inside ``run_epoch`` yet, so the benchmark
puts its own around the two public sweep methods that ``run_epoch``
calls: it shadows ``trainer.eval_class`` and ``trainer.train_class``
on the instance with wrappers that time the call to the point where
its results are ready (``run_epoch`` reads them on the next line
anyway) and write a ``jax.profiler.TraceAnnotation``. Whatever of an
epoch lies outside the two sweeps — the shuffle, the index matrix,
the summaries — is the epoch boundary.

End-to-end metrics of this driver (host clock, untraced window):

* ``train_samples_per_s``: train samples of the whole epochs closed
  in the window over the window's wall time, first ``run_epoch`` call
  to the return of the last. Global, not per chip.
* ``eval_samples_per_s``: validation samples over the median time of
  the window's validation sweeps.
* ``peak_hbm_mb``: largest ``peak_bytes_in_use`` over the cell's
  devices after the window, in 10^6 bytes.
"""

import math
import statistics
import time

import jax
import numpy

TRAIN_SPAN = "bench:train_sweep"
EVAL_SPAN = "bench:eval_sweep"
EPOCH_SPAN = "bench:epoch"
#: |untrained loss - ln(classes)| bound: a fresh softmax head is near
#: uniform over the classes
FRESH_LOSS_BAND = 0.1


class Sweeps(object):
    """Timing wrappers around a trainer's two public sweep methods."""

    def __init__(self, trainer):
        self.spans = []  # (name, start, end) on time.perf_counter
        self.eval_losses = []  # per validation sweep: per-batch losses
        self.train_losses = []  # per train sweep: per-step losses
        self._eval, self._train = trainer.eval_class, trainer.train_class
        trainer.eval_class, trainer.train_class = self.eval, self.train

    def eval(self, params, klass, skip=0):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(EVAL_SPAN):
            out = self._eval(params, klass, skip=skip)
            jax.block_until_ready(out[0])
        self.spans.append((EVAL_SPAN, t0, time.perf_counter()))
        self.eval_losses.append(out[0])
        return out

    def train(self, params, states, skip=0):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(TRAIN_SPAN):
            out = self._train(params, states, skip=skip)
            jax.block_until_ready(out[2])
        self.spans.append((TRAIN_SPAN, t0, time.perf_counter()))
        self.train_losses.append(out[2])
        return out

    def take(self):
        """Spans and losses since the last call, losses on the host."""
        taken = (self.spans,
                 [numpy.asarray(v, numpy.float64)
                  for v in self.eval_losses],
                 [numpy.asarray(v, numpy.float64)
                  for v in self.train_losses])
        self.spans, self.eval_losses, self.train_losses = [], [], []
        return taken


def _epochs(system, sweeps, state, epoch, until):
    """Run whole epochs until ``until(n_done)`` says stop. Returns the
    phase's record; ``state`` is ``[params, states]``, updated."""
    trainer = system.trainer
    wait0 = trainer.input_wait_s
    n = 0
    start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(EPOCH_SPAN, epoch=epoch + n):
            state[0], state[1], _ = trainer.run_epoch(
                state[0], state[1], epoch + n)
        n += 1
        if until(n, time.perf_counter() - start):
            break
    end = time.perf_counter()
    spans, eval_losses, train_losses = sweeps.take()
    sweep_s = sum(e - s for _, s, e in spans)
    return {"epochs": n, "wall_s": end - start, "sweep_s": sweep_s,
            "input_wait_s": trainer.input_wait_s - wait0,
            "spans": spans, "eval_losses": eval_losses,
            "train_losses": train_losses}


def run(system, traffic, seconds, trace_dir, compiles, reference, log):
    """Warm up, measure for ``seconds``, then trace if asked. Returns
    ``{"window_start", "end_to_end", "attempted", "failed", "checks",
    "counters", "traced"}``; ``window_start`` is on ``time.time``."""
    trainer = system.trainer
    sweeps = Sweeps(trainer)
    state = list(trainer.pull_params())

    # -- set-up: every program the window uses runs here first. Two
    # whole epochs: the first call of a segment compiles, the cost
    # harvest compiles it again, and the second call compiles a third
    # time for operands that now arrive committed (PR 21)
    warm = _epochs(system, sweeps, state, 0,
                   lambda n, _: n >= traffic["warm_epochs"])
    log("warm-up: %d epochs in %.1f s (sweeps %s)" % (
        warm["epochs"], warm["wall_s"],
        " ".join("%.2f" % (e - s) for _, s, e in warm["spans"])))
    untrained = warm["eval_losses"][0]
    agrees, report = reference.agreement(untrained,
                                         system.reference_losses)
    log("agreement with the float32 reference on the untrained "
        "validation sweep: %s" % report)
    checks = {
        "residency_as_traffic": bool(trainer.streaming)
        == bool(traffic["stream"]),
        "untrained_loss_near_ln_classes": abs(
            float(untrained.mean()) - math.log(system.classes))
        <= FRESH_LOSS_BAND,
        "agrees_with_reference": agrees,
    }

    # -- the window
    compiled_before = compiles()
    window_start = time.time()
    window = _epochs(system, sweeps, state, warm["epochs"],
                     lambda _, elapsed: elapsed >= seconds)
    compiled_in_window = compiles() - compiled_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in system.devices)
    steps = sum(len(v) for v in window["train_losses"])
    losses = numpy.concatenate(window["train_losses"])
    last_loss = float(window["train_losses"][-1].mean())
    checks.update({
        "no_compilation_in_window": compiled_in_window == 0,
        "loss_finite_and_below_untrained": bool(
            numpy.isfinite(last_loss)
            and last_loss < float(untrained.mean())),
    })
    eval_sweeps = [e - s for name, s, e in window["spans"]
                   if name == EVAL_SPAN]
    train_rate = window["epochs"] * system.n_train / window["wall_s"]
    end_to_end = {
        "train_samples_per_s": train_rate,
        "eval_samples_per_s": system.n_valid
        / statistics.median(eval_sweeps),
        "peak_hbm_mb": peak / 1e6,
    }
    log("window: %d epochs, %d train samples and %d validation sweeps "
        "of %d in %.3f s; compilations in the window: %d"
        % (window["epochs"], window["epochs"] * system.n_train,
           len(eval_sweeps), system.n_valid, window["wall_s"],
           compiled_in_window))
    log("loss: untrained validation %.4f, train sweeps %s" % (
        float(untrained.mean()),
        " ".join("%.4f" % v.mean() for v in window["train_losses"])))
    counters = {
        "window_s": window["wall_s"],
        "gap_s": window["wall_s"] - window["sweep_s"],
        "input_wait_s": window["input_wait_s"],
        "train_flops_per_s": system.train_flops_per_sample * train_rate,
    }

    # -- the traced epochs, after the window: tracing slows the host,
    # so nothing above is taken with the profiler on
    traced = None
    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            phase = _epochs(
                system, sweeps, state, warm["epochs"] + window["epochs"],
                lambda n, _: n >= traffic["trace_epochs"])
        finally:
            jax.profiler.stop_trace()
        traced = {
            "epochs": phase["epochs"],
            "train_steps": sum(len(v) for v in phase["train_losses"]),
            "eval_steps": sum(len(v) for v in phase["eval_losses"]),
            "compiled": compiles() - compiled_before - compiled_in_window,
        }
        log("traced: %(epochs)d epochs, %(train_steps)d train and "
            "%(eval_steps)d validation steps" % traced)
    trainer.shutdown()
    return {
        "window_start": window_start, "end_to_end": end_to_end,
        "attempted": steps,
        "failed": int(numpy.count_nonzero(~numpy.isfinite(losses))),
        "checks": checks, "counters": counters, "traced": traced}
