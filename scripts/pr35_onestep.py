#!/usr/bin/env python3
"""PR 35, call 7's probe (a record; it ran against trees of its own).

    python3 scripts/pr35_onestep.py ROOT --cell <token cell>

ROOT's trainer of a token cell, weights left at zero (only shapes
matter), traces and lowers its ONE-STEP train segment through the
public ``train_class(skip=)`` on whatever device JAX offers, and stops
where the compile would start. Prints JAX's own trace and lowering
times and what Python's collector did meanwhile. The workflow is built
as ``scripts/compile_token_cell.py`` builds it. ``BALLAST=<n>`` keeps
``n`` more tracked objects alive, to see a full collection's cost
move between the trace and the lowering.
"""

import argparse
import gc
import os
import sys
import time

ROOT = sys.argv.pop(1)
sys.path.insert(0, ROOT)


class Stop(Exception):
    """Raised where the train segment's compile would start."""


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cell", required=True)
    args = parser.parse_args()

    import jax
    import jax.monitoring

    from benchmark import harness
    from benchmark.seeded_tokens import SeededTokenLoader
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.nn.base import ForwardBase
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.standard_workflow import StandardWorkflow
    from veles_tpu.train import FusedTrainer

    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.cell)
    config, traffic = bench.config(cell), bench.traffic(cell)
    layers = [dict(layer) for layer in config["layers"]]
    first, optimizer = layers[0], config["optimizer"]
    lookahead = 1 + max(d.get("shift", 0) for d in layers)
    set_policy(config["precision"])
    prng.get().seed(1)
    prng.get("loader").seed(2)
    ForwardBase.fill_matrices = lambda self, mem: None  # shapes only

    t0 = time.time()
    workflow = StandardWorkflow(
        DummyLauncher(),
        loader=lambda wf: SeededTokenLoader(
            wf, n_train=traffic["n_train"], n_valid=traffic["n_valid"],
            length=first["positions"] + lookahead,
            vocabulary=first["vocabulary"], seed=3,
            exponent=traffic["zipf_exponent"],
            minibatch_size=config["batch"]),
        layers=layers, loss=config["loss"], solver=optimizer["solver"],
        learning_rate=optimizer["learning_rate"], momentum=0.0,
        weights_decay=optimizer["weights_decay"],
        solver_hp={k: optimizer[k] for k in (
            "beta1", "beta2", "epsilon", "warmup_steps")})
    workflow.initialize(device=Device(backend=jax.default_backend()))
    trainer = FusedTrainer(workflow, stream=traffic["stream"],
                           offload=False)
    params, states = trainer.pull_params()
    print("%s: workflow and trainer in %.0f s" % (
        cell["name"], time.time() - t0), flush=True)

    spans, collections_ = [], []

    def span(event, start, end, **kwargs):
        spans.append((event.rsplit("/", 1)[1], end - start))

    def stop(event, value, fun_name=None, **_):
        if event.endswith("backend_compile_duration") \
                and "train_segment" in str(fun_name):
            raise Stop()

    def collected(phase, info):
        if phase == "start":
            collected.since = time.time()
        else:
            collections_.append((info["generation"],
                                 time.time() - collected.since))

    jax.monitoring.register_event_time_span_listener(span)
    jax.monitoring.register_scalar_listener(stop)
    gc.callbacks.append(collected)
    ballast = [(i, str(i)) for i in range(int(os.environ.get(
        "BALLAST", "0")))]
    batch = trainer.loader.max_minibatch_size
    t0 = time.time()
    try:
        trainer.train_class(
            params, states,
            skip=trainer.loader.class_lengths[TRAIN] - batch)
    except Stop:
        pass
    full = [took for generation, took in collections_ if generation == 2]
    print("one-step %.1f s; events %d; %s; full collections %d (%.1f s), "
          "all %d (%.1f s); ballast %d" % (
              time.time() - t0, len(spans),
              [(name, round(took, 2)) for name, took in spans
               if took > 0.5], len(full), sum(full), len(collections_),
              sum(took for _, took in collections_), len(ballast)),
          flush=True)


if __name__ == "__main__":
    main()
