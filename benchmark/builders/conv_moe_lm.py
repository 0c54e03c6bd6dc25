"""Builder of the gated short-convolution and grouped-query attention,
sparse-expert language-model family with one table for embedding and
head: layer list -> ``StandardWorkflow`` -> ``FusedTrainer``, as a
user's workflow file and the launcher would.

``builders/indexed_moe_lm.py``'s build, phase by phase and log line by
log line, without that family's selection: its helpers are imported,
not copied (``place_experts``: the experts held are one chip's bin of
the group's balanced packing by load, so that every seed does a chip's
share of the work; ``builders/moe_lm.py``'s: the device's memory for
the log, the batch ``train_class(skip=)`` serves, ONE TRAIN STEP of
the timed program laid out as the reference lays a step out), with
this family's operation count. The head owns no array: its row of
every list is empty, and the table's change and moment are the
embedding's. A sample is one sequence; a row holds ``positions + 1``
ids.
"""

import time
import types

# what the family needs of the program first, before any data or
# weight exists: a checkout that lacks the unit fails here, in seconds
from veles_tpu.nn.short_conv import ShortConvForward  # noqa: F401

import jax  # noqa: E402
import numpy  # noqa: E402

from benchmark import flops_conv_lm  # noqa: E402
from benchmark.builders.indexed_moe_lm import place_experts  # noqa: E402
from benchmark.builders.moe_lm import (  # noqa: E402
    hbm, last_minibatch, program_step)
from benchmark.seeded_tokens import SeededTokenLoader  # noqa: E402


def build(config, traffic, seed, devices, reference, log):
    """Returns the system under test as a namespace: ``workflow``,
    ``trainer``, sizes, ``train_flops_per_sample`` and
    ``reference_losses``, what ``reference.agreement`` takes beside
    the program's untrained validation losses: ``{"losses": the plain
    reference's per-batch validation losses at the initial weights,
    "step": reference.step_comparison(...) of ONE TRAIN STEP of the
    program against reference.train_step}``. The reference runs BEFORE
    the trainer exists, on a device that holds nothing else, and drops
    everything it put there."""
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.standard_workflow import StandardWorkflow
    from veles_tpu.train import FusedTrainer

    if config["trainer"] != "fused":
        raise ValueError("unknown trainer %r" % config["trainer"])
    set_policy(config["precision"])
    # weights, the shuffles, the data set: each its own stream of --seed
    prng.get().seed(seed)
    prng.get("loader").seed(seed + 1)
    layers = [dict(layer) for layer in config["layers"]]
    first, head = layers[0], layers[-1]
    optimizer = config["optimizer"]
    t0 = time.perf_counter()
    workflow = StandardWorkflow(
        DummyLauncher(),
        loader=lambda wf: SeededTokenLoader(
            wf, n_train=traffic["n_train"], n_valid=traffic["n_valid"],
            length=first["positions"] + 1,
            vocabulary=first["vocabulary"], seed=seed + 2,
            exponent=traffic["zipf_exponent"],
            minibatch_size=config["batch"]),
        layers=[dict(layer) for layer in layers],
        loss=config["loss"], solver=optimizer["solver"],
        learning_rate=optimizer["learning_rate"],
        momentum=0.0, weights_decay=optimizer["weights_decay"],
        solver_hp={k: optimizer[k] for k in (
            "beta1", "beta2", "epsilon", "warmup_steps")})
    workflow.initialize(device=Device(backend=devices[0].platform))
    # initialize makes each solver's state on the device (Adam's two
    # moments), which the trainer would adopt. Dropped, so that the
    # reference does not stand on them; pull_params makes them anew
    for gd in workflow.gds:
        gd.opt_state = None
    place_experts(workflow, layers, config["batch"], devices[0], log)
    n_params = sum(arr.mem.size for fwd in workflow.forwards
                   for arr in fwd.param_arrays().values())
    log("build: workflow, %d parameters (the table once) and %d+%d "
        "sequences of %d ids on the host: %.1f s; %s" % (
            n_params, traffic["n_train"], traffic["n_valid"],
            first["positions"] + 1, time.perf_counter() - t0,
            hbm(devices[0])))

    t0 = time.perf_counter()
    loader = workflow.loader
    n_valid, batch = loader.class_lengths[1], config["batch"]
    for descr, fwd in zip(layers, workflow.forwards):
        descr["name"] = fwd.name
    initial = [{name: numpy.array(arr.map_read())
                for name, arr in fwd.param_arrays().items()}
               for fwd in workflow.forwards]
    with jax.default_device(devices[0]):
        reference_losses = reference.validation_batch_losses(
            layers, initial, loader.original_data.mem[:n_valid],
            loader.original_labels.mem[:n_valid], batch)
        log("build: reference validation losses (%d batches, float32 "
            "highest): %.1f s; %s" % (len(reference_losses),
                                      time.perf_counter() - t0,
                                      hbm(devices[0])))
        t0 = time.perf_counter()
        # on the batch the program's one step will take
        expected = reference.train_step(
            layers, initial, *last_minibatch(loader), optimizer)
    log("build: reference train step (gradients of %d sequences by "
        "jax.grad, float32 highest; Adam on the host): %.1f s; %s"
        % (batch, time.perf_counter() - t0, hbm(devices[0])))

    t0 = time.perf_counter()
    # the state fits the chip and stays on it: no host offload
    trainer = FusedTrainer(workflow, stream=traffic["stream"],
                           offload=False)
    log("build: fused trainer, streaming=%s, per-token objective=%s: "
        "%.1f s" % (trainer.streaming, trainer.per_token,
                    time.perf_counter() - t0))
    program, _ = program_step(trainer, layers, initial, log, devices[0])
    t0 = time.perf_counter()
    step = reference.step_comparison(layers, program, expected)
    log("build: the step compared on the host: %.1f s"
        % (time.perf_counter() - t0))
    return types.SimpleNamespace(
        workflow=workflow, trainer=trainer, devices=list(devices),
        n_train=loader.class_lengths[2], n_valid=n_valid,
        classes=head["vocabulary"],
        reference_losses={"losses": reference_losses, "step": step},
        train_flops_per_sample=flops_conv_lm.train_flops_per_sample(
            layers))
