"""Builder of the convnet family: layer list -> ``StandardWorkflow``
-> trainer, as a user's workflow file and the launcher would.

What a configuration file says is all that is passed on: layers,
input, classes, batch, precision policy, optimizer, normalization,
trainer (``fused`` or ``gspmd`` with its mesh). What a traffic file
says decides the sample counts and whether the data set streams
(``FusedTrainer(stream=...)``, the constructor's own argument).
"""

import time
import types

import jax
import numpy

from benchmark import flops
from benchmark.seeded_loader import SeededImageLoader


def build(config, traffic, seed, devices, reference, log):
    """Returns the system under test as a namespace: ``workflow``,
    ``trainer``, sizes, ``train_flops_per_sample`` and, from the plain
    reference, ``reference_losses``: the validation sweep's per-batch
    losses at the initial weights. The reference runs BEFORE the
    trainer exists and drops everything it put on the device, so the
    program's peak memory is its own."""
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.standard_workflow import StandardWorkflow

    set_policy(config["precision"])
    # weights and dropout masks, the shuffles, the data set: each its
    # own stream of --seed
    prng.get().seed(seed)
    prng.get("loader").seed(seed + 1)
    side, _, channels = shape = flops.input_shape(config)
    optimizer = config["optimizer"]
    t0 = time.perf_counter()
    workflow = StandardWorkflow(
        DummyLauncher(),
        loader=lambda wf: SeededImageLoader(
            wf, n_train=traffic["n_train"], n_valid=traffic["n_valid"],
            side=side, channels=channels, n_classes=config["classes"],
            seed=seed + 2, dtype=config["dataset"]["dtype"],
            minibatch_size=config["batch"],
            normalization_type=config["normalization"]),
        layers=[dict(layer) for layer in config["layers"]],
        loss=config["loss"], solver=optimizer["solver"],
        learning_rate=optimizer["learning_rate"],
        momentum=optimizer["momentum"],
        weights_decay=optimizer["weights_decay"])
    workflow.initialize(device=Device(backend=devices[0].platform))
    log("build: workflow and %d+%d samples on the host: %.1f s"
        % (traffic["n_train"], traffic["n_valid"],
           time.perf_counter() - t0))

    t0 = time.perf_counter()
    loader = workflow.loader
    n_valid = loader.class_lengths[1]
    initial = [{name: numpy.array(arr.map_read())
                for name, arr in fwd.param_arrays().items()}
               for fwd in workflow.forwards]
    with jax.default_device(devices[0]):
        reference_losses = reference.validation_batch_losses(
            config["layers"], initial,
            loader.original_data.mem[:n_valid],
            loader.original_labels.mem[:n_valid], config["batch"])
    log("build: reference validation losses (%d batches, float32 "
        "highest): %.1f s" % (len(reference_losses),
                              time.perf_counter() - t0))

    t0 = time.perf_counter()
    if config["trainer"] == "gspmd":
        from veles_tpu.parallel.gspmd import GSPMDTrainer, parse_mesh_spec
        trainer = GSPMDTrainer(
            workflow, mesh=parse_mesh_spec(config["mesh"],
                                           devices=devices),
            stream=traffic["stream"])
    elif config["trainer"] == "fused":
        from veles_tpu.train import FusedTrainer
        trainer = FusedTrainer(workflow, stream=traffic["stream"])
    else:
        raise ValueError("unknown trainer %r" % config["trainer"])
    log("build: %s trainer, streaming=%s, s2d staged=%s: %.1f s"
        % (config["trainer"], trainer.streaming, trainer._staged_s2d,
           time.perf_counter() - t0))
    return types.SimpleNamespace(
        workflow=workflow, trainer=trainer, devices=list(devices),
        n_train=loader.class_lengths[2], n_valid=n_valid,
        classes=config["classes"],
        reference_losses=reference_losses,
        train_flops_per_sample=flops.train_flops_per_sample(
            config["layers"], shape))
