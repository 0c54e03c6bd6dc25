"""Builder of the latent-attention, sparse-expert language-model
family: layer list -> ``StandardWorkflow`` -> ``FusedTrainer``, as a
user's workflow file and the launcher would.

What a configuration file says is all that is passed on: the layers
(embedding, blocks, the MTP side branch, the head; this chip's share
of the experts and of the vocabulary is in them), batch, precision
policy and optimizer. What a traffic file says decides the sequence
counts, the Zipf exponent and whether the data set streams. A sample
is one sequence.
"""

import time
import types

# the new units first, before any data or weight exists: a checkout
# whose program lacks them fails here, in seconds
from veles_tpu.loader.tokens import TokenLoader  # noqa: F401
from veles_tpu.nn.attention import LatentAttentionForward  # noqa: F401
from veles_tpu.nn.mlp import GatedMLPForward  # noqa: F401
from veles_tpu.nn.tokens import (TokenEmbeddingForward,  # noqa: F401
                                 TokenMergeForward, VocabularyHeadForward)

import jax  # noqa: E402
import numpy  # noqa: E402

from benchmark import flops_lm  # noqa: E402
from benchmark.seeded_tokens import SeededTokenLoader  # noqa: E402


def hbm(device):
    """The device's memory as its runtime counts it, for the log: the
    cell's ``peak_hbm_mb`` is the largest over the whole process, so
    what the builder itself puts there has to be seen."""
    stats = (device.memory_stats() if device is not None else None) or {}
    return "device holds %.0f MB, peak so far %.0f MB" % (
        stats.get("bytes_in_use", 0) / 1e6,
        stats.get("peak_bytes_in_use", 0) / 1e6)


def last_minibatch(loader):
    """``(tokens, labels)`` of the LAST minibatch of the train set's
    order as it stands: the batch ``train_class(skip=)`` serves when
    all the others are skipped."""
    from veles_tpu.loader.base import TRAIN
    end = loader.class_end_offsets[TRAIN]
    rows = numpy.asarray(loader.shuffled_indices.map_read()[
        end - loader.max_minibatch_size:end])
    return loader.original_data.mem[rows], loader.original_labels.mem[rows]


def program_step(trainer, layers, initial, log, device=None):
    """ONE TRAIN STEP of the timed program, from the initial weights
    and a fresh optimizer state, through the trainer's public
    ``train_class(params, states, skip=)`` on the LAST minibatch of
    the train set's order (the scan the window runs, one step long),
    laid out as ``reference.stepped`` lays a step out. Returns it with
    the batch's ``(tokens, labels)``.

    Afterwards the workflow is as it was: the donated buffers are
    gone, so every unit gets its initial array back and every
    optimizer state is dropped; ``pull_params`` then makes both anew.
    What stays is the cost book's harvest of ``train_segment``, taken
    from this one-step program."""
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.train.step import unit_tag

    loader, batch = trainer.loader, trainer.loader.max_minibatch_size
    served = last_minibatch(loader)
    t0 = time.perf_counter()
    params, states = trainer.pull_params()
    params, states, losses, _ = trainer.train_class(
        params, states, skip=loader.class_lengths[TRAIN] - batch)
    observed = jax.device_get(trainer.last_step_stats)
    log("build: the program's one-step segment ran: %.1f s; %s"
        % (time.perf_counter() - t0, hbm(device)))
    out = {"moments": [], "changes": [], "counts": [],
           "losses": dict({k: float(v[0])
                           for k, v in observed["losses"].items()},
                          main=float(losses[0]))}
    for i, (descr, fwd) in enumerate(zip(layers, trainer.forwards)):
        out["moments"].append(jax.device_get(
            dict(states[i].get("m", {}))))
        out["changes"].append({
            name: numpy.asarray(value) - initial[i][name]
            for name, value in jax.device_get(dict(params[i])).items()})
        if descr["type"] == "moe":
            out["counts"].append(numpy.asarray(
                observed["stats"][unit_tag(i, fwd)]["expert_counts"][0]))
    del params, states
    for values, fwd in zip(initial, trainer.forwards):
        for name, arr in fwd.param_arrays().items():
            arr.reset(values[name])
        gd = trainer.gd_for.get(id(fwd))
        if gd is not None:
            gd.opt_state = None
    trainer.last_step_stats = None
    log("build: one train step of the program (%d sequences), pulled "
        "to the host, the workflow put back: %.1f s; %s"
        % (batch, time.perf_counter() - t0, hbm(device)))
    return out, served


def build(config, traffic, seed, devices, reference, log):
    """Returns the system under test as a namespace: ``workflow``,
    ``trainer``, sizes, ``train_flops_per_sample`` and
    ``reference_losses``, what ``reference.agreement`` takes beside
    the program's untrained validation losses: ``{"losses": the plain
    reference's per-batch validation losses at the initial weights,
    "step": reference.step_comparison(...) of ONE TRAIN STEP of the
    program against reference.train_step}``. The reference runs BEFORE
    the trainer exists, on a device that holds nothing else, and drops
    everything it put there; every phase logs the device's memory, so
    that the process's peak can be seen to be the program's own."""
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
    lookahead = 1 + max(d.get("shift", 0) for d in layers)
    optimizer = config["optimizer"]
    t0 = time.perf_counter()
    workflow = StandardWorkflow(
        DummyLauncher(),
        loader=lambda wf: SeededTokenLoader(
            wf, n_train=traffic["n_train"], n_valid=traffic["n_valid"],
            length=first["positions"] + lookahead,
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
    # moments: 5.7 GB of zeros here), which the trainer would adopt.
    # Dropped, so that the reference does not stand on them and the
    # process's peak stays the program's; pull_params makes them anew
    for gd in workflow.gds:
        gd.opt_state = None
    n_params = sum(arr.mem.size for fwd in workflow.forwards
                   for arr in fwd.param_arrays().values())
    log("build: workflow, %d parameters and %d+%d sequences of %d ids "
        "on the host: %.1f s; %s" % (
            n_params, traffic["n_train"], traffic["n_valid"],
            first["positions"] + lookahead, time.perf_counter() - t0,
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
    log("build: fused trainer, streaming=%s, per-token objective=%s, "
        "side branches %s: %.1f s" % (
            trainer.streaming, trainer.per_token,
            sorted(trainer._branches), time.perf_counter() - t0))
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
        train_flops_per_sample=flops_lm.train_flops_per_sample(layers))
