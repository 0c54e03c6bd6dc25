"""Builder of the grouped-query attention under a learned selection of
keys, sparse-expert language-model family: layer list ->
``StandardWorkflow`` -> ``FusedTrainer``, as a user's workflow file and
the launcher would.

``builders/window_moe_lm.py``'s build, phase by phase and log line by
log line (``builders/moe_lm.py``'s helpers are imported: the device's
memory for the log, the batch ``train_class(skip=)`` serves, ONE TRAIN
STEP of the timed program laid out as the reference lays a step out),
with this family's operation count and what its comparison needs
beside: from the step's own stats every layer's ``L_I``, the keys
every query selected and the sum of their positions, and the FIRST
attention layer's whole selection, made after the step from the
initial weights by the unit's own index and search
(``GroupedAttentionForward.selection``, under the configuration's
precision policy: the timed program never holds a mask whole, and its
counts and sums are what ties that mask to it). A sample is one
sequence; a row holds ``positions + 1`` ids.

Before any of that the builder PLACES THE EXPERTS (``place_experts``):
which of a sparse layer's experts this chip holds is the group's
choice, and a group places them so that its chips get the same rows.
Left as the fills deal them, the 16 held got 5,297 to 12,211 rows a
step of the 8,192 expected, by the seed, and a seed's step time
followed them.
"""

import json
import time
import types

# what the family needs of the program first, before any data or
# weight exists: a checkout that lacks the selected core fails here,
# in seconds
from veles_tpu.parallel.sequence import selected_attention  # noqa: F401

import jax  # noqa: E402
import numpy  # noqa: E402

from benchmark import flops_indexed_lm  # noqa: E402
from benchmark.builders.moe_lm import (  # noqa: E402
    hbm, last_minibatch, program_step)
from benchmark.seeded_tokens import SeededTokenLoader  # noqa: E402

ATTENTION = "grouped_attention"


def balanced_packing(loads, bins):
    """``bins`` lists of ``len(loads) // bins`` expert ids each: the
    heaviest expert first, each to the lightest bin that has room (the
    lower bin on a tie): the packing an expert-parallel group places
    its experts by (DeepSeek's EPLB calls it so)."""
    size = len(loads) // bins
    packs, totals = [[] for _ in range(bins)], [0.0] * bins
    for expert in numpy.argsort(-numpy.asarray(loads), kind="stable"):
        into = min((b for b in range(bins) if len(packs[b]) < size),
                   key=lambda b: (totals[b], b))
        packs[into].append(int(expert))
        totals[into] += float(loads[expert])
    return packs


def place_experts(workflow, layers, batch, device, log):
    """Relabels every dropless sparse layer's experts so that the
    ones held here (``experts_held``) are ONE CHIP'S BIN of the
    group's balanced packing of all the experts' loads: the columns
    of the router (and the selection bias) are permuted, bin 0 into
    the held places, the other bins behind them in their order. Every
    column is still the fill the seed dealt it; which expert a chip
    holds is the deployment's to choose.

    The loads are the rows the whole train set routes to each expert
    at the initial weights (the rate in a window is about 0), counted
    by the units' own forward under the policy, a layer at a time:
    layer ``l`` is placed, then run as placed, before ``l + 1`` is
    counted, since what a chip holds is what its tokens get back. The
    forward of one description is compiled once."""
    loader = workflow.loader
    n_valid = loader.class_lengths[1]
    rows = numpy.asarray(loader.original_data.mem[n_valid:])
    sparse = [d["type"] == "moe" and fwd.dropless
              for d, fwd in zip(layers, workflow.forwards)]
    if not any(sparse):
        return
    last = max(i for i, placed in enumerate(sparse) if placed)
    t0 = time.perf_counter()
    compiled, moved = {}, []

    def forward(descr, fwd, counted):
        key = json.dumps({k: v for k, v in descr.items() if k != "name"},
                         sort_keys=True)
        if key not in compiled:
            compiled[key] = jax.jit(
                (lambda p, x: fwd.apply_step(p, x, None)) if counted
                else fwd.apply)
        return compiled[key]

    with jax.default_device(device):
        xs = [jax.device_put(rows[at:at + batch], device)
              for at in range(0, len(rows) - batch + 1, batch)]
        for descr, fwd, counted in list(zip(
                layers, workflow.forwards, sparse))[:last + 1]:
            run = forward(descr, fwd, counted)
            arrays = fwd.param_arrays()
            params = {name: jax.device_put(arr.map_read(), device)
                      for name, arr in arrays.items()}
            if not counted:
                xs = [run(params, x) for x in xs]
                continue
            first, count = fwd.experts_held
            loads = numpy.sum([numpy.asarray(
                run(params, x)[1]["expert_counts"]) for x in xs],
                axis=0, dtype=numpy.float64) / len(xs)
            packs = balanced_packing(loads, fwd.n_experts // count)
            behind = [e for pack in packs[1:] for e in pack]
            order = numpy.array(
                behind[:first] + packs[0] + behind[first:])
            for name in ("weights", "select_bias"):
                placed = numpy.ascontiguousarray(numpy.take(
                    arrays[name].map_read(), order, axis=-1))
                arrays[name].reset(placed)
                params[name] = jax.device_put(placed, device)
            xs = [run(params, x)[0] for x in xs]
            moved.append("%d -> %d of %d" % (
                loads[first:first + count].sum(), loads[packs[0]].sum(),
                loads.sum()))
        del xs, params
    log("build: experts placed, one chip's bin of the group's balanced "
        "packing in the held places; rows a step to the experts held, "
        "by sparse layer, as filled -> as placed: %s: %.1f s; %s"
        % (", ".join(moved), time.perf_counter() - t0, hbm(device)))


def indexed_program_step(trainer, layers, initial, log, device):
    """``builders/moe_lm.py`` ``program_step``, and from the same
    step's stats ``losses["index<i>"]``, ``selected`` and
    ``selected_places`` (lists, an attention layer each); then
    ``selection``, the first attention
    layer's mask on the batch the step took."""
    from veles_tpu.train.step import unit_tag

    observed = {}
    inner = trainer.train_class

    def train_class(*args, **kwargs):
        out = inner(*args, **kwargs)
        observed.update(jax.device_get(trainer.last_step_stats))
        return out

    trainer.train_class = train_class
    try:
        program, served = program_step(trainer, layers, initial, log,
                                       device)
    finally:
        del trainer.train_class
    program["selected"], program["selected_places"] = [], []
    first = None
    for i, (descr, fwd) in enumerate(zip(layers, trainer.forwards)):
        if descr["type"] != ATTENTION:
            continue
        first = i if first is None else first
        stats = observed["stats"][unit_tag(i, fwd)]
        program["losses"]["index%d" % len(program["selected"])] = float(
            stats["index_loss"][0])
        program["selected"].append(numpy.asarray(stats["selected"][0]))
        program["selected_places"].append(
            numpy.asarray(stats["selected_places"][0]))
    t0 = time.perf_counter()
    embedding, unit = trainer.forwards[0], trainer.forwards[first]
    tokens = numpy.asarray(served[0])
    with jax.default_device(device):
        program["selection"] = numpy.asarray(jax.jit(
            lambda table, p, ids: unit.selection(
                p, embedding.apply(table, ids)))(
                    initial[0], initial[first], tokens))
    log("build: the first attention layer's selection by the unit's "
        "own index and search (%d pairs): %.1f s; %s" % (
            int(program["selection"].sum()), time.perf_counter() - t0,
            hbm(device)))
    return program


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
    log("build: workflow, %d parameters and %d+%d sequences of %d ids "
        "on the host: %.1f s; %s" % (
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
    program = indexed_program_step(trainer, layers, initial, log,
                                   devices[0])
    t0 = time.perf_counter()
    step = reference.step_comparison(layers, program, expected)
    log("build: the step compared on the host: %.1f s"
        % (time.perf_counter() - t0))
    return types.SimpleNamespace(
        workflow=workflow, trainer=trainer, devices=list(devices),
        n_train=loader.class_lengths[2], n_valid=n_valid,
        classes=head["vocabulary"],
        reference_losses={"losses": reference_losses, "step": step},
        train_flops_per_sample=flops_indexed_lm.train_flops_per_sample(
            layers))
