"""The checks every token-model family runs, defined once: each family
through the normal path (layer descriptors -> ``TokenLMWorkflow`` ->
``FusedTrainer``) against its plain float32 reference
(``benchmark/reference/``) at its tiny preset, the CLI on that preset,
the chip's share of a sparse layer, the published layers and the
benchmark's configuration, and the programs the family lowers to.

A family's test file holds its record (:class:`Family`) as ``FAMILY``,
imports the fixtures below and runs the checks as ``class
Test<Family>(TokenModelChecks): family = FAMILY``; its own mechanism's
tests stay beside that class. Where families differ, the record says
how.
"""

import contextlib
import dataclasses
import functools
import hashlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference.moe_lm import bias_change
from veles_tpu import prng, remat
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.nn import precision
from veles_tpu.nn.attention import (GroupedAttentionForward,
                                    LatentAttentionForward)
from veles_tpu.nn.mlp import GatedMLPForward
from veles_tpu.nn.moe import MoEForward
from veles_tpu.nn.short_conv import ShortConvForward
from veles_tpu.parallel import sequence
from veles_tpu.standard_workflow import LAYER_TYPES
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer
from veles_tpu.train.step import StepContext, unit_tag

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the stat a unit hands its own term of the objective by
OBJECTIVE = GroupedAttentionForward.OBJECTIVE_STAT

#: Adam as every family's workflow sets it
ADAM = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8)

#: what a snapshot must bring back of a unit beside its arrays
DESCRIPTOR_KEYS = {
    GroupedAttentionForward: ("window", "yarn", "heads", "kv_heads",
                              "gated", "rotary_fraction", "index",
                              "qk_norm", "PARAMS"),
    ShortConvForward: ("n_taps", "PARAMS", "eps")}


@dataclasses.dataclass(frozen=True)
class Family(object):
    """What the shared checks need to know of one family."""

    #: its ``TokenLMWorkflow``: ``TINY``, ``layers`` and the module
    #: the CLI loads
    workflow: type
    #: ``PUBLISHED`` of the family's module
    published: dict
    #: ``benchmark/reference/<family>.py``
    reference: object
    #: ``{layer type: reference(descr, params, x)}`` of the units that
    #: run alone
    units: dict
    #: ``moe(descr, params, x, ctx, routed)``: the reference's sparse
    #: layer, its routed experts alone where ``routed``
    moe: object
    #: ``(n_experts, top_k, experts a share)`` of the shares' check
    shares: tuple
    #: ``kept(fwd, batch, sizes, core)``: the bytes a rematerialized
    #: sequence mixer keeps (``sizes`` over ``TINY``)
    kept: object
    #: the benchmark's configuration file (``benchmark/configs/``), the
    #: sizes over ``published`` it says it holds (``cut(config)``) and
    #: the parameters it holds
    config: str
    cut: object
    parameters: int
    #: the family's own facts of its published chain
    #: (``published_facts(chain, tiny_chain)``) and of its configuration
    #: (``configuration_facts(config, chain, parameters a unit)``)
    published_facts: object
    configuration_facts: object
    #: sha256 of the tiny preset's train and validation segments as
    #: StableHLO, every block's unit rematerialized (see
    #: ``test_lowers_to_the_pinned_programs``)
    programs: tuple
    #: the sizes over ``TINY`` the checks build
    sizes: dict = dataclasses.field(default_factory=dict)
    #: the configuration's keys that are ``published``'s
    #: (``{its key: ours}``)
    config_keys: dict = dataclasses.field(default_factory=dict)
    #: the sizes at which the mixers take the fused kernels, where the
    #: rematerialized mixers are checked on them too
    fused_sizes: dict = None
    #: ``logits(descr, params, tokens)`` where the reference names no
    #: ``logits`` of its own
    logits: object = None
    #: whether rematerialized sequence mixers give the chain's
    #: gradients to the bit; where not (a router reads the stream before
    #: attention, whose cotangents then add up in another order) the
    #: rematerialized chain is held to the reference instead
    remat_exact: bool = True
    #: whether the units run one by one (``Unit.run``): a router placed
    #: before attention runs in a fused step only
    eager: bool = True
    #: the routers' score functions the shares' check runs under
    #: (``None``: the preset's)
    scorings: tuple = (None,)
    #: whether the configuration counts the selection bias among the
    #: parameters
    counts_selection_bias: bool = True
    #: how close two steps' selection bias comes to the rule's where
    #: the bias moves
    bias_atol: float = 0.0
    #: how close two steps' terms beside the main loss come to the
    #: reference's
    term_rtol: float = 2e-5

    @property
    def tiny(self):
        return dict(self.workflow.TINY, **self.sizes)

    @property
    def name(self):
        return self.workflow.__module__.rsplit(".", 1)[-1]


# -- helpers ------------------------------------------------------------------

@pytest.fixture(autouse=True)
def float32_highest():
    """The comparisons are float32 against float32: the policy pinned,
    every product at full precision on both sides."""
    precision.set_policy("float32")
    with jax.default_matmul_precision("highest"):
        yield
    precision.set_policy(None)


def build(family, sizes=None, n_train=8, n_valid=4, batch=4, seed=3,
          **kwargs):
    prng.get().seed(seed)
    prng.get("loader").seed(seed + 1)
    sizes = dict(family.sizes, **(sizes or {}))
    wf = family.workflow(DummyLauncher(), sizes=sizes, n_train=n_train,
                         n_valid=n_valid, minibatch_size=batch, seed=seed,
                         **kwargs)
    wf.initialize(device=Device(backend="cpu"))
    descr = family.workflow.layers(**dict(family.workflow.TINY, **sizes))
    for d, fwd in zip(descr, wf.forwards):
        d["name"] = fwd.name
    return wf, descr


def host_params(wf):
    return [{k: numpy.array(a.map_read())
             for k, a in fwd.param_arrays().items()}
            for fwd in wf.forwards]


@pytest.fixture(scope="module")
def model(request):
    """The family's tiny model, its trainer and its parameters on the
    host: ``FAMILY`` of the test file."""
    precision.set_policy("float32")
    wf, descr = build(request.module.FAMILY)
    return wf, descr, FusedTrainer(wf), host_params(wf)


def random_state(seed=0, batch=2, positions=16, dim=32):
    """A stream of the tiny presets' 16 positions of 32."""
    return jnp.asarray(numpy.random.default_rng(seed).normal(
        size=(batch, positions, dim)), jnp.float32)


def gauge(name, label="unit"):
    """The registry's readings of ``name`` by ``label``."""
    return {labels[label]: child.value
            for labels, child in get_registry().get(name).series()}


def batch_of(wf, trainer, klass, row=0):
    idx = trainer._segment_indices(klass)[row]
    return (wf.loader.original_data.mem[idx],
            wf.loader.original_labels.mem[idx])


def traced_as(monkeypatch, core):
    """The context in which ``causal_attention`` takes ``core``:
    nothing for XLA's blocks; for the fused kernels the chooser sees a
    TPU and the kernels run in ``pallas_call``'s plain interpreter
    (``True``: the TPU simulator's callbacks are effects, which
    ``jax.checkpoint`` refuses to partial-evaluate)."""
    if core != "fused":
        return contextlib.nullcontext()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return pltpu.force_tpu_interpret_mode(True)


def keeping(fn):
    """``fn`` under :func:`veles_tpu.remat.checkpoint`, its result
    alone."""
    return lambda *args: remat.checkpoint(fn)(*args)[0]


def objective(trainer, tokens, labels):
    """``params -> (total, (report, extras))`` of the trainer's
    per-token objective on one batch, as a train step takes it."""
    valid = jnp.ones(len(tokens), bool)

    def fn(p):
        total, (report, _, extras) = trainer._token_objective(
            p, jnp.asarray(tokens), jnp.asarray(labels), None, valid,
            True)
        return total, (report, extras)
    return fn


def reference_objective(ref, descr, host, tokens, labels):
    """``(total, terms, counts)`` of the reference: the objective, its
    terms and every sparse layer's tokens to each expert."""
    def fn(p):
        counts = {}
        total, terms = ref.objective(descr, p, tokens, labels, counts)
        return total, terms, counts
    return jax.jit(fn)(host)


def program_terms(extras):
    """The terms of the objective beside the main loss as a step
    reports them, by the reference's names: each side branch's loss,
    and the ``index<i>`` term of the ``i``-th unit that hands one."""
    own = [extras["stats"][tag][OBJECTIVE] for tag in sorted(extras["stats"])
           if OBJECTIVE in extras["stats"][tag]]
    return dict(extras["losses"], **{
        "index%d" % i: term for i, term in enumerate(own)})


def sparse_tags(wf):
    return [unit_tag(i, fwd) for i, fwd in enumerate(wf.forwards)
            if isinstance(fwd, MoEForward)]


def is_mixer(fwd):
    """A rematerialized unit that mixes the sequence, not a
    feed-forward half."""
    return fwd.remat and not isinstance(fwd, (MoEForward, GatedMLPForward))


def parameters(chain, shape, selection_bias):
    """Each unit's trained parameters by its own shapes, nothing
    allocated, the selection bias where ``selection_bias`` says so."""
    counts, dim = [], shape[-1]
    for descr in chain:
        if descr["type"] == "moe":
            held = descr["experts_held"][1] + descr["shared_experts"]
            counts.append(dim * (descr["n_experts"] + 1)
                          + held * 3 * dim * descr["hidden"]
                          + selection_bias * descr["n_experts"])
            continue
        if descr["type"] in ("token_embedding", "vocabulary_head"):
            counts.append(0 if descr.get("tied_to")
                          else descr["vocabulary"] * dim)
            continue
        unit = LAYER_TYPES[descr["type"]](DummyLauncher(), **{
            k: v for k, v in descr.items()
            if k not in ("type", "remat", "branch")})
        counts.append(sum(math.prod(s) for s, _ in
                          unit.param_shapes(shape).values())
                      if getattr(unit, "PARAMS", ()) else
                      math.prod(unit.weights_shape_for(shape)))
    return counts


def every_mechanism(published, tiny):
    """The tiny chain has every mechanism of the published one: the
    same unit types; a key that some units of a type set and others
    leave unset (a window, YaRN, a rotary table) so in both; fewer
    key/value than query heads; a window or a selection of keys
    shorter than the sequence and no multiple of the block; top-k > 1
    of more experts than are held."""
    assert {d["type"] for d in tiny} == {d["type"] for d in published}
    for ltype in {d["type"] for d in published}:
        ours = [d for d in tiny if d["type"] == ltype]
        theirs = [d for d in published if d["type"] == ltype]
        for key in set().union(*theirs):
            unset = {d.get(key) is None for d in theirs}
            if len(unset) == 2:
                assert {d.get(key) is None for d in ours} == unset, (
                    ltype, key)
    positions = tiny[0]["positions"]
    for d in tiny:
        if "kv_heads" in d:
            assert d["kv_heads"] < d["heads"], d
        for span in (d.get("window"), (d.get("index") or {}).get("top_k")):
            if span is not None:
                assert span < positions and span % d["block"], d
        if d["type"] == "moe":
            assert d["top_k"] > 1 and d["experts_held"][1] < d["n_experts"]


# -- the checks ---------------------------------------------------------------

class TokenModelChecks(object):
    """The checks of :attr:`family`, one family a test file."""

    family = None

    def pytest_generate_tests(self, metafunc):
        """A check's cases as the family's record gives them: the
        chain against the reference with and without ``remat``, the
        cores a rematerialized mixer is checked on, the score functions
        of the shares' check."""
        family = self.family
        cases = {"remat": (False,) if family.remat_exact else (False, True),
                 "core": ("updates",) + (
                     ("blockwise",) if family.remat_exact else ()) + (
                     ("fused",) if family.fused_sizes else ()),
                 "scoring": family.scorings}
        for name, values in cases.items():
            if name in metafunc.fixturenames:
                metafunc.parametrize(name, values)

    # -- the whole model against the reference --------------------------------

    def test_validation_losses_match_reference(self, model):
        wf, descr, trainer, host = model
        params, _ = trainer.pull_params()
        losses, metrics, conf = trainer.eval_class(params, VALIDATION)
        n = wf.loader.class_lengths[VALIDATION]
        expected = self.family.reference.validation_batch_losses(
            descr, host, wf.loader.original_data.mem[:n],
            wf.loader.original_labels.mem[:n], 4)
        numpy.testing.assert_allclose(losses, expected, rtol=1e-5)
        assert conf is None and numpy.asarray(metrics).sum() > 0
        # a fresh head is near uniform over the vocabulary held
        assert abs(float(jnp.mean(losses))
                   - numpy.log(self.family.tiny["vocabulary"])) < 0.1

    def test_logits_fused_equals_eager_equals_reference(self, model):
        """One batch through the fused chain gives the head what the
        reference gives it; through ``Unit.run`` of every forward unit
        too where the family's units run alone (a side branch beside
        the main path, a merge reading its linked tokens and table, a
        head reading the embedding's)."""
        wf, descr, trainer, host = model
        tokens, _ = batch_of(wf, trainer, VALIDATION)
        expected = jax.jit(functools.partial(
            self.family.logits or self.family.reference.logits, descr))(
                host, jnp.asarray(tokens))
        head = wf.forwards[-1]

        @jax.jit
        def fused(params, tokens):
            ctx = StepContext(wf.forwards, params, tokens, False)
            state = trainer._forward_range(params[:-1], tokens, None, False,
                                           0, len(params) - 1, ctx=ctx)
            return head.apply_for_grad(head.step_params(params[-1], ctx),
                                       state)
        params, _ = trainer.pull_params()
        numpy.testing.assert_allclose(fused(params, jnp.asarray(tokens)),
                                      expected, rtol=2e-4, atol=2e-5)
        if not self.family.eager:
            return
        wf.loader.minibatch_data.map_invalidate()[...] = tokens
        for fwd in wf.forwards:
            fwd.run()
        numpy.testing.assert_allclose(
            numpy.asarray(head.output.map_read()),
            jax.nn.softmax(expected, -1), rtol=2e-4, atol=1e-7)

    def test_objective_and_every_gradient_match_reference(self, model,
                                                          remat):
        """The objective and each of its terms (a side branch's loss, an
        index's own term), the gradient to every parameter, and what
        every sparse layer routed and every index selected; with every
        unit rematerialized where :attr:`Family.remat_exact` does not
        hold, and then the sparse units keep nothing of their own
        across their rematerialization."""
        if remat:
            wf, descr = build(self.family, sizes={"remat": True})
            trainer, host = FusedTrainer(wf), host_params(wf)
        else:
            wf, descr, trainer, host = model
        tokens, labels = batch_of(wf, trainer, TRAIN)
        params, _ = trainer.pull_params()
        (total, (report, extras)), grads = jax.jit(jax.value_and_grad(
            objective(trainer, tokens, labels), has_aux=True))(params)
        ref = self.family.reference
        r_total, terms, counts = reference_objective(
            ref, descr, host, tokens, labels)
        numpy.testing.assert_allclose(total, r_total, rtol=1e-5)
        numpy.testing.assert_allclose(report, terms["main"], rtol=1e-5)
        beside = program_terms(extras)
        assert set(terms) == {"main"} | set(beside)
        for name, term in beside.items():
            numpy.testing.assert_allclose(term, terms[name], rtol=1e-5,
                                          err_msg=name)
            assert float(term) > 0, name
        r_grads = ref.gradients(descr, host, tokens, labels)
        checked = 0
        for fwd, g, r in zip(wf.forwards, grads, r_grads):
            assert set(g) == set(r)
            for name in g:
                if name in fwd.non_gradient:
                    assert not numpy.asarray(g[name]).any()
                    continue
                scale = float(numpy.abs(r[name]).max())
                assert scale > 0, (fwd.name, name)
                numpy.testing.assert_allclose(
                    g[name], r[name], rtol=2e-3, atol=2e-4 * scale,
                    err_msg="%s.%s" % (fwd.name, name))
                checked += 1
        # a tied table, an embedding read by a merge: once each
        assert checked == sum(len(fwd.gradient_params(p))
                              for fwd, p in zip(wf.forwards, params))
        for k, tag in enumerate(sparse_tags(wf)):
            numpy.testing.assert_array_equal(
                extras["stats"][tag]["expert_counts"], counts[k])
        if remat:
            kept = gauge("veles_remat_kept_bytes")
            assert all(kept[fwd.name] == 0 for fwd in wf.forwards
                       if isinstance(fwd, MoEForward))
        positions = self.family.tiny["positions"]
        for i, fwd in enumerate(wf.forwards):
            if getattr(fwd, "index", None):
                numpy.testing.assert_array_equal(
                    extras["stats"][unit_tag(i, fwd)]["selected"],
                    numpy.broadcast_to(numpy.minimum(
                        numpy.arange(positions) + 1, fwd.index["top_k"]),
                        (len(tokens), positions)))

    def test_two_adam_steps(self):
        """Two steps of the train segment against Adam written out here
        on the reference's gradients, every terms of the objective, a
        selection bias by the rule on the reference's own counts; no
        token is dropped; what the trainer publishes after the sweep."""
        family, hp = self.family, ADAM
        wf, descr = build(family)
        trainer = FusedTrainer(wf)
        host = host_params(wf)
        params, states = trainer.pull_params()
        idx = trainer._segment_indices(TRAIN)
        new_params, new_states, losses, _ = trainer.train_class(params,
                                                                states)
        observed = trainer.last_step_stats
        m = [{k: numpy.zeros_like(v) for k, v in p.items()} for p in host]
        v = [{k: numpy.zeros_like(v) for k, v in p.items()} for p in host]
        data, labels = wf.loader.original_data.mem, \
            wf.loader.original_labels.mem
        # the reference's gradients, terms and counts of a batch
        reference_step = jax.jit(family.reference.step_function(descr))
        for step in range(2):
            tokens, targets = data[idx[step]], labels[idx[step]]
            grads, terms, counts = reference_step(host, tokens,
                                                  targets)[:3]
            numpy.testing.assert_allclose(losses[step], terms["main"],
                                          rtol=2e-5)
            for name, term in program_terms(observed).items():
                numpy.testing.assert_allclose(
                    term[step], terms[name], rtol=family.term_rtol,
                    err_msg=name)
            t = step + 1
            corr = numpy.sqrt(1 - hp["b2"] ** t) / (1 - hp["b1"] ** t)
            sparse = 0
            for i, layer in enumerate(host):
                for k in layer:
                    if k == "select_bias":
                        layer[k] = layer[k] + bias_change(descr[i],
                                                          counts[sparse])
                        continue
                    g = numpy.asarray(grads[i][k])
                    m[i][k] = hp["b1"] * m[i][k] + (1 - hp["b1"]) * g
                    v[i][k] = hp["b2"] * v[i][k] + (1 - hp["b2"]) * g * g
                    layer[k] = layer[k] - hp["lr"] * corr * m[i][k] / (
                        numpy.sqrt(v[i][k]) + hp["eps"])
                sparse += descr[i]["type"] == "moe"
        for i, (fwd, layer) in enumerate(zip(wf.forwards, host)):
            for k in layer:
                # Adam's first steps move every weight by ~lr whatever
                # its gradient: compare the MOVE, to a twentieth of a step
                numpy.testing.assert_allclose(
                    new_params[i][k], layer[k], rtol=0,
                    atol=(family.bias_atol if descr[i]["bias_rate"] else 0)
                    if k == "select_bias" else 0.05 * hp["lr"] * 2,
                    err_msg="%s.%s" % (fwd.name, k))
        positions = family.tiny["positions"]
        for i, (d, fwd) in enumerate(zip(descr, wf.forwards)):
            if d["type"] != "moe":
                continue
            assert "select_bias" not in new_states[i]["m"]
            assert float(new_states[i]["t"]) == len(idx)
            assert (numpy.abs(new_params[i]["select_bias"]).max() > 0) \
                == (d["bias_rate"] > 0)
            # no token dropped, any step: counts over ALL experts sum
            # to tokens * top_k
            numpy.testing.assert_array_equal(numpy.asarray(
                observed["stats"][unit_tag(i, fwd)]["expert_counts"]
            ).sum(1), 4 * positions * d["top_k"])
        trainer.publish_step_stats(new_params)
        assert set(sparse_tags(wf)) <= set(gauge(
            "veles_moe_load_max_over_mean"))
        for i, fwd in enumerate(wf.forwards):
            stats = observed["stats"].get(unit_tag(i, fwd), {})
            if OBJECTIVE in stats:
                numpy.testing.assert_allclose(
                    gauge("veles_index_loss")[unit_tag(i, fwd)],
                    float(jnp.mean(stats[OBJECTIVE])), rtol=1e-6)
            if "selected" in stats:
                pairs = sequence.selected_pairs(positions,
                                                fwd.index["top_k"])
                assert gauge("veles_attention_selected_per_step")[
                    unit_tag(i, fwd)] == pairs
                assert gauge("veles_attention_selected_pairs")[
                    fwd.name] == pairs

    def test_remat_changes_nothing(self, monkeypatch, core):
        """``updates``: ``remat`` on every block's units gives the same
        losses and updates to rounding (XLA fuses a recomputed unit its
        own way). ``blockwise``, ``fused``: with the sequence mixers
        alone rematerialized, op by op, the objective and EVERY
        gradient are the chain's without ``remat`` to the bit, on
        either lowering of the attention core: what the units keep is
        what they would have made again. The gauge reads what a unit
        kept, 0 without ``remat``."""
        family = self.family
        sizes = family.fused_sizes if core == "fused" else {}
        batch = 2 if core == "fused" else 4
        plain_wf, _ = build(family, sizes=sizes, batch=batch)
        remat_wf, descr = build(family, sizes=dict(sizes, remat=True),
                                batch=batch)
        assert all(fwd.remat for fwd, d in zip(remat_wf.forwards, descr)
                   if "remat" in d)
        if core != "updates":
            for fwd in remat_wf.forwards:
                fwd.remat = is_mixer(fwd)
        size = dict(family.tiny, **sizes)
        outs = []
        # the registry is the process's: the series that other chains'
        # units left in it (another test file's, in the same worker) go
        kept_bytes = get_registry().get("veles_remat_kept_bytes")
        if kept_bytes is not None:
            kept_bytes.reset()
        for wf in (plain_wf, remat_wf):
            trainer = FusedTrainer(wf)
            params, states = trainer.pull_params()
            if core == "updates":
                outs.append(trainer.train_class(params, states))
            else:
                tokens, labels = batch_of(wf, trainer, TRAIN)
                with traced_as(monkeypatch, core):
                    outs.append(jax.value_and_grad(lambda p: objective(
                        trainer, tokens, labels)(p)[0])(params))
            kept = gauge("veles_remat_kept_bytes")
            assert {fwd.name: kept[fwd.name]
                    for fwd in wf.forwards[:-1]} == {
                fwd.name: family.kept(fwd, batch, size, core)
                if is_mixer(fwd) else 0 for fwd in wf.forwards[:-1]}
        if core == "updates":
            numpy.testing.assert_allclose(outs[0][2], outs[1][2], rtol=1e-5)
            for a, b in zip(jax.tree_util.tree_leaves(outs[0][0]),
                            jax.tree_util.tree_leaves(outs[1][0])):
                numpy.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)
            return
        assert float(outs[0][0]) == float(outs[1][0])
        for a, b in zip(*(jax.tree_util.tree_leaves(o[1]) for o in outs)):
            numpy.testing.assert_array_equal(a, b)
        fused = gauge("veles_attention_core_fused")
        windows = gauge("veles_attention_window")
        for fwd in remat_wf.forwards:
            if isinstance(fwd, (GroupedAttentionForward,
                                LatentAttentionForward)):
                assert fused[fwd.name] == (core == "fused"), fwd.name
            if isinstance(fwd, GroupedAttentionForward):
                assert windows[fwd.name] == (fwd.window or 0), fwd.name

    def test_snapshot_and_resume(self):
        """A run of one epoch, dumped, loaded and run for a second gives
        what two epochs in one process give; the units' descriptor keys,
        a selection bias that moved and Adam's moments come back with
        it."""
        from veles_tpu.snapshotter import dump_workflow, load_workflow

        def run(wf, epochs):
            FusedTrainer(wf).train(max_epochs=epochs)

        whole, _ = build(self.family, max_epochs=2)
        run(whole, 2)
        first, descr = build(self.family, max_epochs=2)
        run(first, 1)
        resumed = load_workflow(dump_workflow(first))
        resumed.workflow = DummyLauncher()
        resumed.initialize(device=Device(backend="cpu"))
        for d, a, b in zip(descr, first.forwards, resumed.forwards):
            for key in DESCRIPTOR_KEYS.get(type(a), ()):
                assert getattr(a, key) == getattr(b, key), (a.name, key)
            if d["type"] != "moe":
                continue
            bias = a.select_bias.map_read()
            assert (numpy.abs(bias).max() > 0) == (d["bias_rate"] > 0)
            numpy.testing.assert_array_equal(b.select_bias.map_read(), bias)
            gd, = (g for g in resumed.gds if g.forward is b)
            assert float(gd.opt_state["t"]) == 2 and \
                "gate" in gd.opt_state["m"]
        run(resumed, 2)
        assert [h["epoch"] for h in resumed.decision.epoch_history] == [0, 1]
        for a, b in zip(whole.forwards, resumed.forwards):
            for name, arr in a.param_arrays().items():
                numpy.testing.assert_allclose(
                    b.param_arrays()[name].map_read(), arr.map_read(),
                    rtol=1e-4, atol=1e-6, err_msg="%s.%s" % (a.name, name))

    def test_cli_trains_the_tiny_preset(self, tmp_path):
        """Launcher -> FusedRunner reaches the model: the family's CLI
        line of the README."""
        from veles_tpu.__main__ import main
        name = self.family.name
        result_file = str(tmp_path / "results.json")
        code = main(["veles_tpu/models/%s.py" % name, "-s", "5",
                     "root.%s.max_epochs=2" % name,
                     "--result-file", result_file])
        assert code == 0
        with open(result_file) as f:
            assert json.load(f)

    # -- the units ------------------------------------------------------------

    def test_layer_types_are_registered(self, model):
        wf, descr, _, _ = model
        for d, fwd in zip(descr, wf.forwards):
            assert type(fwd) is LAYER_TYPES[d["type"]], d["type"]

    def test_other_units_match_reference(self, model):
        """Every unit the reference has alone, at the model's weights."""
        wf, descr, _, host = model
        assert {d["type"] for d in descr} >= set(self.family.units)
        x = random_state()
        for i, (d, fwd) in enumerate(zip(descr, wf.forwards)):
            if d["type"] in self.family.units:
                params = {k: jnp.asarray(v) for k, v in host[i].items()}
                numpy.testing.assert_allclose(
                    jax.jit(fwd.apply)(params, x),
                    jax.jit(functools.partial(
                        self.family.units[d["type"]], d))(params, x),
                    rtol=2e-5, atol=2e-6, err_msg=fwd.name)

    def test_the_shares_add_up(self, scoring):
        """``n_experts`` over shares of ``experts a share``, the
        deployment's cut at a small width: the partial routed results
        plus what every chip computes alike (the shared expert, the
        residual) once equal the uncut reference layer, each share the
        program's own against the reference's routed experts; a router
        placed before attention reads the attention unit's input on
        every share."""
        family = self.family
        n, top_k, share = family.shares
        sizes = dict(n_experts=n, top_k=top_k,
                     **({"scoring": scoring} if scoring else {}))
        wf, descr = build(family, sizes=dict(sizes, experts_held=(0, n)))
        index = [d["type"] for d in descr].index("moe")
        fwd, whole = wf.forwards[index], descr[index]
        rng = numpy.random.default_rng(7)
        full = {}
        for name, array in fwd.param_arrays().items():
            shape = array.shape
            if name == "norm":
                value = numpy.ones(shape)
            elif name == "select_bias":
                # a bias that moves is off zero; one that does not is 0
                value = 0.1 * rng.normal(size=shape) * bool(
                    whole["bias_rate"])
            else:
                value = rng.normal(size=shape) / numpy.sqrt(shape[-2])
            full[name] = jnp.asarray(value, jnp.float32)
        ctx = StepContext(wf.forwards, [{
            k: jnp.asarray(v) for k, v in p.items()}
            for p in host_params(wf)], None, True)
        if fwd.route_from:
            ctx.inputs[fwd.route_from] = random_state(10)
        x = random_state(6)
        expected = family.moe(whole, full, x, ctx, False)
        once = expected - family.moe(whole, full, x, ctx, True)
        total = None
        for first in range(0, n, share):
            share_wf, share_descr = build(
                family, sizes=dict(sizes, experts_held=(first, share)))
            unit = share_wf.forwards[index]
            part = dict(full, **{k: full[k][first:first + share]
                                 for k in ("gate", "up", "down")})
            if first:
                part.update((k, jnp.zeros_like(v)) for k, v in part.items()
                            if k.startswith("shared_"))
            # what every chip computes alike, counted once
            unit.residual = first == 0
            y = unit.apply_step(part, x, ctx)[0]
            numpy.testing.assert_allclose(
                y - (once if first == 0 else 0),
                family.moe(share_descr[index], part, x, ctx, True),
                rtol=2e-4, atol=2e-5)
            total = y if total is None else total + y
        numpy.testing.assert_allclose(total, expected, rtol=2e-5, atol=2e-5)

    # -- the published layers and the benchmark's cut of them -----------------

    def configuration(self):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               self.family.config)) as f:
            return json.load(f)

    def test_published_layers_are_what_the_preset_describes(self):
        """The published chain is what the family says of it; the tiny
        preset has every mechanism of the published one."""
        family = self.family
        positions = self.configuration()["layers"][0]["positions"]
        chain = family.workflow.layers(**dict(family.published,
                                              positions=positions))
        tiny = family.workflow.layers(**family.tiny)
        every_mechanism(chain, tiny)
        family.published_facts(chain, tiny)

    def test_the_benchmarks_configuration_is_the_published_layers_cut(
            self):
        """The configuration's layer list is ``layers(**PUBLISHED)`` cut
        as the file itself says; its keys that name a published size
        say what ``PUBLISHED`` says; the parameters it holds by the
        units' own shapes."""
        family, config = self.family, self.configuration()
        expected = family.workflow.layers(**dict(
            family.published, **family.cut(config), remat=True))
        assert json.loads(json.dumps(expected)) == config["layers"]
        for key, ours in family.config_keys.items():
            assert config[key] == family.published[ours], key
        counts = parameters(expected, (
            config["batch"], config["layers"][0]["positions"],
            family.published["dim"]), family.counts_selection_bias)
        assert sum(counts) == config["held_here"]["parameters"] \
            == family.parameters
        family.configuration_facts(config, expected, counts)

    # -- the programs ---------------------------------------------------------

    def test_lowers_to_the_pinned_programs(self):
        """The tiny preset's train and validation segments, every block's
        unit rematerialized, hash to :attr:`Family.programs`: StableHLO
        (``jitted.lower(...).as_text()``, which carries no debug info)
        under jax 0.9.0 and this suite's ``conftest.py``. A change that
        means to move a family's programs prints the new ones here to
        pin them again."""
        if jax.__version__ != "0.9.0":
            pytest.skip("the hashes were taken under jax 0.9.0")
        prng.get().seed(3)
        prng.get("loader").seed(4)
        jitted = {}

        class Capturing(FusedTrainer):
            def _compile_train(self, fn):
                jitted["train"] = super()._compile_train(fn)
                return jitted["train"]

            def _compile_eval(self, fn):
                jitted["eval"] = super()._compile_eval(fn)
                return jitted["eval"]

        wf = self.family.workflow(DummyLauncher(), sizes={"remat": True},
                                  n_train=8, n_valid=4, minibatch_size=4,
                                  seed=3)
        wf.initialize(device=Device(backend="cpu"))
        trainer = Capturing(wf)
        params, states = trainer.pull_params()
        idx = jnp.asarray(trainer._segment_indices(TRAIN))
        keys = jax.vmap(lambda i: jax.random.fold_in(
            trainer._dropout_base_key(), i))(jnp.arange(idx.shape[0]))
        texts = (
            jitted["train"].lower(trainer._data_args, params, states, idx,
                                  keys).as_text(),
            jitted["eval"].lower(trainer._data_args, params, jnp.asarray(
                trainer._segment_indices(VALIDATION))).as_text())
        got = tuple(hashlib.sha256(t.encode()).hexdigest() for t in texts)
        assert got == self.family.programs, got
