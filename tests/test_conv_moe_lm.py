"""The language model of gated short-convolution and grouped-query
attention blocks with sparse experts and one table for embedding and
head, through the normal path (layer descriptors ->
``StandardWorkflow`` -> ``FusedTrainer``) against the plain float32
reference ``benchmark/reference/conv_moe_lm.py``, at a tiny size; the
short convolution against a banded matrix; the tied table's two
readers."""

import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.reference import conv_moe_lm as ref
from veles_tpu import prng, remat
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.models.conv_moe_lm import (PUBLISHED, TINY,
                                          ConvMoELMWorkflow, layers)
from veles_tpu.nn import precision
from veles_tpu.nn.attention import GroupedAttentionForward
from veles_tpu.nn.short_conv import ShortConvForward, causal_taps
from veles_tpu.parallel import sequence
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer

MIXERS = ("ShortConvForward", "GroupedAttentionForward")


@pytest.fixture(autouse=True)
def float32_highest():
    """The comparisons are float32 against float32: the policy pinned,
    every product at full precision on both sides."""
    precision.set_policy("float32")
    with jax.default_matmul_precision("highest"):
        yield
    precision.set_policy(None)


def build(sizes=None, n_train=8, n_valid=4, batch=4, seed=3, **kwargs):
    prng.get().seed(seed)
    prng.get("loader").seed(seed + 1)
    wf = ConvMoELMWorkflow(DummyLauncher(), sizes=sizes, n_train=n_train,
                           n_valid=n_valid, minibatch_size=batch,
                           seed=seed, **kwargs)
    wf.initialize(device=Device(backend="cpu"))
    descr = layers(**dict(TINY, **(sizes or {})))
    for d, fwd in zip(descr, wf.forwards):
        d["name"] = fwd.name
    return wf, descr


def host_params(wf):
    return [{k: numpy.array(a.map_read())
             for k, a in fwd.param_arrays().items()}
            for fwd in wf.forwards]


@pytest.fixture(scope="module")
def model():
    precision.set_policy("float32")
    wf, descr = build()
    return wf, descr, FusedTrainer(wf), host_params(wf)


def random_state(seed=0, batch=2, positions=TINY["positions"]):
    return jnp.asarray(numpy.random.default_rng(seed).normal(
        size=(batch, positions, TINY["dim"])), jnp.float32)


def gauge(name, label="unit"):
    """The registry's readings of ``name`` by ``label``."""
    return {labels[label]: child.value
            for labels, child in get_registry().get(name).series()}


def mat(rng, *shape):
    return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                       jnp.float32)


# -- the unit against the reference ----------------------------------------

def conv_unit(taps, bias, seed=0):
    descr = dict(type="short_conv", taps=taps, bias=bias, eps=1e-5)
    fwd = ShortConvForward(DummyLauncher(), name="sc%d" % taps, **{
        k: v for k, v in descr.items() if k != "type"})
    rng = numpy.random.default_rng(seed + taps)
    params = {
        name: jnp.asarray(
            1.0 + 0.1 * rng.normal(size=shape) if kind == "gain"
            else rng.normal(size=shape) / math.sqrt(shape[0])
            if kind == "matrix" else rng.normal(size=shape), jnp.float32)
        for name, (shape, kind) in fwd.param_shapes(
            (2, 16, TINY["dim"])).items()}
    return descr, fwd, params


@pytest.mark.parametrize("taps,bias", [(3, False), (3, True), (1, False),
                                       (4, False), (5, True)])
def test_short_conv_matches_reference(taps, bias):
    """The published three taps and other counts, with and without the
    convolution's bias, on 16 positions (no multiple of three): the
    output, the gradient to the input and to every parameter."""
    descr, fwd, params = conv_unit(taps, bias)
    assert ("taps_bias" in params) == bias
    assert params["taps"].shape == (TINY["dim"], taps)
    assert params["in"].shape == (TINY["dim"], 3 * TINY["dim"])
    x = random_state(3)
    numpy.testing.assert_allclose(
        fwd.apply(params, x), ref.short_conv(descr, params, x),
        rtol=2e-5, atol=2e-6)

    def loss(fn):
        return lambda p, v: jnp.sum(jnp.sin(fn(p, v)))
    got = jax.grad(loss(fwd.apply), (0, 1))(params, x)
    want = jax.grad(loss(lambda p, v: ref.short_conv(descr, p, v)),
                    (0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 0
        numpy.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.abs(w).max()))
    assert gauge("veles_short_conv_taps")[fwd.name] == taps
    assert gauge("veles_short_conv_lowering")[fwd.name] == 0.0


@pytest.mark.parametrize("taps", [3, 4])
def test_the_taps_are_a_causal_band(taps):
    """The convolution as an explicit (seq, seq) banded matrix a
    channel; and by the whole unit's Jacobian, output ``t`` moves with
    inputs ``t - taps + 1 .. t`` and with no other."""
    rng = numpy.random.default_rng(taps)
    seq, dim = 7, 8
    u = jnp.asarray(rng.normal(size=(2, seq, dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(dim, taps)), jnp.float32)
    back = numpy.arange(seq)[:, None] - numpy.arange(seq)[None, :]
    band = (back >= 0) & (back < taps)
    # M[c, t, s] = w[c, taps - 1 - (t - s)] inside the band
    matrix = numpy.where(band[None], numpy.asarray(w)[
        :, numpy.clip(taps - 1 - back, 0, taps - 1)], 0.0)
    numpy.testing.assert_allclose(
        causal_taps(u, w), numpy.einsum("cts,bsc->btc", matrix, u),
        rtol=1e-5, atol=1e-6)
    descr, fwd, params = conv_unit(taps, False)
    x = jnp.asarray(rng.normal(size=(1, seq, TINY["dim"])), jnp.float32)
    jac = jax.jacobian(lambda v: fwd.apply(params, v) - v)(x)
    moved = numpy.abs(numpy.asarray(jac)[0, :, :, 0]).sum((1, 3)) > 0
    numpy.testing.assert_array_equal(moved, band)
    with pytest.raises(ValueError, match="taps is none"):
        ShortConvForward(DummyLauncher(), taps=0)


def test_short_conv_names_its_parts_under_remat():
    """``proj`` (the norm, the two products, the residual) and ``mix``
    (gate, convolution, gate) are siblings, an operation under one of
    them, in the forward pass, in the backward pass and in the forward
    a rematerialized unit runs again."""
    _, fwd, params = conv_unit(3, False)
    x = random_state(1)

    def loss(p, v):
        y, _ = remat.checkpoint(lambda p, v: fwd.apply(p, v))(p, v)
        return jnp.sum(y)

    def equations(jaxpr, outer=""):
        # an inner jaxpr's names stand behind its equation's
        for eqn in jaxpr.eqns:
            stack = "%s/%s" % (outer, eqn.source_info.name_stack)
            yield eqn, stack
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner, stack)

    seen = set()
    for eqn, stack in equations(jax.make_jaxpr(jax.grad(loss, (0, 1)))(
            params, x).jaxpr):
        parts = set(re.findall(r"\b(proj|mix)\b", stack))
        assert len(parts) <= 1, stack
        if eqn.primitive.name == "dot_general":
            assert parts == {"proj"}, stack
        if eqn.primitive.name == "pad":
            assert parts == {"mix"}, stack
        for part in parts:
            seen.add((part, "transpose" in stack,
                      "rematted_computation" in stack))
    # (part, under the transposition, in the forward run again)
    assert seen == {("proj", False, False), ("mix", False, False),
                    ("proj", True, True), ("mix", True, True),
                    ("proj", True, False), ("mix", True, False)}


def test_other_units_match_reference(model):
    wf, descr, _, host = model
    x = random_state()
    for i, (d, fwd) in enumerate(zip(descr, wf.forwards)):
        if d["type"] in ("short_conv", "grouped_attention", "gated_mlp",
                         "moe", "rms_norm"):
            params = {k: jnp.asarray(v) for k, v in host[i].items()}
            numpy.testing.assert_allclose(
                fwd.apply(params, x), ref.UNITS[d["type"]](d, params, x),
                rtol=2e-5, atol=2e-6, err_msg=fwd.name)


# -- the core at a head size under a lane ------------------------------------

@pytest.mark.parametrize("heads,window", [((8, 2), None), ((2, 2), None),
                                          ((4, 2), 100)])
def test_a_head_of_64_takes_the_fused_kernels_behind_zeros(
        monkeypatch, caplog, heads, window):
    """Query heads of 64 on fewer (or as many) key/value heads, read
    as on a TPU: ``fused_refusal`` turns half a lane away, so
    ``causal_attention`` pads every head to 128 with zeros, runs the
    kernels (interpreted here) and cuts the output back; the gauges
    say so, nothing is logged as a fallback; output and the three
    gradients are the explicit mask's at head size 64."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sequence, "_refusals_logged", set())
    rng = numpy.random.default_rng(64)
    q = jnp.asarray(rng.normal(size=(1, heads[0], 256, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, heads[1], 256, 64)),
                        jnp.float32) for _ in range(2))
    assert "head size 64 is not a multiple of 128" == \
        sequence.fused_refusal(q, k, v, 128, window)
    unit = "gqa64_%d_%s" % (heads[0], window)

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(jnp.sin(out)), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out,) + grads

    with caplog.at_level("WARNING", logger="sequence"), \
            pltpu.force_tpu_interpret_mode():
        got = run(lambda q, k, v: sequence.causal_attention(
            q, k, v, 0.125, 128, unit=unit, window=window))
    assert gauge("veles_attention_core_fused")[unit] == 1.0
    assert gauge("veles_attention_head_padding")[unit] == 64.0
    assert gauge("veles_attention_kv_group")[unit] == heads[0] / heads[1]
    assert not [r for r in caplog.records if unit in r.getMessage()]
    group = heads[0] // heads[1]
    oracle = run(lambda q, k, v: sequence.local_attention(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
        causal=True, scale=0.125, window=window))
    for name, g, o in zip(("out", "dq", "dk", "dv"), got, oracle):
        assert g.shape == o.shape, name
        numpy.testing.assert_allclose(
            g, o, rtol=1e-5 if name == "out" else 2e-4, atol=2e-5,
            err_msg=name)
    # whole lanes are not padded, and off the TPU nothing is
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    sequence.causal_attention(q, k, v, 0.125, 128, unit=unit)
    assert gauge("veles_attention_head_padding")[unit] == 0.0
    assert gauge("veles_attention_core_fused")[unit] == 0.0


# -- the whole model through the trainer -----------------------------------

def batch_of(wf, trainer, klass, row=0):
    idx = trainer._segment_indices(klass)[row]
    return (wf.loader.original_data.mem[idx],
            wf.loader.original_labels.mem[idx])


def test_published_layers_are_what_the_preset_describes():
    """The published list block by block: 18 convolution and 6
    attention blocks in the order no period gives, the first two
    blocks dense by count, a tied head; the tiny preset has every
    mechanism."""
    chain = layers(**dict(PUBLISHED, positions=8192))
    mixers = [d["type"] for d in chain[1:-2:2]]
    assert len(mixers) == 24 and mixers.count("short_conv") == 18
    assert [i for i, t in enumerate(mixers) if t == "grouped_attention"] \
        == [2, 6, 10, 14, 18, 21]
    assert [d["type"] for d in chain[2:-2:2]] == \
        ["gated_mlp"] * 2 + ["moe"] * 22
    conv, attention, sparse = chain[1], chain[5], chain[6]
    assert (conv["taps"], conv["bias"], conv["eps"]) == (3, False, 1e-5)
    assert (attention["heads"], attention["kv_heads"],
            attention["head_dim"], attention["qk_norm"],
            attention["gated"], attention["window"],
            attention["rope_theta"]) == (32, 8, 64, True, False, None, 1e6)
    assert (sparse["n_experts"], sparse["top_k"], sparse["hidden"],
            sparse["scoring"], sparse["normalize"], sparse["scale"],
            sparse["shared_experts"], sparse["normalize_eps"]) == (
                32, 4, 1792, "sigmoid", True, 1.0, 0, 1e-6)
    assert sparse["bias_rate"] > 0 and chain[2]["hidden"] == 7168
    assert chain[-1]["tied_to"] == chain[0]["name"] == "embedding"
    assert "weights_stddev" not in chain[-1]
    tiny = [d["type"] for d in layers(**TINY)[1:-2:2]]
    gaps = numpy.diff([i for i, t in enumerate(tiny)
                       if t == "grouped_attention"])
    assert len(set(gaps)) > 1 or len(gaps) < 2 and tiny[0] != tiny[-1]
    assert TINY["positions"] % TINY["taps"] and TINY["head_dim"] < 128
    assert TINY["kv_heads"] < TINY["heads"] and TINY["top_k"] > 1
    assert TINY["experts_held"][1] < TINY["n_experts"]
    assert {tiny[i] for i in range(TINY["dense_layers"])} == \
        {"short_conv", "grouped_attention"}


def test_validation_losses_match_reference(model):
    wf, descr, trainer, host = model
    params, _ = trainer.pull_params()
    losses, metrics, conf = trainer.eval_class(params, VALIDATION)
    n = wf.loader.class_lengths[VALIDATION]
    expected = ref.validation_batch_losses(
        descr, host, wf.loader.original_data.mem[:n],
        wf.loader.original_labels.mem[:n], 4)
    numpy.testing.assert_allclose(losses, expected, rtol=1e-5)
    assert abs(float(jnp.mean(losses))
               - numpy.log(TINY["vocabulary"])) < 0.1


def test_logits_fused_equals_eager_equals_reference(model):
    """One batch through ``Unit.run`` of every forward unit gives the
    head what the fused chain gives it; the head reads the embedding's
    table on both paths and owns nothing."""
    wf, descr, trainer, host = model
    tokens, _ = batch_of(wf, trainer, VALIDATION)
    wf.loader.minibatch_data.map_invalidate()[...] = tokens
    for fwd in wf.forwards:
        fwd.run()
    head = wf.forwards[-1]
    assert head.tied_to == "embedding" and not head.has_weights
    assert host[-1] == {} and head.param_values() == {}
    eager = numpy.asarray(head.output.map_read())
    expected = ref.logits(descr, host, jnp.asarray(tokens))
    numpy.testing.assert_allclose(
        eager, jax.nn.softmax(expected, -1), rtol=2e-4, atol=1e-7)
    params, _ = trainer.pull_params()
    state = trainer._forward_range(
        params[:-1], jnp.asarray(tokens), None, False, 0,
        len(params) - 1)
    numpy.testing.assert_allclose(
        head.apply_for_grad({"table": params[0]["weights"]}, state),
        expected, rtol=2e-4, atol=2e-5)
    # a table that moved is read anew by the eager head
    table = wf.forwards[0].weights
    kept = numpy.array(table.map_read())
    table.map_invalidate()[...] = 2 * kept
    head.run()
    numpy.testing.assert_allclose(
        head.output.map_read(), jax.nn.softmax(2 * expected, -1),
        rtol=2e-4, atol=1e-7)
    table.map_invalidate()[...] = kept


def inputs_of(descr, host, tokens, index):
    """The reference's state entering layer ``index``."""
    cut = descr[:index] + [{"type": "vocabulary_head"}]
    return jax.jit(lambda p, t: ref.states(cut, p, t))(
        host[:index] + [{}], jnp.asarray(tokens))


def test_objective_and_every_gradient_match_reference(model):
    """The objective and the gradient to every parameter; THE TABLE'S
    ALONE, which is the sum of its two readers', the embedding's rows
    and the head's columns, each of which is most of it somewhere."""
    wf, descr, trainer, host = model
    tokens, labels = batch_of(wf, trainer, TRAIN)
    params, _ = trainer.pull_params()
    valid = jnp.ones(len(tokens), bool)

    def objective(p):
        total, (report, _, extras) = trainer._token_objective(
            p, jnp.asarray(tokens), jnp.asarray(labels), None, valid,
            True)
        return total, (report, extras)

    (total, (report, extras)), grads = jax.value_and_grad(
        objective, has_aux=True)(params)
    r_total, terms = ref.objective(descr, host, tokens, labels)
    numpy.testing.assert_allclose(total, r_total, rtol=1e-5)
    numpy.testing.assert_allclose(report, terms["main"], rtol=1e-5)
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
    assert checked == sum(len(fwd.gradient_params(p))
                          for fwd, p in zip(wf.forwards, params))
    assert grads[-1] == {} and gauge("veles_head_tied")[
        wf.forwards[-1].name] == 1.0

    # the two readers apart: the head given a copy of the table
    table = jnp.asarray(host[0]["weights"])
    with jax.default_matmul_precision("highest"):
        by_rows, by_head = jax.grad(
            lambda p, t: ref.objective(descr, p, tokens, labels,
                                       head_table=t)[0], (0, 1))(
                [{k: jnp.asarray(v) for k, v in p.items()} for p in host],
                table)
    by_rows = numpy.asarray(by_rows[0]["weights"])
    by_head, whole = numpy.asarray(by_head), numpy.asarray(
        grads[0]["weights"])
    norm = numpy.linalg.norm
    assert norm(by_rows) > 0.05 * norm(whole)
    assert norm(by_head) > 0.05 * norm(whole)
    numpy.testing.assert_allclose(
        whole, by_rows + by_head, rtol=2e-3,
        atol=2e-4 * float(numpy.abs(whole).max()))
    assert norm(whole - by_head) > 0.05 * norm(whole)
    assert norm(whole - by_rows) > 0.05 * norm(whole)
    # what the comparison on the chip reads when a reader is dropped
    step = {"moments": [{k: 0.1 * numpy.asarray(v) for k, v in g.items()
                         if k != "select_bias"} for g in grads]}
    dropped = {"moments": [dict(m) for m in step["moments"]]}
    dropped["moments"][0]["weights"] = 0.1 * by_rows
    expected = {"moments": [{k: 0.1 * numpy.asarray(v)
                             for k, v in g.items()} for g in r_grads]}
    wanted = lambda i, d: ["weights"] if i == 0 else []  # noqa: E731
    assert ref._error_over(descr, step, expected, wanted) < 2e-3
    assert ref._error_over(descr, dropped, expected, wanted) > 0.05

    for tag, stats in extras["stats"].items():
        index = int(tag[1:3])
        numpy.testing.assert_array_equal(
            stats["expert_counts"],
            ref.expert_counts(descr[index], host[index], inputs_of(
                descr, host, tokens, index)))


def test_two_adam_steps_with_the_bias_rule():
    """Two steps of the train segment against Adam written out here on
    the reference's gradients, the selection bias moved by the rule
    from the reference's own counts; no token is dropped; the sparse
    layers publish their rows."""
    wf, descr = build()
    trainer = FusedTrainer(wf)
    host = host_params(wf)
    params, states = trainer.pull_params()
    idx = trainer._segment_indices(TRAIN)
    new_params, new_states, losses, _ = trainer.train_class(params, states)
    hp = dict(lr=3e-4, b1=0.9, b2=0.95, eps=1e-8)
    m = [{k: numpy.zeros_like(v) for k, v in p.items()} for p in host]
    v = [{k: numpy.zeros_like(v) for k, v in p.items()} for p in host]
    data, labels = wf.loader.original_data.mem, \
        wf.loader.original_labels.mem
    moved = 0
    for step in range(2):
        tokens, targets = data[idx[step]], labels[idx[step]]
        counts = {}
        _, terms = ref.objective(descr, host, tokens, targets, counts)
        numpy.testing.assert_allclose(losses[step], terms["main"],
                                      rtol=2e-5)
        grads = ref.gradients(descr, host, tokens, targets)
        t = step + 1
        corr = numpy.sqrt(1 - hp["b2"] ** t) / (1 - hp["b1"] ** t)
        sparse = 0
        for i, layer in enumerate(host):
            for k in layer:
                if k == "select_bias":
                    change = ref.moe_lm.bias_change(descr[i],
                                                    counts[sparse])
                    moved += int(numpy.count_nonzero(change))
                    layer[k] = layer[k] + change
                    continue
                g = numpy.asarray(grads[i][k])
                m[i][k] = hp["b1"] * m[i][k] + (1 - hp["b1"]) * g
                v[i][k] = hp["b2"] * v[i][k] + (1 - hp["b2"]) * g * g
                layer[k] = layer[k] - hp["lr"] * corr * m[i][k] / (
                    numpy.sqrt(v[i][k]) + hp["eps"])
            sparse += descr[i]["type"] == "moe"
    assert moved > 0
    for i, (fwd, layer) in enumerate(zip(wf.forwards, host)):
        for k in layer:
            # Adam's first steps move every weight by ~lr whatever its
            # gradient: compare the MOVE, to a twentieth of a step
            numpy.testing.assert_allclose(
                new_params[i][k], layer[k], rtol=0,
                atol=1e-9 if k == "select_bias" else 0.05 * hp["lr"] * 2,
                err_msg="%s.%s" % (fwd.name, k))
    for stats in trainer.last_step_stats["stats"].values():
        numpy.testing.assert_array_equal(
            numpy.asarray(stats["expert_counts"]).sum(1),
            4 * TINY["positions"] * TINY["top_k"])
    trainer.publish_step_stats(new_params)
    sparse = [fwd for fwd in wf.forwards
              if type(fwd).__name__ == "MoEForward"]
    assert len(gauge("veles_moe_load_max_over_mean")) >= len(sparse)
    assert max(gauge("veles_moe_select_bias_max").values()) > 0


@pytest.mark.parametrize("what", ["updates", "mixers"])
def test_remat_changes_nothing(what):
    """``updates``: ``remat`` on every block's units gives the same
    losses and updates to rounding. ``mixers``: with the convolution
    and attention units alone rematerialized, the objective and EVERY
    gradient are the chain's without ``remat`` to the bit. A
    convolution unit keeps nothing across its rematerialization, an
    attention unit its core's output and one statistic a row."""
    plain_wf, _ = build()
    remat_wf, _ = build(sizes=dict(remat=True))
    assert all(fwd.remat for fwd in remat_wf.forwards
               if type(fwd).__name__ in MIXERS + ("MoEForward",
                                                  "GatedMLPForward"))
    if what == "mixers":
        for fwd in remat_wf.forwards:
            fwd.remat = type(fwd).__name__ in MIXERS
    outs = []
    for wf in (plain_wf, remat_wf):
        trainer = FusedTrainer(wf)
        params, states = trainer.pull_params()
        if what == "updates":
            outs.append(trainer.train_class(params, states))
        else:
            tokens, labels = batch_of(wf, trainer, TRAIN)
            outs.append(jax.value_and_grad(
                lambda p: trainer._token_objective(
                    p, jnp.asarray(tokens), jnp.asarray(labels), None,
                    jnp.ones(len(tokens), bool), True)[0])(params))
        kept = gauge("veles_remat_kept_bytes")
        assert {fwd.name: kept[fwd.name] for fwd in wf.forwards[:-1]} == {
            fwd.name: 4 * fwd.heads * TINY["positions"]
            * (TINY["head_dim"] * 4 + 4) if fwd.remat
            and isinstance(fwd, GroupedAttentionForward) else 0
            for fwd in wf.forwards[:-1]}
    if what == "updates":
        numpy.testing.assert_allclose(outs[0][2], outs[1][2], rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(outs[0][0]),
                        jax.tree_util.tree_leaves(outs[1][0])):
            numpy.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-5)
        return
    assert float(outs[0][0]) == float(outs[1][0])
    for a, b in zip(*(jax.tree_util.tree_leaves(o[1]) for o in outs)):
        numpy.testing.assert_array_equal(a, b)


def test_snapshot_and_resume_hold_the_table_once():
    """A run of one epoch, dumped, loaded and run for a second gives
    what two epochs in one process give; the units' descriptor keys
    come back with it; the head brings no array of its own into the
    snapshot, and the resumed head reads the resumed embedding's."""
    from veles_tpu.snapshotter import dump_workflow, load_workflow

    def run(wf, epochs):
        trainer = FusedTrainer(wf)
        trainer.train(max_epochs=epochs)
        return trainer

    whole, _ = build(max_epochs=2)
    run(whole, 2)
    first, _ = build(max_epochs=2)
    run(first, 1)
    dumped = dump_workflow(first)
    resumed = load_workflow(dumped)
    resumed.workflow = DummyLauncher()
    resumed.initialize(device=Device(backend="cpu"))
    for a, b in zip(first.forwards, resumed.forwards):
        if isinstance(a, ShortConvForward):
            assert (a.n_taps, a.PARAMS, a.eps) == \
                (b.n_taps, b.PARAMS, b.eps)
    head, embedding = resumed.forwards[-1], resumed.forwards[0]
    assert head.tied_to == "embedding" and not head.param_arrays()
    assert head.weights.mem is None
    assert head.table is embedding.weights
    for wf in (first, resumed):
        ends = [wf.forwards[0].param_arrays(), wf.forwards[-1].param_arrays()]
        assert [sorted(arrays) for arrays in ends] == [["weights"], []]
        assert wf.forwards[-1].weights.mem is None
    run(resumed, 2)
    assert [h["epoch"] for h in resumed.decision.epoch_history] == [0, 1]
    for a, b in zip(whole.forwards, resumed.forwards):
        for name, arr in a.param_arrays().items():
            numpy.testing.assert_allclose(
                b.param_arrays()[name].map_read(), arr.map_read(),
                rtol=1e-4, atol=1e-6, err_msg="%s.%s" % (a.name, name))


def test_export_holds_the_table_once():
    """The two ends of a token chain as package entries: the
    embedding brings the table, the tied head a name and no array."""
    from veles_tpu.export.package import _MemberWriter, _unit_entry
    wf, _ = build()
    writer = _MemberWriter("float32")
    first = _unit_entry(wf.forwards[0], writer)
    last = _unit_entry(wf.forwards[-1], writer)
    assert first["data"]["weights"].startswith("@")
    assert last["data"] == {"vocabulary": TINY["vocabulary"],
                            "tied_to": "embedding"}
    assert len(writer.members) == 1


def test_cli_trains_the_tiny_preset(tmp_path):
    """Launcher -> FusedRunner reaches the model."""
    from veles_tpu.__main__ import main
    result_file = str(tmp_path / "results.json")
    code = main(["veles_tpu/models/conv_moe_lm.py", "-s", "5",
                 "root.conv_moe_lm.max_epochs=2",
                 "--result-file", result_file])
    assert code == 0
    assert json.load(open(result_file))


def test_layer_types_are_registered():
    from veles_tpu.nn.tokens import VocabularyHeadForward
    from veles_tpu.standard_workflow import LAYER_TYPES
    assert LAYER_TYPES["short_conv"] is ShortConvForward
    assert LAYER_TYPES["vocabulary_head"] is VocabularyHeadForward
    with pytest.raises(KeyError):
        build(sizes={"layer_types": ("conv", "linear_attention")})


# -- the other families' programs are untouched ------------------------------

#: sha256 of the tiny presets' train and validation segments as
#: StableHLO (``jitted.lower(...).as_text()``, which carries no debug
#: info) at the parent commit of PR 37 (``96a2645``) under jax 0.9.0
#: and this suite's ``conftest.py`` (this same test run in a checkout
#: of that commit), every block's unit rematerialized: an untied head,
#: a sparse layer without ``normalize_eps`` and a step whose head
#: owns its weights trace to what they traced to before this PR. After
#: a change that means to move these programs, print the new ones by
#: running this test and pin them again.
PARENT_PROGRAMS = {
    ("indexed_moe_lm", "IndexedMoELMWorkflow"): (
        "457bac576ad5bdad37a4344263759d9f8e9c55c020b70c5b34ffefd04fb6e688",
        "394db08d34d1b5b793787161de4a536083775e3df56111504492ca52ffe8c6f2"),
    ("latent_moe_lm", "LatentMoELMWorkflow"): (
        "13133eda9b325eddb336319dc520c7424365fd4016a6d1b82bc5fc753edfbae5",
        "c2c8f3b9ac9298200d7fca68e5539dac18f77a8341ff7e46a37f240df23ff72b"),
    ("window_moe_lm", "WindowMoELMWorkflow"): (
        "05655b5122b331656c33d17b6fdda314f203e488f5f0116c3b208657dfa63cbe",
        "cfa5ab17026c34f418b0853e62d07123f008e44422e8fa5d98d1dd713e8e4b2a"),
}


@pytest.mark.parametrize("module,name", sorted(PARENT_PROGRAMS))
def test_the_other_token_models_lower_to_the_parents_programs(module,
                                                              name):
    if jax.__version__ != "0.9.0":
        pytest.skip("the hashes were taken under jax 0.9.0")
    import importlib
    cls = getattr(importlib.import_module("veles_tpu.models." + module),
                  name)
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

    wf = cls(DummyLauncher(), sizes={"remat": True}, n_train=8, n_valid=4,
             minibatch_size=4, seed=3)
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
    assert got == PARENT_PROGRAMS[module, name], got


# -- routing at the published width, and the chip's share ------------------

def sparse_params(rng, dim, n_experts, held, hidden, bias=0.0):
    return {"weights": mat(rng, dim, n_experts), "norm": jnp.ones(dim),
            "select_bias": jnp.asarray(
                bias * rng.normal(size=n_experts), jnp.float32),
            "gate": mat(rng, held, dim, hidden),
            "up": mat(rng, held, dim, hidden),
            "down": mat(rng, held, hidden, dim)}


@pytest.mark.parametrize("bias", [0.0, 0.2])
def test_top_4_of_32_under_a_bias_routes_as_the_reference(bias):
    """The published router, 32 sigmoid outputs and 4 a token under a
    selection bias, renormalised over the sum + 1e-6, scale 1, no
    shared expert, experts 0..7 held: the unit's result and its counts
    are the reference's; the bias changes the choice and not the
    weights' source."""
    from veles_tpu.nn.moe import MoEForward
    descr = dict(type="moe", n_experts=32, hidden=16,
                 capacity_factor=None, top_k=4, scoring="sigmoid",
                 normalize=True, normalize_eps=1e-6, scale=1.0,
                 shared_experts=0, experts_held=[0, 8], bias_rate=1e-3,
                 dispatch_rows=48, eps=1e-5)
    fwd = MoEForward(DummyLauncher(), name="biased", **{
        k: v for k, v in descr.items() if k != "type"})
    assert fwd.normalize_eps == 1e-6
    dim = TINY["dim"]
    params = sparse_params(numpy.random.default_rng(11), dim, 32, 8, 16,
                           bias)
    x = random_state(9, batch=4)
    y, stats = fwd.apply_step(params, x, None)
    numpy.testing.assert_allclose(y, ref.moe(descr, params, x), rtol=2e-5,
                                  atol=2e-6)
    counts = ref.expert_counts(descr, params, x)
    numpy.testing.assert_array_equal(stats["expert_counts"], counts)
    assert int(counts.sum()) == 64 * 4
    h = ref.rms_norm(x, params["norm"], 1e-5).reshape(-1, dim)
    chosen, weights = ref.route(descr, params, h)
    scores = jax.nn.sigmoid(h @ params["weights"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    numpy.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    unbiased, _ = ref.route(
        descr, dict(params, select_bias=jnp.zeros(32)), h)
    assert bool(jnp.any(jnp.sort(chosen) != jnp.sort(unbiased))) \
        == (bias > 0)
    # the bound of 48 rows is passed by some routing: the exact
    # overflow path gives the same layer
    assert int(counts[:8].sum()) > 48
    # the default of the other models' layers is what it was
    assert MoEForward(DummyLauncher(), capacity_factor=None
                      ).normalize_eps == 1e-20


def test_the_shares_add_up():
    """32 experts over 4 shares of 8, the deployment's cut at a small
    width: the four partial routed results plus the residual once
    equal the uncut reference layer; there is no shared expert."""
    sizes = dict(TINY, n_experts=32, top_k=4)
    whole = layers(**dict(sizes, experts_held=None))
    index = [i for i, d in enumerate(whole) if d["type"] == "moe"][0]
    dim, hidden = TINY["dim"], TINY["expert_hidden"]
    full = sparse_params(numpy.random.default_rng(7), dim, 32, 32, hidden,
                         0.1)
    x = random_state(6)
    expected = ref.moe(whole[index], full, x)
    total = None
    for first in range(0, 32, 8):
        wf, descr = build(sizes={"n_experts": 32, "top_k": 4,
                                 "experts_held": (first, 8)})
        fwd = wf.forwards[index]
        share = dict(full, **{k: full[k][first:first + 8]
                              for k in ("gate", "up", "down")})
        # what every chip computes alike, counted once: the residual
        fwd.residual = first == 0
        part = fwd.apply(share, x)
        numpy.testing.assert_allclose(
            part - (x if first == 0 else 0),
            ref.moe(descr[index], share, x, residual=False), rtol=2e-4,
            atol=2e-5)
        total = part if total is None else total + part
    numpy.testing.assert_allclose(total, expected, rtol=2e-5, atol=2e-5)


def test_the_benchmarks_configuration_is_the_published_layers_cut():
    """``benchmark/configs/lfm2-8b-a1b-ep4share.json`` holds
    ``layers(**PUBLISHED)`` cut as the file itself says: blocks 1..5
    of the published list (the leading dense blocks counted once), 8
    of 32 experts, 16,384 vocabulary rows, one sequence of the length
    it assumes; and 507,820,160 trained parameters by the units' own
    shapes, the table once."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "lfm2-8b-a1b-ep4share.json")
    with open(path) as f:
        config = json.load(f)
    positions = config["layers"][0]["positions"]
    blocks = config["held_here"]["blocks"]
    expected = layers(**dict(
        PUBLISHED,
        layer_types=PUBLISHED["layer_types"][blocks[0]:blocks[1] + 1],
        dense_layers=PUBLISHED["dense_layers"] - blocks[0],
        vocabulary=config["vocab_size"], positions=positions,
        experts_held=(0, config["num_experts"]),
        dispatch_rows=config["layers"][4]["dispatch_rows"], remat=True))
    assert json.loads(json.dumps(expected)) == config["layers"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], blocks) == (5, 8, 16384, [1, 5])
    assert config["published"]["layer_types"] == \
        list(PUBLISHED["layer_types"]) == config["layer_types"]
    assert [d["type"] for d in expected[1:-2:2]] == [
        "short_conv", "grouped_attention", "short_conv", "short_conv",
        "short_conv"]
    assert [d["type"] for d in expected[2:-2:2]] == \
        ["gated_mlp"] + ["moe"] * 4
    for key, ours in (("hidden_size", "dim"),
                      ("num_attention_heads", "heads"),
                      ("num_key_value_heads", "kv_heads"),
                      ("conv_L_cache", "taps"),
                      ("intermediate_size", "dense_hidden"),
                      ("moe_intermediate_size", "expert_hidden"),
                      ("num_experts_per_tok", "top_k"),
                      ("rope_theta", "rope_theta"),
                      ("norm_eps", "eps")):
        assert config[key] == PUBLISHED[ours], key
    assert config["published"]["num_experts"] == PUBLISHED["n_experts"] \
        == expected[4]["n_experts"]
    total, shape = 0, (config["batch"], positions, PUBLISHED["dim"])
    by_block, biases = [], 0
    for descr in expected:
        before = total
        kwargs = {k: v for k, v in descr.items()
                  if k not in ("type", "remat")}
        if descr["type"] == "grouped_attention":
            total += sum(math.prod(s) for s, _ in GroupedAttentionForward(
                DummyLauncher(), **kwargs).param_shapes(shape).values())
        elif descr["type"] == "short_conv":
            total += sum(math.prod(s) for s, _ in ShortConvForward(
                DummyLauncher(), **kwargs).param_shapes(shape).values())
        elif descr["type"] == "moe":
            # router, norm and the held experts; the selection bias
            # apart: no gradient reaches it, no moment is kept for it
            total += shape[-1] * (descr["n_experts"] + 1) \
                + descr["experts_held"][1] * 3 * shape[-1] * descr["hidden"]
            biases += descr["n_experts"]
        elif descr["type"] == "gated_mlp":
            total += shape[-1] + 3 * shape[-1] * descr["hidden"]
        elif descr["type"] == "rms_norm":
            total += shape[-1]
        elif descr["type"] == "token_embedding":
            total += descr["vocabulary"] * shape[-1]
        else:  # the head owns nothing
            assert descr["tied_to"] == "embedding"
        by_block.append(total - before)
    held = config["held_here"]
    assert total == held["parameters"] == 507820160
    assert biases == 128 and "128" in held["and_selection_bias"]
    assert by_block[0] == held["parameters_by_part"]["table"] == 33554432
    assert by_block[1] + by_block[2] == \
        held["parameters_by_part"]["dense_conv_block"] == 60827648
    assert by_block[3] + by_block[4] == \
        held["parameters_by_part"]["sparse_attention_block"] == 98635904
    assert by_block[5] + by_block[6] == \
        held["parameters_by_part"]["sparse_conv_block"] == 104933376
