"""The language model of grouped-query attention under a learned
selection of keys (an index and its KL objective) with sparse experts,
through the normal path (layer descriptors -> ``StandardWorkflow`` ->
``FusedTrainer``) against the plain float32 reference
``benchmark/reference/indexed_moe_lm.py``, at a tiny size; the
selected core against an explicit mask; the search for the selected
keys against ``lax.top_k``."""

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import indexed_moe_lm as ref
from veles_tpu import prng, remat
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.models.indexed_moe_lm import (PUBLISHED, TINY,
                                             IndexedMoELMWorkflow, layers)
from veles_tpu.nn import precision
from veles_tpu.nn.attention import GroupedAttentionForward
from veles_tpu.parallel import sequence
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer
from veles_tpu.train.step import StepContext

ATTENTION = "GroupedAttentionForward"
INDEX = GroupedAttentionForward.INDEX


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
    wf = IndexedMoELMWorkflow(DummyLauncher(), sizes=sizes,
                              n_train=n_train, n_valid=n_valid,
                              minibatch_size=batch, seed=seed, **kwargs)
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


def batch_of(wf, trainer, klass, row=0):
    idx = trainer._segment_indices(klass)[row]
    return (wf.loader.original_data.mem[idx],
            wf.loader.original_labels.mem[idx])


# -- the search for the selected keys --------------------------------------

@pytest.mark.parametrize("seq,block,top_k", [
    (16, 8, 6), (16, 8, 8), (16, 8, 16), (16, 8, 40), (19, 8, 5),
    (24, 24, 7), (64, 16, 1)])
@pytest.mark.parametrize("levels", [None, 5, 1])
def test_select_keys_is_top_k_with_the_lower_key_on_a_tie(seq, block,
                                                          top_k, levels):
    """Every query selects exactly ``min(t + 1, top_k)`` of the keys
    before it, the very ones ``lax.top_k`` picks from the row (the
    reference's ``select``), for scores without ties (``levels``
    None), with many (5 values, zeros of both signs among them) and
    with nothing else (one value)."""
    rng = numpy.random.default_rng(seq * 100 + top_k)
    scores = rng.normal(size=(2, seq, seq)).astype(numpy.float32)
    if levels:
        scores = numpy.round(scores * (levels - 1) / 2) / 2
        scores = numpy.where(rng.random(scores.shape) < 0.5, scores,
                             -scores * (scores == 0))
    scores = jnp.asarray(scores, jnp.float32)
    for start in range(0, seq, block):
        stop = min(start + block, seq)
        got = sequence.select_keys(scores[:, start:stop, :stop], start,
                                   top_k)
        want = ref.select(scores[:, start:stop], start, top_k)
        numpy.testing.assert_array_equal(got, want[..., :stop])
        assert not numpy.asarray(want[..., stop:]).any()
        numpy.testing.assert_array_equal(
            numpy.asarray(got).sum(-1),
            numpy.broadcast_to(numpy.minimum(
                numpy.arange(start, stop) + 1, top_k), got.shape[:2]))


def test_selected_pairs_at_the_published_shape():
    assert sequence.selected_pairs(8192, 2048) == 14681088
    assert sequence.selected_pairs(8192, 8192) == 8192 * 8193 // 2 \
        == 33558528
    assert sequence.selected_pairs(16, 40) == 16 * 17 // 2


# -- the unit against the reference -----------------------------------------

def unit_and_reference(top_k, heads=4, kv_heads=2, seed=0, tie=None,
                       block=TINY["block"]):
    """A unit with an index that selects ``top_k`` keys, its
    descriptor, random parameters (no gain at one, no bias at zero) and
    a state. ``tie``: ``"all"`` zeroes the index key's norm, so that
    every index score is a zero of either sign and the selection is
    all ties; ``"some"`` makes the state of the positions 3..6 one
    vector and drops the index's rotary embedding's effect on them by
    zeroing the index queries' first head... no: see the test."""
    descr = dict(type="grouped_attention", heads=heads, kv_heads=kv_heads,
                 head_dim=8, window=None, gated=False, qk_norm=True,
                 eps=1e-6, block=block, rope_theta=1e7,
                 rotary_fraction=1.0, yarn=None,
                 index={"heads": 3, "head_dim": 4, "top_k": top_k})
    fwd = GroupedAttentionForward(DummyLauncher(), name="unit", **{
        k: v for k, v in descr.items() if k != "type"})
    x = random_state(seed)
    rng = numpy.random.default_rng(seed + 1)
    params = {}
    for name, (shape, kind) in fwd.param_shapes(x.shape).items():
        value = rng.normal(size=shape) / (
            math.sqrt(shape[0]) if kind == "matrix" else 2.0)
        params[name] = jnp.asarray(value + (kind == "gain"), jnp.float32)
    if tie == "all":
        params["index_norm_gain"] = jnp.zeros_like(
            params["index_norm_gain"])
        params["index_norm_bias"] = jnp.zeros_like(
            params["index_norm_bias"])
    return fwd, descr, params, x


def unit_objective(fwd, params, x, cotangent, train=True):
    """``(sum(y * cotangent) + L_I, (y, L_I, stats))`` through the
    unit's ``apply_step``, as a fused step calls it."""
    ctx = StepContext([fwd], [params], None, train)
    y, stats = fwd.apply_step(params, x, ctx)
    term = stats.get(fwd.OBJECTIVE_STAT)
    total = jnp.sum(y * cotangent)
    return (total if term is None else total + term), (y, term, stats)


def reference_objective(descr, params, x, cotangent):
    y, loss, mask = ref.selected_attention(descr, params, x)
    return jnp.sum(y * cotangent) + loss, (y, loss, mask)


@pytest.mark.parametrize("top_k", [5, 6, 8, 16, 40])
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (4, 4), (3, 1)])
@pytest.mark.parametrize("tie", [None, "all"])
def test_selected_attention_matches_reference(top_k, heads, kv_heads, tie):
    """Output, ``L_I``, the keys selected and the gradient to every
    parameter and to the input, for a selection under a block, at the
    block, over it, at the sequence's length and over it; with all the
    index scores tied (zeros of both signs: the first ``top_k`` keys
    win)."""
    fwd, descr, params, x = unit_and_reference(top_k, heads, kv_heads,
                                               tie=tie)
    cot = random_state(7)
    (total, (y, loss, stats)), grads = jax.jit(jax.value_and_grad(
        lambda p, v: unit_objective(fwd, p, v, cot), (0, 1),
        has_aux=True))(params, x)
    (r_total, (r_y, r_loss, mask)), r_grads = jax.jit(jax.value_and_grad(
        lambda p, v: reference_objective(descr, p, v, cot), (0, 1),
        has_aux=True))(params, x)
    numpy.testing.assert_allclose(y, r_y, rtol=2e-5, atol=2e-6)
    numpy.testing.assert_allclose(loss, r_loss, rtol=2e-5, atol=1e-7)
    numpy.testing.assert_allclose(stats["index_loss"], r_loss, rtol=2e-5,
                                  atol=1e-7)
    numpy.testing.assert_array_equal(stats["selected"], mask.sum(-1))
    numpy.testing.assert_array_equal(stats["selected_places"],
                                     ref.places(mask))
    numpy.testing.assert_array_equal(
        jax.jit(fwd.selection)(params, x), mask)
    seq = x.shape[1]
    numpy.testing.assert_array_equal(
        stats["selected"][0], numpy.minimum(numpy.arange(seq) + 1, top_k))
    if tie == "all":
        # all ties: the lower key wins, so the first top_k keys
        numpy.testing.assert_array_equal(
            mask[0], numpy.tril(numpy.ones((seq, seq), bool))
            & (numpy.arange(seq) < top_k)[None])
    for name in params:
        scale = float(jnp.abs(r_grads[0][name]).max())
        if tie == "all" and name in INDEX:
            # a relu at 0 passes nothing on either side
            continue
        assert scale > 0, name
        numpy.testing.assert_allclose(
            grads[0][name], r_grads[0][name], rtol=2e-3,
            atol=2e-4 * scale, err_msg=name)
    numpy.testing.assert_allclose(grads[1], r_grads[1], rtol=2e-3,
                                  atol=2e-5)


def test_each_objective_moves_its_own_parameters_and_no_others():
    """``L_I`` moves the index's five arrays and nothing else, the
    input included; what the model's loss sees of the unit moves none
    of the five: exact zeros, both ways."""
    fwd, descr, params, x = unit_and_reference(6)
    cot = random_state(7)
    of_index = jax.jit(jax.grad(lambda p, v: unit_objective(
        fwd, p, v, cot)[1][1], (0, 1)))(params, x)
    of_model = jax.jit(jax.grad(lambda p, v: jnp.sum(unit_objective(
        fwd, p, v, cot)[1][0] * cot), (0, 1)))(params, x)
    for name in params:
        mine, other = (of_index, of_model) if name in INDEX \
            else (of_model, of_index)
        assert numpy.asarray(mine[0][name]).any(), name
        assert not numpy.asarray(other[0][name]).any(), name
    assert not numpy.asarray(of_index[1]).any()
    assert numpy.asarray(of_model[1]).any()


def test_a_forward_only_pass_selects_the_same_and_hands_no_term():
    fwd, descr, params, x = unit_and_reference(6)
    cot = random_state(7)
    _, (y, term, stats) = jax.jit(lambda p, v: unit_objective(
        fwd, p, v, cot, train=False))(params, x)
    _, (t_y, t_term, t_stats) = jax.jit(lambda p, v: unit_objective(
        fwd, p, v, cot))(params, x)
    assert term is None and "index_loss" not in stats
    assert t_term is not None
    numpy.testing.assert_array_equal(y, t_y)
    numpy.testing.assert_array_equal(stats["selected"],
                                     t_stats["selected"])
    numpy.testing.assert_array_equal(jax.jit(fwd.apply)(params, x), y)


def test_an_index_needs_a_block_and_takes_no_window():
    with pytest.raises(ValueError, match="block"):
        GroupedAttentionForward(
            DummyLauncher(), heads=2, head_dim=8, block=None,
            index={"heads": 1, "head_dim": 4, "top_k": 2})
    q = jnp.zeros((1, 2, 8, 8))
    with pytest.raises(ValueError, match="window"):
        sequence.causal_attention(
            q, q, q, 1.0, 4, window=3, top_k=2,
            index=(jnp.zeros((1, 1, 8, 4)), jnp.zeros((1, 8, 4)),
                   jnp.zeros((1, 1, 8))))


# -- the blocks path against the oracle with the whole mask -----------------

def masked_attention(q, k, v, a, b, c, scale, top_k):
    """The oracle: the whole square of index scores and of scores, the
    mask from ``lax.top_k`` a row, repeated heads, one softmax."""
    group = q.shape[1] // k.shape[1]
    index = sequence.index_scores(a, b, c)
    mask = ref.select(index, 0, top_k)
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
    share = jax.lax.stop_gradient(jnp.mean(p, 1))
    log_share = jax.nn.log_softmax(jnp.where(mask, index, -jnp.inf), -1)
    loss = jnp.sum(jnp.where(mask & (share > 0), share * (
        jnp.log(jnp.where(share > 0, share, 1.0)) - log_share), 0.0),
        (1, 2))
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), loss


@pytest.mark.parametrize("core,heads,kv_heads,seq,block,top_k", [
    ("blockwise", 4, 2, 32, 8, 5), ("blockwise", 4, 1, 32, 8, 16),
    ("blockwise", 2, 2, 19, 8, 6), ("blockwise", 6, 3, 24, 24, 9),
    ("blockwise", 4, 2, 32, 16, 64),
    # the selected kernels, interpreted: a group of 8 and of 1, the
    # first blocks ending within top_k and the later past it; all the
    # index scores tied; without the index's objective (XLA's forward,
    # the backward kernel without the summed probabilities)
    ("kernels", 8, 1, 384, 128, 200), ("kernels", 2, 2, 384, 128, 200),
    ("kernels-tied", 4, 2, 256, 128, 100),
    ("kernels-no-loss", 4, 2, 384, 128, 300)])
def test_selected_blocks_match_the_whole_mask(core, heads, kv_heads, seq,
                                              block, top_k):
    """``selected_attention`` in blocks (a sequence that is no whole
    blocks and one block among them) against the oracle: output, the
    index's objective, the keys counted, and all six gradients. The
    kernels (``ops/select_attention.py``) against XLA's blocks too, to
    the same limits: ``out``, ``loss``, ``counts``, ``places`` and the
    six gradients."""
    rng = numpy.random.default_rng(seq + top_k)
    kernels = core.startswith("kernels")
    with_loss = core != "kernels-no-loss"
    dim = 128 if kernels else 8

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q = normal(2, heads, seq, dim)
    k, v = normal(2, kv_heads, seq, dim), normal(2, kv_heads, seq, dim)
    a, b, c = normal(2, 3, seq, 4), normal(2, seq, 4), normal(2, 3, seq)
    if core == "kernels-tied":
        a = jnp.zeros_like(a)
    cot = normal(2, heads, seq, dim)
    scale = 1.0 / math.sqrt(dim)

    def blocks(*operands, kernels=False):
        out, loss, counts, places = sequence.selected_attention(
            *operands, scale, block, top_k, with_loss, kernels)
        return jnp.sum(out * cot) + jnp.sum(loss), (out, loss, counts,
                                                    places)

    def oracle(*operands):
        out, loss = masked_attention(*operands, scale, top_k)
        return jnp.sum(out * cot) + jnp.sum(loss), (out, loss)

    (_, (out, loss, counts, places)), grads = jax.jit(jax.value_and_grad(
        blocks, tuple(range(6)), has_aux=True))(q, k, v, a, b, c)
    (_, (o_out, o_loss)), o_grads = jax.jit(jax.value_and_grad(
        oracle, tuple(range(6)), has_aux=True))(q, k, v, a, b, c)
    mask = ref.select(sequence.index_scores(a, b, c), 0, top_k)
    compared = [(out, o_out, loss, o_loss, grads, o_grads)]
    if kernels:
        with pltpu.force_tpu_interpret_mode():
            (_, (k_out, k_loss, k_counts, k_places)), k_grads = jax.jit(
                jax.value_and_grad(functools.partial(blocks, kernels=True),
                                   tuple(range(6)), has_aux=True))(
                q, k, v, a, b, c)
        numpy.testing.assert_array_equal(k_counts, counts)
        numpy.testing.assert_array_equal(k_places, places)
        compared.append((k_out, out, k_loss, loss, k_grads, grads))
    for got_out, want_out, got_loss, want_loss, got, want in compared:
        numpy.testing.assert_allclose(got_out, want_out, rtol=2e-5,
                                      atol=2e-6)
        if with_loss:
            numpy.testing.assert_allclose(got_loss, want_loss, rtol=2e-5)
        for g, w, name in zip(got[:3 if not with_loss else 6], want,
                              "qkvabc"):
            numpy.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5,
                                          err_msg=name)
    if not with_loss:
        assert not numpy.asarray(loss).any()
        for g in grads[3:] + (k_grads[3:] if kernels else ()):
            assert not numpy.asarray(g).any()
    numpy.testing.assert_array_equal(
        counts[0], numpy.minimum(numpy.arange(seq) + 1, top_k))
    numpy.testing.assert_array_equal(
        jax.jit(sequence.selection_mask, static_argnums=(3, 4))(
            a, b, c, block, top_k), mask)
    # WHICH keys, as far as a step says without a mask
    numpy.testing.assert_array_equal(places, ref.places(mask))


def test_causal_attention_takes_the_selected_core_by_its_operands(
        monkeypatch):
    """Given an index's operands the chooser takes the selected core
    and says so in the gauges: XLA's blocks off a TPU, the kernels on
    one (interpreted here), which give what XLA's blocks give; without
    the objective (a validation forward) XLA's blocks' output on
    either, and a loss of 0."""
    rng = numpy.random.default_rng(2)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, k, v = normal(1, 4, 256, 128), normal(1, 2, 256, 128), \
        normal(1, 2, 256, 128)
    index = normal(1, 3, 256, 4), normal(1, 256, 4), normal(1, 3, 256)
    want = jax.jit(lambda *o: sequence.selected_attention(
        *o, 0.1, 128, 100, True))(q, k, v, *index)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        with pltpu.force_tpu_interpret_mode():
            got = jax.jit(lambda *o: sequence.causal_attention(
                *o[:3], 0.1, 128, unit="sel", index=o[3:], top_k=100))(
                    q, k, v, *index)
        fused = backend == "tpu"
        for g, w in zip(got, want):
            if fused and g.dtype == jnp.float32:
                numpy.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
            else:
                numpy.testing.assert_array_equal(g, w)
        assert gauge("veles_attention_core_fused")["sel"] == float(fused)
        assert gauge("veles_attention_backward_kernels")["sel"] == \
            float(fused)
        assert gauge("veles_attention_index_topk")["sel"] == 100
        assert gauge("veles_attention_selected_pairs")["sel"] == \
            100 * 101 // 2 + 156 * 100
        assert gauge("veles_attention_kv_group")["sel"] == 2
        # what the lowering runs: the causal triangle's block pairs
        assert {labels["pass"]: child.value for labels, child in
                get_registry().get("veles_attention_core_blocks").series()
                if labels["unit"] == "sel"} == {"forward": 3,
                                                "backward": 3}
        with pltpu.force_tpu_interpret_mode():
            no_loss = jax.jit(lambda *o: sequence.causal_attention(
                *o[:3], 0.1, 128, unit="sel", index=o[3:], top_k=100,
                index_loss=False))(q, k, v, *index)
        # a forward without the objective is XLA's blocks' everywhere
        numpy.testing.assert_array_equal(no_loss[0], want[0])
        assert not numpy.asarray(no_loss[1]).any()


def test_the_selected_kernels_refuse_what_the_vmem_cannot_hold():
    """The rule is :func:`fused_refusal`'s shapes, then each selected
    kernel's VMEM: the cell's 8,192 positions of 32 heads on 4 fit; a
    sequence whose backward scratch is over the VMEM, a head of 64
    and a ragged block do not."""
    def shapes(heads, kv_heads, seq, dim=128):
        return [jax.ShapeDtypeStruct((1, n, seq, dim), jnp.bfloat16)
                for n in (heads, kv_heads, kv_heads)]
    assert sequence.selected_refusal(*shapes(32, 4, 8192), 512) is None
    assert "VMEM" in sequence.selected_refusal(*shapes(32, 4, 65536), 512)
    assert "head size" in sequence.selected_refusal(
        *shapes(32, 4, 8192, 64), 512)
    assert "whole blocks" in sequence.selected_refusal(
        *shapes(32, 4, 8000), 512)


# -- the whole model through the trainer -----------------------------------

def test_published_layers_are_what_the_preset_describes():
    """48 blocks of one kind at the published sizes; the tiny preset
    has every mechanism."""
    chain = layers(**dict(PUBLISHED, positions=8192))
    attention = [d for d in chain if d["type"] == "grouped_attention"]
    sparse = [d for d in chain if d["type"] == "moe"]
    assert len(attention) == len(sparse) == 48
    assert all(d == attention[0] for d in attention)
    assert (attention[0]["heads"], attention[0]["kv_heads"],
            attention[0]["head_dim"], attention[0]["rope_theta"]) == (
        32, 4, 128, 1e7)
    assert attention[0]["index"] == {"heads": 16, "head_dim": 64,
                                     "top_k": 2048}
    assert attention[0]["qk_norm"] and not attention[0]["gated"]
    assert (sparse[0]["n_experts"], sparse[0]["top_k"],
            sparse[0]["hidden"], sparse[0]["shared_experts"],
            sparse[0]["scoring"], sparse[0]["normalize"]) == (
        128, 8, 768, 0, "softmax", True)
    assert TINY["index_top_k"] < TINY["positions"] and \
        TINY["index_top_k"] % TINY["block"]
    assert TINY["top_k"] > 1 and TINY["experts_held"][1] < \
        TINY["n_experts"] and TINY["kv_heads"] < TINY["heads"]


def index_terms(stats):
    """``{unit tag: L_I}`` of a step's stats."""
    return {tag: unit["index_loss"] for tag, unit in stats.items()
            if "index_loss" in unit}


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


def test_objective_and_every_gradient_match_reference(model):
    """The objective is the model's loss plus every layer's ``L_I``;
    ``extras`` reports each term under its unit's tag; every gradient
    is the reference's; the stats carry the keys selected."""
    wf, descr, trainer, host = model
    tokens, labels = batch_of(wf, trainer, TRAIN)
    params, _ = trainer.pull_params()
    valid = jnp.ones(len(tokens), bool)

    def objective(p):
        total, (report, _, extras) = trainer._token_objective(
            p, jnp.asarray(tokens), jnp.asarray(labels), None, valid,
            True)
        return total, (report, extras)

    (total, (report, extras)), grads = jax.jit(jax.value_and_grad(
        objective, has_aux=True))(params)
    r_total, terms = jax.jit(lambda p: ref.objective(
        descr, p, tokens, labels))(host)
    numpy.testing.assert_allclose(total, r_total, rtol=1e-5)
    numpy.testing.assert_allclose(report, terms["main"], rtol=1e-5)
    index_losses = index_terms(extras["stats"])
    assert sorted(index_losses) == [
        "u01.grouped_attention1", "u03.grouped_attention3"]
    for i, tag in enumerate(sorted(index_losses)):
        numpy.testing.assert_allclose(index_losses[tag],
                                      terms["index%d" % i], rtol=1e-5)
        assert float(index_losses[tag]) > 0
        numpy.testing.assert_array_equal(
            extras["stats"][tag]["selected"],
            numpy.broadcast_to(numpy.minimum(
                numpy.arange(TINY["positions"]) + 1,
                TINY["index_top_k"]), (4, TINY["positions"])))
    numpy.testing.assert_allclose(
        total, report + sum(index_losses.values()), rtol=1e-6)
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


def test_padded_rows_stay_out_of_the_index_term(model):
    """``L_I`` is a mean over the whole padded batch of the valid
    rows' terms, the scale of the model's loss."""
    wf, descr, trainer, host = model
    tokens, labels = batch_of(wf, trainer, TRAIN)
    params, _ = trainer.pull_params()

    @jax.jit
    def terms(valid):
        return index_terms(trainer._token_objective(
            params, jnp.asarray(tokens), jnp.asarray(labels), None,
            valid, True)[1][2]["stats"])

    whole = terms(jnp.asarray([True] * 4))
    half = terms(jnp.asarray([True, True, False, False]))
    _, first = jax.jit(lambda p: ref.objective(
        descr, p, tokens[:2], labels[:2]))(host)
    for i, tag in enumerate(sorted(whole)):
        assert float(half[tag]) < float(whole[tag])
        numpy.testing.assert_allclose(half[tag],
                                      first["index%d" % i] / 2, rtol=1e-5)


def test_two_adam_steps_and_the_published_gauges():
    """Two steps of the train segment against Adam written out here on
    the reference's gradients (the index's arrays among them: they
    move by ``L_I`` alone); the step's terms leave the scan, and the
    trainer publishes ``L_I`` and the pairs really selected."""
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
    observed = trainer.last_step_stats
    for step in range(2):
        tokens, targets = data[idx[step]], labels[idx[step]]
        _, terms = ref.objective(descr, host, tokens, targets)
        numpy.testing.assert_allclose(losses[step], terms["main"],
                                      rtol=2e-5)
        for i, tag in enumerate(sorted(index_terms(observed["stats"]))):
            numpy.testing.assert_allclose(
                observed["stats"][tag]["index_loss"][step],
                terms["index%d" % i], rtol=2e-4)
        grads = ref.gradients(descr, host, tokens, targets)
        t = step + 1
        corr = numpy.sqrt(1 - hp["b2"] ** t) / (1 - hp["b1"] ** t)
        for i, layer in enumerate(host):
            for k in layer:
                if k == "select_bias":
                    continue
                g = numpy.asarray(grads[i][k])
                m[i][k] = hp["b1"] * m[i][k] + (1 - hp["b1"]) * g
                v[i][k] = hp["b2"] * v[i][k] + (1 - hp["b2"]) * g * g
                layer[k] = layer[k] - hp["lr"] * corr * m[i][k] / (
                    numpy.sqrt(v[i][k]) + hp["eps"])
    for i, (fwd, layer) in enumerate(zip(wf.forwards, host)):
        for k in layer:
            # Adam's first steps move every weight by ~lr whatever its
            # gradient: compare the MOVE, to a twentieth of a step
            numpy.testing.assert_allclose(
                new_params[i][k], layer[k], rtol=0,
                atol=0 if k == "select_bias" else 0.05 * hp["lr"] * 2,
                err_msg="%s.%s" % (fwd.name, k))
    trainer.publish_step_stats(new_params)
    pairs = sequence.selected_pairs(TINY["positions"],
                                    TINY["index_top_k"])
    for tag, term in index_terms(observed["stats"]).items():
        numpy.testing.assert_allclose(
            gauge("veles_index_loss")[tag], float(jnp.mean(term)),
            rtol=1e-6)
        assert gauge("veles_attention_selected_per_step")[tag] == pairs
    assert gauge("veles_attention_selected_pairs")[
        "grouped_attention1"] == pairs


def keeping(fn):
    return lambda *args: remat.checkpoint(fn)(*args)[0]


@pytest.mark.parametrize("what", ["updates", "attention"])
def test_remat_changes_nothing(what):
    """``updates``: ``remat`` on every block's units gives the same
    losses and updates to rounding. ``attention``: with rematerialized
    attention alone, the objective, the terms and EVERY gradient are
    the chain's without ``remat`` to the bit. The gauge reads what a
    unit kept: the core's output, a float32 statistic a row of every
    query head and the searched blocks' masks, a byte a pair."""
    plain_wf, _ = build()
    remat_wf, _ = build(sizes=dict(remat=True))
    if what == "attention":
        for fwd in remat_wf.forwards:
            fwd.remat = type(fwd).__name__ == ATTENTION
    outs = []
    for wf in (plain_wf, remat_wf):
        trainer = FusedTrainer(wf)
        params, states = trainer.pull_params()
        if what == "updates":
            outs.append(trainer.train_class(params, states))
        else:
            tokens, labels = batch_of(wf, trainer, TRAIN)
            outs.append(jax.jit(jax.value_and_grad(
                lambda p: trainer._token_objective(
                    p, jnp.asarray(tokens), jnp.asarray(labels), None,
                    jnp.ones(len(tokens), bool), True)[0]))(params))
        kept = gauge("veles_remat_kept_bytes")
        # blocks of 8 queries, both ending past top_k 6 and so both
        # searched: 8 x 8 and 8 x 16 pairs a sequence
        assert {fwd.name: kept[fwd.name] for fwd in wf.forwards[:-1]} == {
            fwd.name: 4 * (fwd.heads * TINY["positions"]
                           * (TINY["head_dim"] * 4 + 4) + 8 * 8 + 8 * 16)
            if fwd.remat and type(fwd).__name__ == ATTENTION else 0
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


def test_rematerialized_unit_runs_its_core_and_its_search_once():
    """What the selected core keeps is its output, its row statistics
    and the searched masks: the rematerialized unit's gradient makes
    the six projections (q, k, v and the index's three) a second time
    and nothing else: no product of the core or of the index scores,
    and the search (its tie-break's running count) once a searched
    block, as without ``remat``."""
    wf, _ = build()
    fwd = wf.forwards[1]
    params = {k: jnp.asarray(a.map_read())
              for k, a in fwd.param_arrays().items()}
    x = random_state(5, batch=4)

    def count(wrap):
        def loss(p, v):
            ctx = StepContext([fwd], [p], None, True)

            def fn(p, v):
                y, stats = fwd.apply_step(p, v, ctx)
                return y, stats[fwd.OBJECTIVE_STAT]
            y, term = wrap(fn)(p, v)
            return jnp.sum(y) + term
        text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x))
        return text.count(" cumsum["), text.count("dot_general[")
    plain, rematted = count(lambda fn: fn), count(keeping)
    # both of the tiny preset's blocks end past its top_k
    assert plain[0] == rematted[0] == 2
    assert rematted[1] - plain[1] == 6


def test_snapshot_and_resume():
    """A run of one epoch, dumped, loaded and run for a second gives
    what two epochs in one process give; the unit's descriptor keys
    (the index's, the q/k norm) come back with it."""
    from veles_tpu.snapshotter import dump_workflow, load_workflow

    def run(wf, epochs):
        trainer = FusedTrainer(wf)
        trainer.train(max_epochs=epochs)
        return trainer

    whole, _ = build(max_epochs=2)
    run(whole, 2)
    first, _ = build(max_epochs=2)
    run(first, 1)
    resumed = load_workflow(dump_workflow(first))
    resumed.workflow = DummyLauncher()
    resumed.initialize(device=Device(backend="cpu"))
    for a, b in zip(first.forwards, resumed.forwards):
        if type(a).__name__ == ATTENTION:
            assert (a.index, a.qk_norm, a.heads, a.kv_heads, a.gated,
                    a.PARAMS) == (
                b.index, b.qk_norm, b.heads, b.kv_heads, b.gated,
                b.PARAMS)
    run(resumed, 2)
    assert [h["epoch"] for h in resumed.decision.epoch_history] == [0, 1]
    for a, b in zip(whole.forwards, resumed.forwards):
        for name, arr in a.param_arrays().items():
            numpy.testing.assert_allclose(
                b.param_arrays()[name].map_read(), arr.map_read(),
                rtol=1e-4, atol=1e-6, err_msg="%s.%s" % (a.name, name))


def test_cli_trains_the_tiny_preset(tmp_path):
    """Launcher -> FusedRunner reaches the model."""
    import json

    from veles_tpu.__main__ import main
    result_file = str(tmp_path / "results.json")
    code = main(["veles_tpu/models/indexed_moe_lm.py", "-s", "5",
                 "root.indexed_moe_lm.max_epochs=2",
                 "--result-file", result_file])
    assert code == 0
    assert json.load(open(result_file))


def test_the_index_key_norm_starts_as_the_identity():
    """``gamma`` 1, ``beta`` 0; the q/k gains 1; the index's matrices
    filled as the others."""
    wf, _ = build()
    arrays = wf.forwards[1].param_arrays()
    assert set(arrays) == {"norm", "q", "k", "v", "o", "q_norm",
                           "k_norm"} | set(INDEX)
    for name in ("q_norm", "k_norm", "index_norm_gain"):
        assert (arrays[name].map_read() == 1).all()
    assert (arrays["index_norm_bias"].map_read() == 0).all()
    for name in ("index_q", "index_k", "index_w"):
        assert 0.5 * 0.02 < arrays[name].map_read().std() < 2 * 0.02


# -- the other families' programs are untouched ------------------------------

#: sha256 of the tiny presets' train and validation segments as
#: StableHLO (``jitted.lower(...).as_text()``, which carries no debug
#: info) at the parent commit of PR 33 (``31b8db1``) under jax 0.9.0
#: and this suite's ``conftest.py`` (the same test run in a checkout
#: of that commit), every block's unit rematerialized: a grouped-attention unit with
#: ``index=None, qk_norm=False`` and a step without terms trace to
#: what they traced to before either existed. After a change that
#: means to move these programs, print the new ones by running this
#: test and pin them again.
PARENT_PROGRAMS = {
    ("window_moe_lm", "WindowMoELMWorkflow"): (
        "05655b5122b331656c33d17b6fdda314f203e488f5f0116c3b208657dfa63cbe",
        "cfa5ab17026c34f418b0853e62d07123f008e44422e8fa5d98d1dd713e8e4b2a"),
    ("latent_moe_lm", "LatentMoELMWorkflow"): (
        "13133eda9b325eddb336319dc520c7424365fd4016a6d1b82bc5fc753edfbae5",
        "c2c8f3b9ac9298200d7fca68e5539dac18f77a8341ff7e46a37f240df23ff72b"),
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

def test_top_8_of_128_routes_as_the_reference():
    """The published router, 128 softmax outputs and 8 a token,
    renormalised, scale 1, no shared expert, experts 0..15 held: the
    unit's result and its counts are the reference's."""
    from veles_tpu.nn.moe import MoEForward
    descr = dict(type="moe", n_experts=128, hidden=16,
                 capacity_factor=None, top_k=8, scoring="softmax",
                 normalize=True, scale=1.0, shared_experts=0,
                 experts_held=[0, 16], bias_rate=0.0, dispatch_rows=64,
                 eps=1e-6)
    fwd = MoEForward(DummyLauncher(), name="wide", **{
        k: v for k, v in descr.items() if k != "type"})
    rng = numpy.random.default_rng(11)
    dim = TINY["dim"]

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                           jnp.float32)

    params = {"weights": mat(dim, 128), "norm": jnp.ones(dim),
              "select_bias": jnp.zeros(128), "gate": mat(16, dim, 16),
              "up": mat(16, dim, 16), "down": mat(16, 16, dim)}
    x = random_state(9, batch=4)
    numpy.testing.assert_allclose(fwd.apply(params, x),
                                  ref.moe(descr, params, x), rtol=2e-5,
                                  atol=2e-6)
    counts = ref.expert_counts(descr, params, x)
    assert int(counts.sum()) == 64 * 8
    # the bound of 64 rows is passed by some routing: the exact
    # overflow path gives the same layer
    assert int(counts[:16].sum()) > 64


def test_the_shares_add_up():
    """128 experts over 8 shares of 16, the deployment's cut at a
    small width: the eight partial routed results plus the residual
    once equal the uncut reference layer; there is no shared expert to
    count once."""
    sizes = dict(n_experts=128, top_k=8)
    whole = layers(**dict(TINY, **sizes, experts_held=None))
    index = [i for i, d in enumerate(whole) if d["type"] == "moe"][0]
    assert whole[index]["shared_experts"] == 0
    rng = numpy.random.default_rng(7)
    dim, hidden = TINY["dim"], TINY["expert_hidden"]

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                           jnp.float32)

    full = {"weights": mat(dim, 128), "norm": jnp.ones(dim),
            "select_bias": jnp.zeros(128),
            "gate": mat(128, dim, hidden), "up": mat(128, dim, hidden),
            "down": mat(128, hidden, dim)}
    x = random_state(6)
    expected = ref.moe(whole[index], full, x)
    total = None
    for first in range(0, 128, 16):
        wf, descr = build(sizes=dict(sizes, experts_held=(first, 16)))
        fwd = wf.forwards[index]
        share = dict(full, **{k: full[k][first:first + 16]
                              for k in ("gate", "up", "down")})
        # what every chip computes alike, counted once: the residual
        fwd.residual = first == 0
        part = fwd.apply(share, x)
        numpy.testing.assert_allclose(
            part - (x if first == 0 else 0),
            ref.moe(descr[index], share, x, shared=False), rtol=2e-4,
            atol=2e-5)
        total = part if total is None else total + part
    numpy.testing.assert_allclose(total, expected, rtol=2e-5, atol=2e-5)


def test_the_benchmarks_configuration_is_the_published_layers_cut():
    """``benchmark/configs/keye-vl2-ep8share.json`` holds
    ``layers(**PUBLISHED)`` cut as the file itself says: the blocks it
    holds, experts 0..15, 18,992 vocabulary rows, one sequence of
    8,192; its parameter count by the units' own shapes; and no width
    differs from the published keys."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "keye-vl2-ep8share.json")
    with open(path) as f:
        config = json.load(f)
    positions = config["layers"][0]["positions"]
    expected = layers(**dict(
        PUBLISHED, blocks=config["num_hidden_layers"],
        vocabulary=config["vocab_size"], positions=positions,
        experts_held=(0, config["num_experts"]), dispatch_rows=16384,
        remat=True))
    assert json.loads(json.dumps(expected)) == config["layers"]
    assert (config["num_experts"], config["vocab_size"], positions) == (
        16, 18992, 8192)
    assert 4 <= config["num_hidden_layers"] <= 6
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_local_experts"],
            config["num_experts_per_tok"], config["rope_theta"]) == (
        PUBLISHED["dim"], PUBLISHED["heads"], PUBLISHED["kv_heads"],
        PUBLISHED["head_dim"], PUBLISHED["expert_hidden"],
        PUBLISHED["n_experts"], PUBLISHED["top_k"],
        PUBLISHED["rope_theta"])
    sa = config["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["indexer_num_kv_heads"], sa["topk"]) == (
        PUBLISHED["index_heads"], PUBLISHED["index_head_dim"], 1,
        PUBLISHED["index_top_k"])
    total, shape = 0, (config["batch"], positions, PUBLISHED["dim"])
    for descr in expected:
        if descr["type"] == "grouped_attention":
            fwd = GroupedAttentionForward(DummyLauncher(), **{
                k: v for k, v in descr.items()
                if k not in ("type", "remat")})
            total += sum(math.prod(s) for s, _ in
                         fwd.param_shapes(shape).values())
        elif descr["type"] == "moe":
            total += shape[-1] * (descr["n_experts"] + 1) \
                + descr["n_experts"] + descr["experts_held"][1] * 3 \
                * shape[-1] * descr["hidden"]
        elif descr["type"] == "rms_norm":
            total += shape[-1]
        else:  # the embedding and the head
            total += descr["vocabulary"] * shape[-1]
    assert total == config["held_here"]["parameters"]
    per_layer = config["held_here"]["parameters_a_layer"]
    assert (per_layer["attention"], per_layer["index"],
            per_layer["router_norm_and_16_experts"]) == (
        18876672, 2261120, 75761792)
