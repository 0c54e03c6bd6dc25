"""The selected attention core (``ops/select_attention.py``, reached
through ``parallel/sequence.py`` ``selected_attention``): the search
for each block's keys against ``lax.top_k``, the pairs selected, an
indexed grouped-attention unit against the reference, the blocks path
and the kernels (interpreted) against the whole mask, which core
``causal_attention`` takes for an index's operands, and what the
kernels refuse."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import indexed_moe_lm as ref
from token_models import (float32_highest, gauge,  # noqa: F401 (fixture)
                          random_state)
from veles_tpu.dummy import DummyLauncher
from veles_tpu.nn.attention import GroupedAttentionForward
from veles_tpu.parallel import sequence
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train.step import StepContext

INDEX = GroupedAttentionForward.INDEX


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


# -- an indexed unit against the reference ------------------------------------

def unit_and_reference(top_k, heads=4, kv_heads=2, seed=0, tie=None,
                       block=8):
    """A unit with an index that selects ``top_k`` keys, its
    descriptor, random parameters (no gain at one, no bias at zero) and
    a state. ``tie="all"`` zeroes the index key's norm, so that every
    index score is a zero of either sign and the selection is all
    ties."""
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
