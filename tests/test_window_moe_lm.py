"""The window/full grouped-query attention, sparse-expert language
model (``models/window_moe_lm.py``) against its plain float32
reference ``benchmark/reference/window_moe_lm.py``: the checks every
token-model family runs (``token_models.py``), and the family's own
units: the grouped attention unit, the rotary tables, the router at
the published width. The banded core is ``test_band_attention.py``'s."""

import math

import jax.numpy as jnp
import numpy
import pytest

from benchmark.reference import window_moe_lm as ref
from token_models import (  # noqa: F401 (the fixtures)
    Family, TokenModelChecks, float32_highest, model, random_state)
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.window_moe_lm import (PUBLISHED, TINY,
                                            WindowMoELMWorkflow)
from veles_tpu.nn.attention import (GroupedAttentionForward, rotary,
                                    rotary_frequencies)

#: the published per-layer head counts: 48 on full layers, 72 on sliding
PUBLISHED_HEADS = [48 if i % 4 == 0 else 72 for i in range(48)]


def published_facts(chain, tiny):
    """Both layer kinds in the published ratio, their head counts and
    rotary tables; the tiny preset has both kinds."""
    attention = [d for d in chain if d["type"] == "grouped_attention"]
    assert [d["heads"] for d in attention] == PUBLISHED_HEADS
    assert [d["window"] for d in attention[:5]] == [
        None, 512, 512, 512, None]
    assert attention[0]["yarn"]["factor"] == 128 and \
        attention[0]["rotary_fraction"] == 0.5
    assert attention[1]["yarn"] is None and \
        attention[1]["rope_theta"] == 1e4
    assert [d["type"] for d in chain[2:5:2]] == ["gated_mlp", "moe"]
    assert {(d["heads"], d["window"]) for d in tiny
            if d["type"] == "grouped_attention"} == {(4, None), (6, 5)}


def configuration_facts(config, chain, parameters):
    """Five blocks, experts 0..7, 12,544 vocabulary rows; the heads a
    block as the published list has them."""
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 12544)
    assert [d["heads"] for d in chain
            if d["type"] == "grouped_attention"] == \
        config["num_attention_heads_per_layer"] == PUBLISHED_HEADS[:5]


FAMILY = Family(
    workflow=WindowMoELMWorkflow, published=PUBLISHED, reference=ref,
    units=ref.UNITS,
    moe=lambda d, p, x, ctx, routed: ref.moe(d, p, x, shared=not routed),
    shares=(16, 4, 4), scorings=("sigmoid", "softmax"),
    # the core's output and one float32 statistic a row of every QUERY
    # head, and no key or value
    kept=lambda fwd, batch, size, core: batch * fwd.heads
    * size["positions"] * (size["head_dim"] * 4 + 4),
    # sizes the fused kernels' tiling admits, as small as it admits them
    fused_sizes=dict(head_dim=128, kv_heads=1, full_heads=2,
                     window_heads=3, window=100, positions=256, block=128,
                     blocks=2),
    config="laguna-s21-ep32share.json",
    cut=lambda config: dict(
        blocks=config["num_hidden_layers"], vocabulary=config["vocab_size"],
        positions=config["layers"][0]["positions"],
        experts_held=(0, config["num_experts"]),
        dispatch_rows=config["layers"][0]["positions"]),
    parameters=811018240,
    published_facts=published_facts,
    configuration_facts=configuration_facts,
    # taken at ``31b8db1``, pinned since
    programs=(
        "05655b5122b331656c33d17b6fdda314f203e488f5f0116c3b208657dfa63cbe",
        "cfa5ab17026c34f418b0853e62d07123f008e44422e8fa5d98d1dd713e8e4b2a"))


class TestWindow(TokenModelChecks):
    family = FAMILY


# -- the unit against the reference ----------------------------------------

YARN = dict(factor=8.0, original_positions=8, beta_fast=4.0,
            beta_slow=1.0, attention_factor=1.2)


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (6, 2), (3, 1)])
@pytest.mark.parametrize("window", [None, 5, 11, 100])
@pytest.mark.parametrize("fraction,yarn,gated", [
    (1.0, None, True), (0.5, YARN, True), (0.5, None, False),
    (1.0, YARN, False)])
def test_grouped_attention_matches_reference(heads, kv_heads, window,
                                             fraction, yarn, gated):
    """A full and a window layer: grouped heads of a size free of
    dim/heads, a window shorter and longer than the 16 positions and no
    multiple of the block of 8, half a head or all of it rotated from
    a plain or a YaRN table, with and without the gate; the blockwise
    core and the oracle core (``block=None``) both."""
    descr = dict(type="grouped_attention", heads=heads, kv_heads=kv_heads,
                 head_dim=8, window=window, rope_theta=5e5,
                 rotary_fraction=fraction, yarn=yarn, gated=gated,
                 eps=1e-6, block=8)
    fwd = GroupedAttentionForward(DummyLauncher(), name="ga", **{
        k: v for k, v in descr.items() if k != "type"})
    rng = numpy.random.default_rng(heads * 7 + (window or 0))
    shapes = fwd.param_shapes((2, 16, TINY["dim"]))
    assert ("gate" in shapes) == gated
    params = {
        name: jnp.asarray(
            1.0 + 0.1 * rng.normal(size=shape) if kind == "gain"
            else rng.normal(size=shape) / math.sqrt(shape[0]), jnp.float32)
        for name, (shape, kind) in shapes.items()}
    x = random_state(3)
    expected = ref.grouped_attention(descr, params, x)
    numpy.testing.assert_allclose(fwd.apply(params, x), expected,
                                  rtol=2e-5, atol=2e-6)
    fwd.block = None
    numpy.testing.assert_allclose(fwd.apply(params, x), expected,
                                  rtol=2e-5, atol=2e-6)


def test_a_head_count_that_is_no_whole_groups_is_refused():
    with pytest.raises(ValueError, match="whole groups"):
        GroupedAttentionForward(DummyLauncher(), heads=6, kv_heads=4,
                                head_dim=8)


# -- the rotary tables -------------------------------------------------------

def test_yarn_table_at_the_published_keys():
    """The closed form at the full layers' published keys over the 64
    rotated dims of a head of 128: the correction dims, the ramp's
    ends, the first frequency (kept) and the last (divided by the
    factor), the magnitude."""
    keys = PUBLISHED["full_rotary"]
    yarn, theta, dims = keys["yarn"], keys["rope_theta"], 64
    assert dims == PUBLISHED["head_dim"] * keys["rotary_fraction"]

    def correction(turns):
        return dims * math.log(8192 / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low, high = math.floor(correction(32)), math.ceil(correction(1))
    assert (low, high) == (9, 18)
    for table in (rotary_frequencies, ref.rotary_table):
        freqs, magnitude = table(dims, theta, yarn)
        freqs = numpy.asarray(freqs, numpy.float64)
        assert freqs.shape == (32,)
        assert magnitude == 1.4852030263919618
        plain = theta ** (-numpy.arange(32) / 32.0)
        ramp = numpy.clip((numpy.arange(32) - low) / (high - low), 0, 1)
        numpy.testing.assert_allclose(
            freqs, plain / 128 * ramp + plain * (1 - ramp), rtol=1e-5)
        assert freqs[0] == 1.0
        numpy.testing.assert_allclose(
            freqs[-1], theta ** (-31 / 32.0) / 128, rtol=1e-5)
        numpy.testing.assert_allclose(freqs[:low + 1], plain[:low + 1],
                                      rtol=1e-6)
        numpy.testing.assert_allclose(freqs[high:], plain[high:] / 128,
                                      rtol=1e-5)


@pytest.mark.parametrize("fraction,yarn", [
    (1.0, None), (0.5, None), (0.5, YARN), (0.25, YARN)])
def test_rotary_rotates_a_fraction_of_a_head(fraction, yarn):
    """The rotated part against the reference's; the rest passes
    unrotated; the default call is the whole head from a plain table
    (what latent attention asks for)."""
    x = jnp.asarray(numpy.random.default_rng(2).normal(
        size=(2, 12, 3, 16)), jnp.float32)
    got = rotary(x, 1e4, fraction, yarn)
    numpy.testing.assert_allclose(got, ref.rope(x, 1e4, fraction, yarn),
                                  rtol=1e-5, atol=1e-6)
    dims = int(16 * fraction)
    numpy.testing.assert_array_equal(got[..., dims:], x[..., dims:])
    # position 0 turns by no angle: the magnitude alone
    numpy.testing.assert_allclose(
        got[:, 0, :, :dims], x[:, 0, :, :dims] * (
            yarn["attention_factor"] if yarn else 1.0), rtol=1e-6)
    numpy.testing.assert_array_equal(rotary(x, 1e4),
                                     rotary(x, 1e4, 1.0, None))



# -- routing at the published width ------------------------------------------

@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_top_10_of_256_routes_as_the_reference(scoring):
    """The published router, 256 outputs and 10 a token, normalised and
    scaled by 2.5, experts 0..7 held: the unit's result and its counts
    are the reference's under either score function."""
    from veles_tpu.nn.moe import MoEForward
    descr = dict(type="moe", n_experts=256, hidden=16,
                 capacity_factor=None, top_k=10, scoring=scoring,
                 normalize=True, scale=2.5, shared_experts=1,
                 experts_held=[0, 8], bias_rate=0.0, dispatch_rows=64,
                 eps=1e-6)
    fwd = MoEForward(DummyLauncher(), name="wide", **{
        k: v for k, v in descr.items() if k != "type"})
    rng = numpy.random.default_rng(11)
    dim = TINY["dim"]

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                           jnp.float32)

    params = {"weights": mat(dim, 256), "norm": jnp.ones(dim),
              "select_bias": jnp.zeros(256), "gate": mat(8, dim, 16),
              "up": mat(8, dim, 16), "down": mat(8, 16, dim),
              "shared_gate": mat(1, dim, 16), "shared_up": mat(1, dim, 16),
              "shared_down": mat(1, 16, dim)}
    x = random_state(9, batch=4)
    numpy.testing.assert_allclose(fwd.apply(params, x),
                                  ref.moe(descr, params, x), rtol=2e-5,
                                  atol=2e-6)
    chosen, weights = ref.route(descr, params, ref.rms_norm(
        x, params["norm"], 1e-6).reshape(-1, dim))
    assert chosen.shape == (64, 10)
    numpy.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    counts = ref.expert_counts(descr, params, x)
    assert int(counts.sum()) == 64 * 10
    # the bound of 64 rows is passed by some routing: the exact
    # overflow path gives the same layer
    assert int(counts[:8].sum()) != 0

