"""The window/full grouped-query attention, sparse-expert language
model through the normal path (layer descriptors ->
``StandardWorkflow`` -> ``FusedTrainer``) against the plain float32
reference ``benchmark/reference/window_moe_lm.py``, at a tiny size;
and the banded attention core against an explicit mask."""

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import window_moe_lm as ref
from veles_tpu import prng, remat
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.models.window_moe_lm import (PUBLISHED, TINY,
                                            WindowMoELMWorkflow, layers)
from veles_tpu.nn import precision
from veles_tpu.nn.attention import (GroupedAttentionForward, rotary,
                                    rotary_frequencies)
from veles_tpu.parallel import sequence
from veles_tpu.parallel.sequence import (banded_attention,
                                         blockwise_attention,
                                         fused_refusal, local_attention)
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer

ATTENTION = "GroupedAttentionForward"


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
    wf = WindowMoELMWorkflow(DummyLauncher(), sizes=sizes,
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


def test_other_units_match_reference(model):
    wf, descr, _, host = model
    x = random_state()
    for i, (d, fwd) in enumerate(zip(descr, wf.forwards)):
        if d["type"] in ("gated_mlp", "moe", "rms_norm"):
            params = {k: jnp.asarray(v) for k, v in host[i].items()}
            numpy.testing.assert_allclose(
                fwd.apply(params, x), ref.UNITS[d["type"]](d, params, x),
                rtol=2e-5, atol=2e-6, err_msg=fwd.name)


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


# -- the banded core ---------------------------------------------------------

def masked_attention(q, k, v, scale, window):
    """The oracle: repeated heads, the whole square, an explicit
    mask."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    return local_attention(q, k, v, causal=True, scale=scale,
                           window=window)


def core_run(fn, q, k, v):
    """Output and the three gradients of ``sum(sin(fn(q, k, v)))``."""
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("core,dtype,heads,seq,block,window", [
    ("blockwise", "float32", (4, 2), 24, 8, 5),
    ("blockwise", "float32", (6, 2), 20, 8, 11),
    ("blockwise", "float32", (2, 2), 16, 8, 100),
    ("blockwise", "float32", (6, 3), 24, 8, None),
    ("blockwise", "bfloat16", (4, 2), 24, 8, 5),
    # the repo's Pallas kernels, interpreted: the band narrower than a
    # block, as wide as one (the published case), wider, and none
    ("fused", "float32", (4, 2), 512, 128, 100),
    ("fused", "float32", (3, 1), 512, 128, 128),
    ("fused", "float32", (2, 2), 384, 128, 300),
    ("fused", "float32", (6, 2), 384, 128, None),
    ("fused", "bfloat16", (4, 2), 512, 128, 128),
    ("fused", "bfloat16", (6, 2), 384, 128, None),
    ("fused", "float32", (2, 1), 1024, 256, 512),
    # one backward kernel: at 640 positions the key block is 128, so a
    # band of 300 spans four key blocks, under 7 query heads on 1; the
    # whole triangle over 5 query blocks; a batch of 2 (the third
    # number), so the whole-sequence dk/dv scratch starts anew for each
    # sequence and key/value head
    ("fused", "float32", (7, 1), 640, 128, 300),
    ("fused", "bfloat16", (7, 1), 640, 128, 300),
    ("fused", "float32", (4, 2), 640, 128, None),
    ("fused", "float32", (4, 2, 2), 384, 128, 200)])
def test_banded_attention_matches_the_explicit_mask(core, dtype, heads,
                                                    seq, block, window):
    """Values and gradients of both lowerings of the grouped, banded
    core against the oracle; ``dk`` and ``dv`` come back in the
    key/value heads' shape, summed over the group. ``heads``: query
    and key/value heads, and the batch where it is not 1."""
    rng = numpy.random.default_rng(seq + (window or 0))
    dim = 128 if core == "fused" else 8
    batch = heads[2] if len(heads) > 2 else 1
    q = jnp.asarray(rng.normal(size=(batch, heads[0], seq, dim)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(batch, heads[1], seq, dim)), dtype)
            for _ in range(2))
    scale = 1.2 / dim ** 0.5
    oracle = core_run(lambda q, k, v: masked_attention(
        q, k, v, scale, window), q, k, v)
    if core == "blockwise":
        got = core_run(lambda q, k, v: blockwise_attention(
            q, k, v, scale, block, window), q, k, v)
    else:
        assert fused_refusal(q, k, v, block, window) is None
        with pltpu.force_tpu_interpret_mode():
            got = core_run(lambda q, k, v: banded_attention(
                q, k, v, scale, block, window), q, k, v)
    for name, g, o in zip(("out", "dq", "dk", "dv"), got, oracle):
        assert g.dtype == o.dtype and g.shape == o.shape, name
        g, o = (numpy.asarray(t, numpy.float32) for t in (g, o))
        if dtype == "float32":
            numpy.testing.assert_allclose(
                g, o, rtol=1e-5 if name == "out" else 2e-4, atol=2e-5,
                err_msg=name)
        else:
            # the probabilities are rounded to bfloat16 for the second
            # product (2**-9 a term), the oracle's are not
            assert numpy.linalg.norm(g - o) < 8e-3 * numpy.linalg.norm(o), \
                name


@pytest.mark.parametrize("seq,block,window,pairs", [
    (4096, 512, 512, 15), (4096, 512, None, 36), (4096, 512, 513, 15),
    (4096, 512, 514, 21), (4096, 512, 1, 8), (2048, 512, 512, 7),
    (1024, 256, 100, 5)])
def test_the_band_runs_the_block_pairs_it_touches(seq, block, window,
                                                  pairs):
    """The kernels' grids by their geometry, against a count over the
    mask itself (at 1,024 positions in blocks of 256 queries the key
    block is 512); XLA's blocks, which start at the band's first key
    and not at a block's, run as many where the band is whole blocks
    wide."""
    back = numpy.arange(seq)[:, None] - numpy.arange(seq)[None, :]
    seen = (back >= 0) & (back < (window or seq))
    kv = math.gcd(seq, sequence.FUSED_KV_BLOCK)
    touched = sum(
        bool(seen[r:r + block, c:c + kv].any())
        for r in range(0, seq, block) for c in range(0, seq, kv))
    assert sequence.core_blocks(seq, block, window) == touched == pairs
    if (window or seq) % block == 0:
        assert sequence.core_blocks(seq, block, window, fused=False) == pairs


@pytest.mark.parametrize("backend,heads,seq,block,window,taken", [
    ("cpu", (4, 2), 256, 128, 100, "blockwise_attention"),
    ("tpu", (4, 2), 20, 8, 5, "blockwise_attention"),   # ragged blocks
    ("tpu", (4, 2), 256, 128, 100, "banded_attention"),
    ("tpu", (4, 2), 256, 128, None, "banded_attention"),  # grouped alone
    ("tpu", (2, 2), 256, 128, 100, "banded_attention"),  # windowed alone
    ("tpu", (2, 2), 256, 128, None, "fused_attention"),  # as before
    # the banded backward's dk/dv scratch over the VMEM, which the
    # test holds to what 256 positions need
    ("tpu", (4, 2), 512, 128, 100, "blockwise_attention")])
def test_causal_attention_chooses_by_platform_and_shape(
        monkeypatch, caplog, backend, heads, seq, block, window, taken):
    """The chooser reads the default backend and the operands' shapes,
    the window and the grouping among them, and nothing else; operands
    of one shape without a window take jaxlib's kernels as before; a
    sequence whose dk/dv scratch the VMEM cannot hold takes XLA's
    blocks; the gauges say what was traced; a fallback on a TPU is
    logged."""
    from veles_tpu.ops import band_attention
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(sequence, "_refusals_logged", set())
    monkeypatch.setattr(band_attention, "VMEM_BYTES",
                        band_attention.backward_vmem_bytes(
                            256, 128, jnp.float32))
    calls = []
    for name in ("fused_attention", "banded_attention",
                 "blockwise_attention"):
        monkeypatch.setattr(
            sequence, name, lambda *a, _name=name, _fn=getattr(
                sequence, name): calls.append(_name) or _fn(*a))
    rng = numpy.random.default_rng(seq)
    q = jnp.asarray(rng.normal(size=(1, heads[0], seq, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, heads[1], seq, 128)),
                        jnp.float32) for _ in range(2))
    unit = "chooser_%s_%s_%d_%s" % (backend, heads[0], seq, window)
    with caplog.at_level("WARNING", logger="sequence"), \
            pltpu.force_tpu_interpret_mode():
        out = sequence.causal_attention(q, k, v, 0.1, block, unit=unit,
                                        window=window)
    assert calls == [taken]
    fused = taken != "blockwise_attention"
    assert gauge("veles_attention_core_fused")[unit] == float(fused)
    assert gauge("veles_attention_window")[unit] == float(window or 0)
    assert gauge("veles_attention_kv_group")[unit] == heads[0] / heads[1]
    assert gauge("veles_attention_backward_kernels")[unit] == {
        "blockwise_attention": 0, "banded_attention": 1,
        "fused_attention": 2}[taken]
    pairs = {labels["pass"]: child.value for labels, child in
             get_registry().get("veles_attention_core_blocks").series()
             if labels["unit"] == unit}
    expected = sequence.core_blocks(seq, block, window, fused=fused)
    assert pairs == {"forward": expected, "backward": expected}
    warned = [r for r in caplog.records if unit in r.getMessage()]
    assert len(warned) == (1 if backend == "tpu" and not fused else 0)
    numpy.testing.assert_allclose(
        out, masked_attention(q, k, v, 0.1, window), rtol=1e-5, atol=1e-6)


def test_refusals_name_the_grouped_and_windowed_cases():
    q = jax.ShapeDtypeStruct((1, 6, 256, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.bfloat16)
    assert "head count that divides" in fused_refusal(q, kv, kv, 128)
    kv = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)
    assert fused_refusal(q, kv, kv, 128) is None
    assert fused_refusal(q, kv, kv, 128, 40) is None
    assert "is none" in fused_refusal(q, kv, kv, 128, 0)
    assert "fewer heads" in fused_refusal(
        q, q, jax.ShapeDtypeStruct((1, 6, 128, 128), jnp.bfloat16), 128)
    # the banded backward holds dk, dv of a key/value head's whole
    # sequence in VMEM: the published 16,384 positions of 28 heads on 4
    # fit, 65,536 do not; jaxlib's kernels hold no such thing
    for seq, refused in ((16384, False), (65536, True)):
        q, kv = (jax.ShapeDtypeStruct((1, heads, seq, 128), jnp.bfloat16)
                 for heads in (28, 4))
        assert ("dk/dv scratch" in (fused_refusal(q, kv, kv, 512) or "")
                ) == refused
        assert fused_refusal(q, kv, kv, 512, 4096) == fused_refusal(
            q, kv, kv, 512)
        assert fused_refusal(q, q, q, 512) is None


# -- the whole model through the trainer -----------------------------------

def batch_of(wf, trainer, klass, row=0):
    idx = trainer._segment_indices(klass)[row]
    return (wf.loader.original_data.mem[idx],
            wf.loader.original_labels.mem[idx])


def test_published_layers_are_what_the_preset_describes():
    """Both layer kinds in the published ratio, their head counts and
    rotary tables; the tiny preset has every mechanism."""
    chain = layers(**dict(PUBLISHED, positions=4096))
    attention = [d for d in chain if d["type"] == "grouped_attention"]
    assert len(attention) == 48
    assert [d["heads"] for d in attention[:5]] == [48, 72, 72, 72, 48]
    assert [d["window"] for d in attention[:5]] == [
        None, 512, 512, 512, None]
    assert attention[0]["yarn"]["factor"] == 128 and \
        attention[0]["rotary_fraction"] == 0.5
    assert attention[1]["yarn"] is None and \
        attention[1]["rope_theta"] == 1e4
    assert [d["type"] for d in chain[2:5:2]] == ["gated_mlp", "moe"]
    tiny = [d for d in layers(**TINY) if d["type"] == "grouped_attention"]
    assert {(d["heads"], d["window"]) for d in tiny} == {(4, None), (6, 5)}
    assert TINY["window"] < TINY["positions"] and \
        TINY["window"] % TINY["block"]
    assert TINY["top_k"] > 1 and TINY["experts_held"][1] < \
        TINY["n_experts"]


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
    head what the fused chain gives it."""
    wf, descr, trainer, host = model
    tokens, _ = batch_of(wf, trainer, VALIDATION)
    wf.loader.minibatch_data.map_invalidate()[...] = tokens
    for fwd in wf.forwards:
        fwd.run()
    head = wf.forwards[-1]
    eager = numpy.asarray(head.output.map_read())
    expected = ref.logits(descr, host, jnp.asarray(tokens))
    numpy.testing.assert_allclose(
        eager, jax.nn.softmax(expected, -1), rtol=2e-4, atol=1e-7)
    params, _ = trainer.pull_params()
    state = trainer._forward_range(
        params[:-1], jnp.asarray(tokens), None, False, 0,
        len(params) - 1)
    numpy.testing.assert_allclose(
        head.apply_for_grad(params[-1], state), expected, rtol=2e-4,
        atol=2e-5)


def inputs_of(descr, host, tokens, index):
    """The reference's state entering layer ``index``."""
    cut = descr[:index] + [descr[-1]]
    return jax.jit(lambda p, t: ref.states(cut, p, t))(
        host[:index] + [host[-1]], jnp.asarray(tokens))


def test_objective_and_every_gradient_match_reference(model):
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
    for tag, stats in extras["stats"].items():
        index = int(tag[1:3])
        numpy.testing.assert_array_equal(
            stats["expert_counts"],
            ref.expert_counts(descr[index], host[index], inputs_of(
                descr, host, tokens, index)))


def test_two_adam_steps():
    """Two steps of the train segment against Adam written out here on
    the reference's gradients; no selection bias moves (``bias_rate``
    0) and no token is dropped."""
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
    for step in range(2):
        tokens, targets = data[idx[step]], labels[idx[step]]
        _, terms = ref.objective(descr, host, tokens, targets)
        numpy.testing.assert_allclose(losses[step], terms["main"],
                                      rtol=2e-5)
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
    for stats in trainer.last_step_stats["stats"].values():
        numpy.testing.assert_array_equal(
            numpy.asarray(stats["expert_counts"]).sum(1),
            4 * TINY["positions"] * TINY["top_k"])


#: sizes the fused kernels' tiling admits, as small as it admits them
FUSED_SIZES = dict(head_dim=128, kv_heads=1, full_heads=2, window_heads=3,
                   window=100, positions=256, block=128, blocks=2)


def traced_as(monkeypatch, core):
    """The context in which ``causal_attention`` takes ``core``:
    nothing for XLA's blocks; for the fused kernels the chooser sees a
    TPU and the kernels run in ``pallas_call``'s plain interpreter
    (``True``: ``jax.checkpoint`` refuses the simulator's effects)."""
    if core != "fused":
        return contextlib.nullcontext()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return pltpu.force_tpu_interpret_mode(True)


def keeping(fn):
    return lambda *args: remat.checkpoint(fn)(*args)[0]


@pytest.mark.parametrize("core", ["updates", "blockwise", "fused"])
def test_remat_changes_nothing(monkeypatch, core):
    """``updates``: ``remat`` on every block's units gives the same
    losses and updates to rounding. ``blockwise``, ``fused``: with
    rematerialized attention alone, the objective and EVERY gradient
    are the chain's without ``remat`` to the bit, on either lowering of
    the core. The gauge reads what a unit kept: the core's output and
    one float32 statistic a row of every QUERY head, and no key or
    value."""
    sizes = FUSED_SIZES if core == "fused" else {}
    batch = 2 if core == "fused" else 4
    plain_wf, _ = build(sizes=sizes, batch=batch)
    remat_wf, _ = build(sizes=dict(sizes, remat=True), batch=batch)
    assert all(fwd.remat for fwd in remat_wf.forwards
               if type(fwd).__name__ in (ATTENTION, "MoEForward",
                                         "GatedMLPForward"))
    if core != "updates":
        for fwd in remat_wf.forwards:
            fwd.remat = type(fwd).__name__ == ATTENTION
    size = dict(TINY, **sizes)
    outs = []
    for wf in (plain_wf, remat_wf):
        trainer = FusedTrainer(wf)
        params, states = trainer.pull_params()
        if core == "updates":
            outs.append(trainer.train_class(params, states))
        else:
            tokens, labels = batch_of(wf, trainer, TRAIN)
            with traced_as(monkeypatch, core):
                outs.append(jax.value_and_grad(
                    lambda p: trainer._token_objective(
                        p, jnp.asarray(tokens), jnp.asarray(labels), None,
                        jnp.ones(len(tokens), bool), True)[0])(params))
        kept = gauge("veles_remat_kept_bytes")
        assert {fwd.name: kept[fwd.name] for fwd in wf.forwards[:-1]} == {
            fwd.name: batch * fwd.heads * size["positions"]
            * (size["head_dim"] * 4 + 4) if fwd.remat
            and type(fwd).__name__ == ATTENTION else 0
            for fwd in wf.forwards[:-1]}
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
        if type(fwd).__name__ == ATTENTION:
            assert fused[fwd.name] == (core == "fused")
            assert windows[fwd.name] == (fwd.window or 0)


def unit_gradient(wf, index, wrap):
    """The gradient of ``sum(unit(params, x))`` of forward unit
    ``index``, the unit under ``wrap``, at the chain's shapes."""
    fwd = wf.forwards[index]
    params = {k: jnp.asarray(a.map_read())
              for k, a in fwd.param_arrays().items()}
    x = jnp.asarray(numpy.random.default_rng(5).normal(
        size=fwd.input.shape), jnp.float32)

    def loss(p, v):
        return jnp.sum(wrap(lambda p, v: fwd.apply(p, v))(p, v))
    return jax.grad(loss, (0, 1)), params, x


@pytest.mark.parametrize("core,index", [
    ("blockwise", 1), ("blockwise", 3), ("fused", 1), ("fused", 3)])
def test_rematerialized_unit_runs_its_core_forward_once(monkeypatch, core,
                                                        index):
    """In the jaxpr of a rematerialized unit's gradient (a full layer,
    unit 1, and a window layer, unit 3) the core's forward stands
    once, as without ``remat``: one forward kernel of two
    ``pallas_call``s, the other the one backward kernel (fused), one
    pass of ``exp`` over the blocks' scores (blockwise). Under a plain
    ``jax.checkpoint`` it stands twice."""
    wf, _ = build(sizes=FUSED_SIZES if core == "fused" else {}, batch=2)
    assert type(wf.forwards[index]).__name__ == ATTENTION
    mark = "pallas_call" if core == "fused" else " exp "
    counts = {}
    with traced_as(monkeypatch, core):
        for how, wrap in (("none", lambda fn: fn),
                          ("plain", jax.checkpoint), ("kept", keeping)):
            grad, params, x = unit_gradient(wf, index, wrap)
            counts[how] = str(jax.make_jaxpr(grad)(params, x)).count(mark)
    blocks = TINY["positions"] // TINY["block"]
    forward, backward = (1, 1) if core == "fused" \
        else (2 * blocks, blocks)
    assert counts == {"none": forward + backward,
                      "plain": 2 * forward + backward,
                      "kept": forward + backward}


def test_snapshot_and_resume():
    """A run of one epoch, dumped, loaded and run for a second gives
    what two epochs in one process give; the unit's descriptor keys
    (window, YaRN's, the gate) come back with it."""
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
            assert (a.window, a.yarn, a.heads, a.kv_heads, a.gated,
                    a.rotary_fraction) == (b.window, b.yarn, b.heads,
                                           b.kv_heads, b.gated,
                                           b.rotary_fraction)
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
    code = main(["veles_tpu/models/window_moe_lm.py", "-s", "5",
                 "root.window_moe_lm.max_epochs=2",
                 "--result-file", result_file])
    assert code == 0
    assert json.load(open(result_file))


def test_layer_type_is_registered():
    from veles_tpu.standard_workflow import LAYER_TYPES
    assert LAYER_TYPES["grouped_attention"] is GroupedAttentionForward


# -- routing at the published width, and the chip's share ------------------

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


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shares_add_up(scoring):
    """16 experts over 4 shares of 4: the four partial routed results
    plus the shared expert and the residual once equal the uncut
    reference layer."""
    sizes = dict(TINY, n_experts=16, top_k=4, scoring=scoring)
    whole = layers(**dict(sizes, experts_held=None))
    index = [i for i, d in enumerate(whole) if d["type"] == "moe"][0]
    rng = numpy.random.default_rng(7)
    dim, hidden = TINY["dim"], TINY["expert_hidden"]

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                           jnp.float32)

    full = {"weights": mat(dim, 16), "norm": jnp.ones(dim),
            "select_bias": jnp.zeros(16),
            "gate": mat(16, dim, hidden), "up": mat(16, dim, hidden),
            "down": mat(16, hidden, dim), "shared_gate": mat(1, dim, hidden),
            "shared_up": mat(1, dim, hidden),
            "shared_down": mat(1, hidden, dim)}
    x = random_state(6)
    expected = ref.moe(whole[index], full, x)
    total = None
    for first in range(0, 16, 4):
        wf, descr = build(sizes={"n_experts": 16, "top_k": 4,
                                 "scoring": scoring,
                                 "experts_held": (first, 4)})
        fwd = wf.forwards[index]
        share = dict(full, **{k: full[k][first:first + 4]
                              for k in ("gate", "up", "down")})
        # what every chip computes alike, counted once: the shared
        # expert and the residual, on the first share only
        fwd.residual = first == 0
        if first:
            share = dict(share, **{
                k: jnp.zeros_like(v) for k, v in share.items()
                if k.startswith("shared_")})
        part = fwd.apply(share, x)
        numpy.testing.assert_allclose(
            part - (ref.moe(dict(descr[index], experts_held=[0, 1]),
                            dict(share, gate=0 * share["gate"][:1],
                                 up=share["up"][:1],
                                 down=share["down"][:1]), x)
                    if first == 0 else 0),
            ref.moe(descr[index], share, x, shared=False), rtol=2e-4,
            atol=2e-5)
        total = part if total is None else total + part
    numpy.testing.assert_allclose(total, expected, rtol=2e-5, atol=2e-5)


def test_the_benchmarks_configuration_is_the_published_layers_cut():
    """``benchmark/configs/laguna-s21-ep32share.json`` holds
    ``layers(**PUBLISHED)`` cut as the file itself says: five blocks,
    experts 0..7, 12,544 vocabulary rows, one sequence of the length
    it assumes; and 811,018,240 parameters by the units' own shapes."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "laguna-s21-ep32share.json")
    with open(path) as f:
        config = json.load(f)
    positions = config["layers"][0]["positions"]
    expected = layers(**dict(
        PUBLISHED, blocks=config["num_hidden_layers"],
        vocabulary=config["vocab_size"], positions=positions,
        experts_held=(0, config["num_experts"]),
        dispatch_rows=positions, remat=True))
    assert json.loads(json.dumps(expected)) == config["layers"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 12544)
    assert [d["heads"] for d in expected
            if d["type"] == "grouped_attention"] == \
        config["num_attention_heads_per_layer"] == PUBLISHED_HEADS[:5]
    total, shape = 0, (config["batch"], positions, PUBLISHED["dim"])
    for descr in expected:
        if descr["type"] == "grouped_attention":
            fwd = GroupedAttentionForward(DummyLauncher(), **{
                k: v for k, v in descr.items()
                if k not in ("type", "remat")})
            total += sum(math.prod(s) for s, _ in
                         fwd.param_shapes(shape).values())
        elif descr["type"] == "moe":
            held = descr["experts_held"][1] + descr["shared_experts"]
            total += shape[-1] * (descr["n_experts"] + 1) \
                + descr["n_experts"] + held * 3 * shape[-1] * descr["hidden"]
        elif descr["type"] == "gated_mlp":
            total += shape[-1] + 3 * shape[-1] * descr["hidden"]
        elif descr["type"] == "rms_norm":
            total += shape[-1]
        else:  # the embedding and the head
            total += descr["vocabulary"] * shape[-1]
    assert total == config["held_here"]["parameters"] == 811018240


#: the published per-layer head counts: 48 on full layers, 72 on sliding
PUBLISHED_HEADS = [48 if i % 4 == 0 else 72 for i in range(48)]
