"""The causal attention cores beside an explicit mask: XLA's blocks
(``parallel/sequence.py`` ``blockwise_attention``), jaxlib's fused
kernels (``fused_attention``) and the repo's banded kernels
(``ops/band_attention.py``, ``banded_attention``), values and
gradients, grouped heads and windows; the block pairs the band runs;
which core ``causal_attention`` takes and what it refuses; a head of
64 behind zeros; and what a rematerialized unit keeps of its core.
The kernels run interpreted here."""

import math

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from test_latent_moe_lm import FAMILY as LATENT
from test_window_moe_lm import FAMILY as WINDOW
from token_models import (float32_highest, gauge,  # noqa: F401 (fixture)
                          keeping, traced_as)
from veles_tpu.dummy import DummyLauncher
from veles_tpu.parallel import sequence
from veles_tpu.parallel.sequence import (banded_attention,
                                         blockwise_attention,
                                         fused_attention, fused_refusal,
                                         local_attention)
from veles_tpu.standard_workflow import LAYER_TYPES
from veles_tpu.telemetry.registry import get_registry

CORES = {"blockwise": blockwise_attention, "banded": banded_attention,
         "fused": lambda q, k, v, scale, block, window: fused_attention(
             q, k, v, scale, block)}

#: the probabilities are rounded to bfloat16 for the second product
#: (2**-9 a term), the oracle's are not
BFLOAT16_ERROR = {"blockwise": 8e-3, "banded": 8e-3, "fused": 6e-3}


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


@pytest.mark.parametrize("core,dtype,heads,seq,block,window,dims", [
    # XLA's blocks whole and ragged, ``qk`` and ``v`` head sizes apart
    # (two sequences of three heads)
    ("blockwise", "float32", (3, 3, 2), 16, 8, None, (16, 12)),
    ("blockwise", "float32", (3, 3, 2), 24, 8, None, (8, 8)),
    ("blockwise", "float32", (3, 3, 2), 20, 8, None, (16, 4)),
    ("blockwise", "float32", (3, 3, 2), 8, 16, None, (4, 4)),
    # grouped and windowed
    ("blockwise", "float32", (4, 2), 24, 8, 5, (8, 8)),
    ("blockwise", "float32", (6, 2), 20, 8, 11, (8, 8)),
    ("blockwise", "float32", (2, 2), 16, 8, 100, (8, 8)),
    ("blockwise", "float32", (6, 3), 24, 8, None, (8, 8)),
    ("blockwise", "bfloat16", (4, 2), 24, 8, 5, (8, 8)),
    # jaxlib's fused kernels: three blocks against key blocks of 128,
    # four against key blocks of 512
    ("fused", "float32", (2, 2), 384, 128, None, (128, 128)),
    ("fused", "bfloat16", (2, 2), 384, 128, None, (128, 128)),
    ("fused", "float32", (2, 2), 1024, 256, None, (256, 256)),
    ("fused", "bfloat16", (2, 2), 1024, 256, None, (256, 256)),
    # the repo's banded kernels: the band narrower than a block, as
    # wide as one (the published case), wider, and none
    ("banded", "float32", (4, 2), 512, 128, 100, (128, 128)),
    ("banded", "float32", (3, 1), 512, 128, 128, (128, 128)),
    ("banded", "float32", (2, 2), 384, 128, 300, (128, 128)),
    ("banded", "float32", (6, 2), 384, 128, None, (128, 128)),
    ("banded", "bfloat16", (4, 2), 512, 128, 128, (128, 128)),
    ("banded", "bfloat16", (6, 2), 384, 128, None, (128, 128)),
    ("banded", "float32", (2, 1), 1024, 256, 512, (128, 128)),
    # one backward kernel: at 640 positions the key block is 128, so a
    # band of 300 spans four key blocks, under 7 query heads on 1; the
    # whole triangle over 5 query blocks; a batch of 2 (the third
    # number), so the whole-sequence dk/dv scratch starts anew for each
    # sequence and key/value head
    ("banded", "float32", (7, 1), 640, 128, 300, (128, 128)),
    ("banded", "bfloat16", (7, 1), 640, 128, 300, (128, 128)),
    ("banded", "float32", (4, 2), 640, 128, None, (128, 128)),
    ("banded", "float32", (4, 2, 2), 384, 128, 200, (128, 128))])
def test_causal_core_matches_the_explicit_mask(core, dtype, heads, seq,
                                               block, window, dims):
    """Values and gradients of each lowering of the causal core against
    the oracle; ``dk`` and ``dv`` come back in the key/value heads'
    shape, summed over the group. ``heads``: query and key/value
    heads, and the batch where it is not 1; ``dims``: the ``qk`` and
    the ``v`` head size."""
    rng = numpy.random.default_rng(seq + (window or 0))
    batch = heads[2] if len(heads) > 2 else 1
    q, k, v = (jnp.asarray(rng.normal(size=(batch, h, seq, d)), dtype)
               for h, d in ((heads[0], dims[0]), (heads[1], dims[0]),
                            (heads[1], dims[1])))
    scale = 1.2 / dims[0] ** 0.5
    oracle = core_run(lambda q, k, v: masked_attention(
        q, k, v, scale, window), q, k, v)
    if core == "blockwise":
        got = core_run(lambda q, k, v: blockwise_attention(
            q, k, v, scale, block, window), q, k, v)
    else:
        assert fused_refusal(q, k, v, block, window) is None
        with pltpu.force_tpu_interpret_mode():
            got = core_run(lambda q, k, v: CORES[core](
                q, k, v, scale, block, window), q, k, v)
    for name, g, o in zip(("out", "dq", "dk", "dv"), got, oracle):
        assert g.dtype == o.dtype and g.shape == o.shape, name
        g, o = (numpy.asarray(t, numpy.float32) for t in (g, o))
        if dtype == "float32":
            numpy.testing.assert_allclose(
                g, o, rtol=1e-5 if name == "out" else 2e-4, atol=2e-5,
                err_msg=name)
        else:
            assert numpy.linalg.norm(g - o) < BFLOAT16_ERROR[core] \
                * numpy.linalg.norm(o), name


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
    ("cpu", (2, 2), 256, 128, None, "blockwise_attention"),  # no TPU
    ("tpu", (4, 2), 20, 8, 5, "blockwise_attention"),   # ragged blocks
    ("tpu", (2, 2), 20, 8, None, "blockwise_attention"),
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
    of one shape without a window take jaxlib's kernels; a sequence
    whose dk/dv scratch the VMEM cannot hold takes XLA's blocks; the
    gauges say what was traced; a fallback on a TPU is logged, once a
    reason."""
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
        outs = [sequence.causal_attention(q, k, v, 0.1, block, unit=unit,
                                          window=window) for _ in range(2)]
    assert calls == [taken] * 2
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
        outs[0], masked_attention(q, k, v, 0.1, window), rtol=1e-5,
        atol=1e-6)


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
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sequence, "_refusals_logged", set())
    rng = numpy.random.default_rng(64)
    q = jnp.asarray(rng.normal(size=(1, heads[0], 256, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, heads[1], 256, 64)),
                        jnp.float32) for _ in range(2))
    assert "head size 64 is not a multiple of 128" == \
        sequence.fused_refusal(q, k, v, 128, window)
    unit = "gqa64_%d_%s" % (heads[0], window)
    with caplog.at_level("WARNING", logger="sequence"), \
            pltpu.force_tpu_interpret_mode():
        got = core_run(lambda q, k, v: sequence.causal_attention(
            q, k, v, 0.125, 128, unit=unit, window=window), q, k, v)
    assert gauge("veles_attention_core_fused")[unit] == 1.0
    assert gauge("veles_attention_head_padding")[unit] == 64.0
    assert gauge("veles_attention_kv_group")[unit] == heads[0] / heads[1]
    assert not [r for r in caplog.records if unit in r.getMessage()]
    oracle = core_run(lambda q, k, v: masked_attention(
        q, k, v, 0.125, window), q, k, v)
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


# -- what a rematerialized unit keeps of its core -----------------------------

@pytest.mark.parametrize("family,nth,core,forward,backward", [
    # a latent-attention unit: jaxlib's kernels, one forward kernel of
    # three ``pallas_call``s
    (LATENT, 0, "blockwise", 4, 2), (LATENT, 0, "fused", 1, 2),
    # a full layer and a window layer: one forward kernel of two
    # ``pallas_call``s, the other the one backward kernel
    (WINDOW, 0, "blockwise", 4, 2), (WINDOW, 1, "blockwise", 4, 2),
    (WINDOW, 0, "fused", 1, 1), (WINDOW, 1, "fused", 1, 1)],
    ids=["latent-blockwise", "latent-fused", "full-blockwise",
         "window-blockwise", "full-fused", "window-fused"])
def test_rematerialized_unit_runs_its_core_forward_once(
        monkeypatch, family, nth, core, forward, backward):
    """In the jaxpr of a rematerialized attention unit's gradient (the
    ``nth`` attention unit of its family's tiny chain, at the sizes its
    fused kernels admit where ``core`` is ``fused``) the core's forward
    stands once, as in the gradient of the unit without ``remat``:
    ``forward`` and ``backward`` count the two passes' ``pallas_call``s
    (fused) or passes of ``exp`` over the blocks' scores (blockwise).
    Under a plain ``jax.checkpoint``, the control, the forward stands
    twice."""
    sizes = dict(family.workflow.TINY,
                 **(family.fused_sizes if core == "fused" else {}))
    descr = [d for d in family.workflow.layers(**sizes)
             if d["type"] in ("latent_attention", "grouped_attention")][nth]
    fwd = LAYER_TYPES[descr["type"]](DummyLauncher(), **{
        k: v for k, v in descr.items() if k not in ("type", "remat")})
    shape = (2, sizes["positions"], sizes["dim"])
    rng = numpy.random.default_rng(5)
    params = {name: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
              for name, (s, _) in fwd.param_shapes(shape).items()}
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    mark = "pallas_call" if core == "fused" else " exp "
    counts = {}
    with traced_as(monkeypatch, core):
        for how, wrap in (("none", lambda fn: fn),
                          ("plain", jax.checkpoint), ("kept", keeping)):
            grad = jax.grad(lambda p, v: jnp.sum(wrap(
                lambda p, v: fwd.apply(p, v))(p, v)), (0, 1))
            counts[how] = str(jax.make_jaxpr(grad)(params, x)).count(mark)
    assert counts == {"none": forward + backward,
                      "plain": 2 * forward + backward,
                      "kept": forward + backward}
