"""The latent-attention, sparse-expert language model through the
normal path (layer descriptors -> ``StandardWorkflow`` ->
``FusedTrainer``) against the plain float32 reference
``benchmark/reference/moe_lm.py``, at a tiny size."""

import contextlib

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmark.reference import moe_lm as ref
from veles_tpu import prng, remat
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.models.latent_moe_lm import (TINY, LatentMoELMWorkflow,
                                            layers)
from veles_tpu.nn import precision
from veles_tpu.nn.mlp import gated_mlp
from veles_tpu.nn.moe import MoEForward
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.parallel import sequence
from veles_tpu.parallel.sequence import (blockwise_attention,
                                         fused_attention, fused_refusal,
                                         local_attention)
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer

#: this chip's share in most tests: experts 2..5 of 8
HELD = (2, 4)


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
    sizes = dict({"experts_held": HELD}, **(sizes or {}))
    wf = LatentMoELMWorkflow(DummyLauncher(), sizes=sizes,
                             n_train=n_train, n_valid=n_valid,
                             minibatch_size=batch, seed=seed, **kwargs)
    wf.initialize(device=Device(backend="cpu"))
    descr = layers(**dict(TINY, **sizes))
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


def unit_of(wf, descr, ltype, nth=0):
    index = [i for i, d in enumerate(descr) if d["type"] == ltype][nth]
    fwd = wf.forwards[index]
    return fwd, descr[index], {
        k: jnp.asarray(a.map_read()) for k, a in fwd.param_arrays().items()}


def random_state(seed=0, batch=2):
    return jnp.asarray(numpy.random.default_rng(seed).normal(
        size=(batch, TINY["positions"], TINY["dim"])), jnp.float32)


# -- units against the reference ------------------------------------------

@pytest.mark.parametrize("ltype,reference", [
    ("latent_attention", ref.latent_attention),
    ("gated_mlp", ref.gated_mlp),
    ("moe", ref.moe),
    ("rms_norm", lambda d, p, x: ref.rms_norm(x, p["weights"])),
])
def test_unit_matches_reference(model, ltype, reference):
    """``qk`` and ``v`` head sizes differ and differ from dim/heads
    (16 and 12 of 32/2); one rotary key serves both heads."""
    wf, descr, _, _ = model
    fwd, d, params = unit_of(wf, descr, ltype)
    # gains away from one, so that a dropped norm shows
    params = {k: v + 0.1 * numpy.arange(v.shape[0])[::-1] / v.shape[0]
              if v.ndim == 1 and k != "select_bias" else v
              for k, v in params.items()}
    x = random_state()
    numpy.testing.assert_allclose(
        fwd.apply(params, x), reference(d, params, x), rtol=2e-5,
        atol=2e-6)


def test_latent_attention_oracle_core(model):
    """``block=None`` takes ``local_attention``: same unit, same
    result."""
    wf, descr, _, _ = model
    fwd, _, params = unit_of(wf, descr, "latent_attention")
    x = random_state(1)
    blockwise = fwd.apply(params, x)
    fwd.block = None
    try:
        numpy.testing.assert_allclose(fwd.apply(params, x), blockwise,
                                      rtol=2e-5, atol=2e-6)
    finally:
        fwd.block = TINY["block"]


def test_token_merge_and_head_match_reference(model):
    wf, descr, trainer, host = model
    merge, d, params = unit_of(wf, descr, "token_merge")
    table = jnp.asarray(host[0]["weights"])
    tokens = jnp.asarray(wf.loader.original_data.mem[:2])
    x = random_state(2)
    numpy.testing.assert_allclose(
        merge.merge(params, x, tokens, table),
        ref.token_merge(d, params, table, tokens, x), rtol=2e-5,
        atol=2e-6)
    head, _, hp = unit_of(wf, descr, "vocabulary_head")
    targets = tokens[:, 1:1 + x.shape[1]]
    import contextlib
    loss, wrong = head.token_losses(hp, x, targets, contextlib.nullcontext)
    numpy.testing.assert_allclose(
        loss, ref.head_losses(hp["weights"], x, targets), rtol=2e-5)
    assert wrong.shape == targets.shape and wrong.dtype == jnp.bool_


# -- the router ------------------------------------------------------------

def test_router_ties_take_the_lower_id(model):
    """All-zero router: every score ties at sigmoid(0); program and
    reference both choose experts 0..k-1, weights scale/k each."""
    wf, descr, _, _ = model
    fwd, d, params = unit_of(wf, descr, "moe")
    params = dict(params, weights=jnp.zeros_like(params["weights"]))
    h = random_state(3).reshape(-1, TINY["dim"])
    chosen, weights, _ = fwd.route(params, h)
    r_chosen, r_weights = ref.route(d, params, h)
    numpy.testing.assert_array_equal(chosen, r_chosen)
    numpy.testing.assert_array_equal(
        chosen, numpy.tile(numpy.arange(TINY["top_k"]), (len(h), 1)))
    numpy.testing.assert_allclose(weights, TINY["scale"] / TINY["top_k"],
                                  rtol=1e-6)
    numpy.testing.assert_allclose(weights, r_weights, rtol=1e-6)


def test_selection_bias_picks_and_does_not_weigh(model):
    wf, descr, _, _ = model
    fwd, d, params = unit_of(wf, descr, "moe")
    h = random_state(4).reshape(-1, TINY["dim"])
    plain, _, scores = fwd.route(params, h)
    biased = dict(params, select_bias=params["select_bias"].at[7].set(9.0))
    chosen, weights, _ = fwd.route(biased, h)
    assert (chosen == 7).any(axis=1).all()
    assert not (plain == 7).any(axis=1).all()
    picked = jnp.take_along_axis(scores, chosen, 1)
    numpy.testing.assert_allclose(
        weights, TINY["scale"] * picked / picked.sum(1, keepdims=True),
        rtol=1e-6)
    r_chosen, r_weights = ref.route(d, biased, h)
    numpy.testing.assert_array_equal(chosen, r_chosen)
    numpy.testing.assert_allclose(weights, r_weights, rtol=1e-6)
    # and no gradient reaches it
    grad = jax.grad(lambda b: fwd.apply(
        dict(params, select_bias=b), random_state(4)).sum())(
            params["select_bias"])
    assert not numpy.asarray(grad).any()


@pytest.mark.parametrize("dispatch_rows", [None, 8, 40])
def test_dropless_when_every_token_goes_to_one_held_expert(
        model, dispatch_rows):
    """A bias sends every token to held expert 3 (and one more): 32
    rows for one expert where the mean is 4 a held expert, past a
    bound of 8 (the overflow path) and inside one of 40. Nothing is
    dropped: the reference, which loops over experts with masks, gives
    the same; the counts sum to tokens * top_k."""
    wf, descr, _, _ = model
    fwd, d, params = unit_of(wf, descr, "moe")
    params = dict(params,
                  select_bias=params["select_bias"].at[3].set(9.0))
    x = random_state(5)
    fwd.dispatch_rows = dispatch_rows
    try:
        y, stats = fwd.apply_step(params, x, None)
        grads = jax.grad(lambda p: fwd.apply(p, x).sum())(params)
    finally:
        fwd.dispatch_rows = None
    counts = numpy.asarray(stats["expert_counts"])
    tokens = x.shape[0] * x.shape[1]
    assert counts[3] == tokens and counts.sum() == tokens * TINY["top_k"]
    numpy.testing.assert_array_equal(counts, ref.expert_counts(d, params, x))
    numpy.testing.assert_allclose(y, ref.moe(d, params, x), rtol=2e-5,
                                  atol=2e-6)
    r_grads = jax.grad(lambda p: ref.moe(d, p, x).sum())(params)
    for name in ("weights", "gate", "up", "down", "shared_up", "norm"):
        numpy.testing.assert_allclose(
            grads[name], r_grads[name], rtol=2e-4, atol=2e-5,
            err_msg=name)


# -- the combine: one row a routed assignment (PR 32) ------------------------

#: small widths: the routing's shape is what the cases vary
COMBINE_DIM, COMBINE_HIDDEN, COMBINE_TOKENS = 32, 16, (2, 16)
#: the GLM cell's routing (8 of 64 held, top-4), every expert held, and
#: the Laguna cell's (top-10 of 256, 8 held) under both scorings
COMBINE_SHAPES = {
    "8-of-64-top-4": dict(n_experts=64, top_k=4, experts_held=(8, 8),
                          scoring="sigmoid"),
    "all-held": dict(n_experts=8, top_k=4, experts_held=(0, 8),
                     scoring="sigmoid"),
    "top-10-of-256-sigmoid": dict(n_experts=256, top_k=10,
                                  experts_held=(0, 8), scoring="sigmoid"),
    "top-10-of-256-softmax": dict(n_experts=256, top_k=10,
                                  experts_held=(0, 8), scoring="softmax"),
}
#: a bound no routing here stays under, and one that none passes while
#: it is still below ``tokens * min(top_k, count)``
UNDER, OVER = 2, 64


def sparse_unit(dispatch_rows=None, seed=0, dtype=jnp.float32, **shape):
    """A dropless unit, its parameters and a batch: no workflow, the
    layer's pure functions only."""
    fwd = MoEForward(DummyLauncher(), name="moe4", hidden=COMBINE_HIDDEN,
                     capacity_factor=None, normalize=True, scale=1.8,
                     shared_experts=1, dispatch_rows=dispatch_rows, **shape)
    rng = numpy.random.default_rng(seed)
    dim, hidden, held = COMBINE_DIM, COMBINE_HIDDEN, fwd.experts_held[1]
    matrices = {"weights": (dim, fwd.n_experts),
                "gate": (held, dim, hidden), "up": (held, dim, hidden),
                "down": (held, hidden, dim), "shared_gate": (1, dim, hidden),
                "shared_up": (1, dim, hidden),
                "shared_down": (1, hidden, dim)}
    params = {name: rng.normal(size=dims) / numpy.sqrt(dims[-2])
              for name, dims in matrices.items()}
    params["norm"] = 1 + 0.1 * rng.normal(size=(dim,))
    params["select_bias"] = numpy.zeros((fwd.n_experts,))
    assert set(params) == {"weights", "up", "down"} | set(fwd.extra)
    params = {name: jnp.asarray(value, jnp.float32)
              for name, value in params.items()}
    return fwd, params, jnp.asarray(
        rng.normal(size=COMBINE_TOKENS + (dim,)), dtype)


def slot_gather_moe(fwd, params, x):
    """THE ORACLE: the dropless layer as it combined from PR 27 to
    PR 31, kept here and nowhere in the package. Every (token, slot)
    gathers a row of ``padded`` through ``inverse``, the zero row where
    its expert is not held, and an einsum weighs ``(tokens, top_k,
    dim)``."""
    pol = precision.get_policy()
    first, count = fwd.experts_held
    h = rms_norm(x, params["norm"], fwd.eps).reshape(-1, x.shape[-1])
    chosen, weights, _ = fwd.route(params, h)
    tokens, k = chosen.shape
    flat = chosen.reshape(-1)
    counts = jnp.sum(jax.nn.one_hot(flat, fwd.n_experts, dtype=jnp.int32),
                     axis=0)
    local = flat - first
    held = (local >= 0) & (local < count)
    order = jnp.argsort(jnp.where(held, local, count), stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    sizes = jax.lax.dynamic_slice(counts, (first,), (count,))

    def run(rows):
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        xs = jnp.where(live, pol.cast_in(h)[order[:rows] // k], 0)

        def grouped(lhs, name):
            return jnp.where(live, jax.lax.ragged_dot(
                lhs, pol.cast_in(params[name]), sizes,
                preferred_element_type=pol.accum_dtype), 0)
        hidden = jax.nn.silu(grouped(xs, "gate")) * grouped(xs, "up")
        out = pol.cast_in(grouped(pol.cast_in(hidden), "down"))
        padded = jnp.concatenate(
            [out, jnp.zeros((1, out.shape[1]), out.dtype)])
        slot = jnp.where(held & (inverse < rows), inverse, rows)
        return jnp.einsum("tk,tkd->td", weights,
                          padded[slot.reshape(tokens, k)],
                          preferred_element_type=pol.accum_dtype)

    full = tokens * min(k, count)
    bound = min(int(fwd.dispatch_rows or full), full)
    if bound < full:
        y = jax.lax.cond(jnp.sum(sizes) <= bound,
                         lambda: run(bound), lambda: run(full))
    else:
        y = run(full)
    y = y + gated_mlp(pol, h, params["shared_gate"][0],
                      params["shared_up"][0], params["shared_down"][0])
    y = y.reshape(x.shape)
    return pol.cast_out(y + x.astype(y.dtype))


@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
@pytest.mark.parametrize("dispatch_rows", [None, UNDER, OVER])
@pytest.mark.parametrize("shape", sorted(COMBINE_SHAPES))
def test_combine_by_sorted_row_equals_the_slot_gather(
        shape, dispatch_rows, policy):
    """The combine adds ONE weighted row a routed assignment into its
    token; the form it replaced gathered one a (token, slot). Same
    value and the same gradient to the input, the expert stacks, the
    shared expert, the norm and (through ``weights``) the router, with
    no bound, under a bound the routing passes (``lax.cond``'s overflow
    branch) and under one it does not; in float32 only the order of a
    token's terms differs, under the bfloat16 policy the rows are
    rounded alike on both sides before they are added in float32."""
    precision.set_policy(policy)
    try:
        fwd, params, x = sparse_unit(
            dispatch_rows, dtype=precision.get_policy().keep_dtype,
            **COMBINE_SHAPES[shape])
        probe = jnp.asarray(numpy.random.default_rng(9).normal(
            size=x.shape), jnp.float32)

        def objective(layer):
            def fn(p, x):
                y, stats = layer(p, x)
                return jnp.sum(y.astype(jnp.float32) * probe), (y, stats)
            return jax.jit(jax.value_and_grad(fn, (0, 1), has_aux=True))

        (_, (y, stats)), grads = objective(
            lambda p, x: fwd.apply_step(p, x, None))(params, x)
        (_, (r_y, _)), r_grads = objective(
            lambda p, x: (slot_gather_moe(fwd, p, x), None))(params, x)
    finally:
        precision.set_policy("float32")
    first, count = fwd.experts_held
    routed = int(stats["expert_counts"][first:first + count].sum())
    full = x.shape[0] * x.shape[1] * min(fwd.top_k, count)
    # with every expert held every assignment is a row: both bounds
    # are passed there
    assert UNDER < routed and (routed <= OVER or routed == full)
    assert y.dtype == r_y.dtype == x.dtype
    # a sum in another order may round to the neighbouring bfloat16
    tol = dict(rtol=2e-5, atol=2e-6) if policy == "float32" \
        else dict(rtol=2.0 ** -7, atol=2.0 ** -7)
    numpy.testing.assert_allclose(numpy.asarray(y, numpy.float32),
                                  numpy.asarray(r_y, numpy.float32), **tol)
    assert not numpy.asarray(grads[0]["select_bias"]).any()
    for name, mine, theirs in [("x", grads[1], r_grads[1])] + [
            (k, grads[0][k], r_grads[0][k]) for k in sorted(params)]:
        assert numpy.asarray(theirs).any() or name == "select_bias", name
        numpy.testing.assert_allclose(
            numpy.asarray(mine, numpy.float32),
            numpy.asarray(theirs, numpy.float32), rtol=2e-4, atol=2e-5,
            err_msg=name)


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(inner)


def moved_rows(jaxpr, rows, dim):
    """``(gathers, scatter-adds)`` of ``rows`` rows of ``dim`` in a
    jaxpr, every one of them under the ``route`` sub-scope."""
    found = {"gather": 0, "scatter-add": 0}
    for eqn in equations(jaxpr):
        if eqn.primitive.name == "gather":
            moved = eqn.outvars[0]
        elif eqn.primitive.name == "scatter-add":
            moved = eqn.invars[2]  # operand, indices, updates
        else:
            continue
        if moved.aval.shape == (rows, dim):
            assert "route" in str(eqn.source_info.name_stack), eqn
            found[eqn.primitive.name] += 1
    return found["gather"], found["scatter-add"]


def test_train_step_of_a_sparse_unit_moves_rows_not_slots():
    """What is traced for a rematerialized sparse unit of the GLM
    cell's routing under the bfloat16 policy, forward and gradient: no
    array of ``(tokens, top_k, dim)`` anywhere (the oracle holds
    them), and at the bound ONE gather and ONE scatter-add of ``rows``
    rows forward, the dispatch and the combine, each with the other's
    kind as its transpose in the gradient; the gauge says how many
    rows that is."""
    precision.set_policy("bfloat16")
    try:
        fwd, params, x = sparse_unit(OVER, dtype=jnp.bfloat16,
                                     **COMBINE_SHAPES["8-of-64-top-4"])

        def loss(layer):
            def fn(p, x):
                y, _ = remat.checkpoint(lambda p, x: layer(p, x))(p, x)
                return jnp.sum(y.astype(jnp.float32))
            return fn

        mine = loss(lambda p, x: fwd.apply_step(p, x, None)[0])
        forward = jax.make_jaxpr(mine)(params, x).jaxpr
        step = jax.make_jaxpr(jax.grad(mine, (0, 1)))(params, x).jaxpr
        theirs = jax.make_jaxpr(jax.grad(loss(
            lambda p, x: slot_gather_moe(fwd, p, x)), (0, 1)))(
                params, x).jaxpr
    finally:
        precision.set_policy("float32")
    tokens = x.shape[0] * x.shape[1]
    slots = (tokens, fwd.top_k, COMBINE_DIM)

    def shapes(jaxpr):
        return {v.aval.shape for eqn in equations(jaxpr)
                for v in eqn.outvars}

    assert slots in shapes(theirs)
    assert slots not in shapes(step) and slots not in shapes(forward)
    assert moved_rows(forward, OVER, COMBINE_DIM) == (1, 1)
    # the gradient: the forward's pair, the dispatch gather once more
    # (the recomputed forward needs no combine: it is linear), and the
    # two transposes
    assert moved_rows(step, OVER, COMBINE_DIM) == (3, 2)
    assert gauge("veles_moe_combine_rows")["moe4"] == float(OVER)


def test_the_shares_add_up():
    """16 experts over 4 shares of 4: the four partial routed results
    plus the shared expert and the residual once equal the uncut
    reference layer."""
    sizes = dict(TINY, n_experts=16, top_k=4)
    whole = layers(**sizes)
    index = [i for i, d in enumerate(whole) if d["type"] == "moe"][0]
    rng = numpy.random.default_rng(7)
    dim, hidden = TINY["dim"], TINY["expert_hidden"]

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                           jnp.float32)

    full = {"weights": mat(dim, 16), "norm": jnp.ones(dim),
            "select_bias": jnp.asarray(rng.normal(size=16) * 0.1,
                                       jnp.float32),
            "gate": mat(16, dim, hidden), "up": mat(16, dim, hidden),
            "down": mat(16, hidden, dim), "shared_gate": mat(1, dim, hidden),
            "shared_up": mat(1, dim, hidden),
            "shared_down": mat(1, hidden, dim)}
    x = random_state(6)
    expected = ref.moe(dict(whole[index], experts_held=[0, 16]), full, x)

    total = None
    for first in range(0, 16, 4):
        wf, descr = build(sizes={"n_experts": 16, "top_k": 4,
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
            part - (x if first == 0 else 0) - (
                ref.gated(ref.rms_norm(x, full["norm"]),
                          full["shared_gate"][0], full["shared_up"][0],
                          full["shared_down"][0]) if first == 0 else 0),
            ref.moe(descr[index], share, x, shared=False), rtol=2e-4,
            atol=2e-5)
        total = part if total is None else total + part
    numpy.testing.assert_allclose(total, expected, rtol=2e-5, atol=2e-5)


# -- the attention core ------------------------------------------------------

def core_run(fn, q, k, v):
    """Output and the three gradients of ``sum(sin(fn(q, k, v)))``."""
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
        q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("core,dtype,seq,block,dims", [
    ("blockwise", "float32", 16, 8, (16, 12)),
    ("blockwise", "float32", 24, 8, (8, 8)),
    ("blockwise", "float32", 20, 8, (16, 4)),
    ("blockwise", "float32", 8, 16, (4, 4)),
    # the fused TPU kernel, interpreted: three blocks against key
    # blocks of 128, four against key blocks of 512
    ("fused", "float32", 384, 128, (128, 128)),
    ("fused", "bfloat16", 384, 128, (128, 128)),
    ("fused", "float32", 1024, 256, (256, 256)),
    ("fused", "bfloat16", 1024, 256, (256, 256))])
def test_blockwise_attention_matches_local(core, dtype, seq, block, dims):
    """Values and gradients of both lowerings of the causal core
    against the oracle: XLA's blocks whole and ragged, ``qk`` and ``v``
    head sizes apart; the fused kernel at head sizes 128 and 256,
    float32 and bfloat16 operands."""
    rng = numpy.random.default_rng(seq)
    heads = (2, 3) if core == "blockwise" else (1, 2)
    q, k = (jnp.asarray(rng.normal(size=heads + (seq, dims[0])), dtype)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=heads + (seq, dims[1])), dtype)
    scale = 0.3 if core == "blockwise" else 1.2 / dims[0] ** 0.5

    oracle = core_run(lambda q, k, v: local_attention(
        q, k, v, causal=True, scale=scale), q, k, v)
    if core == "blockwise":
        got = core_run(lambda q, k, v: blockwise_attention(
            q, k, v, scale, block), q, k, v)
    else:
        assert fused_refusal(q, k, v, block) is None
        with pltpu.force_tpu_interpret_mode():
            got = core_run(lambda q, k, v: fused_attention(
                q, k, v, scale, block), q, k, v)
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
            assert numpy.linalg.norm(g - o) < 6e-3 * numpy.linalg.norm(o), \
                name


def gauge(name):
    """The registry's readings of ``name`` by unit."""
    return {labels["unit"]: child.value
            for labels, child in get_registry().get(name).series()}


@pytest.mark.parametrize("backend,seq,block,fused", [
    ("cpu", 256, 128, False),   # fits the tiling, but no TPU
    ("tpu", 20, 8, False),      # a TPU, but a ragged last block
    ("tpu", 256, 128, True)])
def test_causal_attention_chooses_by_platform_and_shape(
        monkeypatch, caplog, backend, seq, block, fused):
    """The chooser reads the default backend and the operands' shapes
    and nothing else; the gauge says what it took; a fallback on a TPU
    is logged, once a reason."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(sequence, "_refusals_logged", set())
    taken = []
    for name in ("fused_attention", "blockwise_attention"):
        monkeypatch.setattr(
            sequence, name, lambda *a, _name=name, _fn=getattr(
                sequence, name): taken.append(_name) or _fn(*a))
    rng = numpy.random.default_rng(seq)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, seq, 128)), jnp.float32)
               for _ in range(3))
    unit = "chooser_%s_%d" % (backend, seq)
    with caplog.at_level("WARNING", logger="sequence"), \
            pltpu.force_tpu_interpret_mode():
        outs = [sequence.causal_attention(q, k, v, 0.1, block, unit=unit)
                for _ in range(2)]
    assert taken == ["fused_attention" if fused
                     else "blockwise_attention"] * 2
    assert gauge("veles_attention_core_fused")[unit] == \
        (1.0 if fused else 0.0)
    warned = [r for r in caplog.records if unit in r.getMessage()]
    assert len(warned) == (1 if backend == "tpu" and not fused else 0)
    numpy.testing.assert_allclose(
        outs[0], local_attention(q, k, v, causal=True, scale=0.1),
        rtol=1e-5, atol=1e-6)


# -- the whole model through the trainer -----------------------------------

def batch_of(wf, trainer, klass, row=0):
    idx = trainer._segment_indices(klass)[row]
    return (wf.loader.original_data.mem[idx],
            wf.loader.original_labels.mem[idx])


def test_validation_losses_match_reference(model):
    wf, descr, trainer, host = model
    params, _ = trainer.pull_params()
    losses, metrics, conf = trainer.eval_class(params, VALIDATION)
    n = wf.loader.class_lengths[VALIDATION]
    expected = ref.validation_batch_losses(
        descr, host, wf.loader.original_data.mem[:n],
        wf.loader.original_labels.mem[:n], 4)
    numpy.testing.assert_allclose(losses, expected, rtol=1e-5)
    assert conf is None and numpy.asarray(metrics).sum() > 0
    # a fresh head is near uniform over the vocabulary held
    assert abs(float(jnp.mean(losses))
               - numpy.log(TINY["vocabulary"])) < 0.1


def test_logits_fused_equals_eager_equals_reference(model):
    """One batch through ``Unit.run`` of every forward unit (the MTP
    branch beside the main path, the merge reading its linked tokens
    and table) gives the head what the fused chain gives it."""
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
    # the branch ran eagerly too, from the final norm's output
    merge = [f for f in wf.forwards if f.name.startswith("token_merge")][0]
    norm = wf.forwards[wf.forwards.index(merge) - 1]
    assert merge.input is norm.output


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
    numpy.testing.assert_allclose(extras["losses"]["mtp"], terms["mtp"],
                                  rtol=1e-5)
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
    # the embedding and the head are each read twice (main and MTP)
    assert checked == sum(len(fwd.gradient_params(p))
                          for fwd, p in zip(wf.forwards, params))
    for tag, stats in extras["stats"].items():
        index = int(tag[1:3])
        numpy.testing.assert_array_equal(
            stats["expert_counts"],
            ref.expert_counts(descr[index], host[index], inputs_of(
                descr, host, tokens, index)))


def inputs_of(descr, host, tokens, index):
    """The reference's state entering layer ``index``."""
    cut = descr[:index] + [descr[-1]]
    main, sides = jax.jit(lambda p, t: ref.states(cut, p, t))(
        host[:index] + [host[-1]], jnp.asarray(tokens))
    branch = descr[index].get("branch")
    return sides[branch] if branch else main


def test_two_adam_steps_and_the_bias_update(model):
    """Two steps of the train segment against Adam and the bias rule
    written out here on the reference's gradients and counts."""
    wf, descr, _, _ = model
    wf, descr = build()  # fresh weights: the segment donates nothing
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
        numpy.testing.assert_allclose(
            trainer.last_step_stats["losses"]["mtp"][step], terms["mtp"],
            rtol=2e-5)
        grads = ref.gradients(descr, host, tokens, targets)
        counts = {i: numpy.asarray(ref.expert_counts(
            d, host[i], inputs_of(descr, host, tokens, i)), numpy.float32)
            for i, d in enumerate(descr) if d["type"] == "moe"}
        t = step + 1
        corr = numpy.sqrt(1 - hp["b2"] ** t) / (1 - hp["b1"] ** t)
        for i, layer in enumerate(host):
            for k in layer:
                if k == "select_bias":
                    layer[k] = layer[k] + descr[i]["bias_rate"] \
                        * numpy.sign(counts[i].mean() - counts[i])
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
                atol=1e-7 if k == "select_bias" else 0.05 * hp["lr"] * 2,
                err_msg="%s.%s" % (fwd.name, k))
    bias = numpy.asarray(new_params[4]["select_bias"])
    assert numpy.abs(bias).max() > 0
    assert "select_bias" not in new_states[4]["m"]
    assert float(new_states[4]["t"]) == len(idx)
    # no token dropped, any step: counts over ALL experts sum to
    # tokens * top_k
    for stats in trainer.last_step_stats["stats"].values():
        numpy.testing.assert_array_equal(
            numpy.asarray(stats["expert_counts"]).sum(1),
            4 * TINY["positions"] * TINY["top_k"])


#: sizes the fused kernel's tiling admits, as small as it admits them
FUSED_SIZES = dict(heads=1, qk_nope_dim=64, qk_rope_dim=64, v_dim=128,
                   positions=256, block=128)
ATTENTION = "LatentAttentionForward"


def traced_as(monkeypatch, core):
    """The context in which ``causal_attention`` takes ``core``:
    nothing for XLA's blocks; for the fused kernel the chooser sees a
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


@pytest.mark.parametrize("core", ["updates", "blockwise", "fused"])
def test_remat_changes_nothing(monkeypatch, core):
    """``updates``: ``remat`` on every block's units gives the same
    losses and updates to rounding (XLA fuses a recomputed unit its
    own way). ``blockwise``, ``fused``: with rematerialized latent
    attention alone, op by op, the objective and EVERY gradient are
    the chain's without ``remat`` to the bit, on either lowering of
    the core: what the units keep is what they would have made again.
    The gauge reads what a unit kept, 0 without ``remat``."""
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
    # the core's output in the compute dtype and a float32 statistic
    # (two on the fused path) for every row of every head
    size = dict(TINY, **sizes)
    kept = batch * size["heads"] * size["positions"] * (
        size["v_dim"] * 4 + 4 * (2 if core == "fused" else 1))
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
                outs.append(jax.value_and_grad(
                    lambda p: trainer._token_objective(
                        p, jnp.asarray(tokens), jnp.asarray(labels), None,
                        jnp.ones(len(tokens), bool), True)[0])(params))
        assert gauge("veles_remat_kept_bytes") == {
            fwd.name: kept if fwd.remat
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
    assert all(fused[fwd.name] == (core == "fused")
               for fwd in remat_wf.forwards
               if type(fwd).__name__ == ATTENTION)


def unit_gradient(wf, name, wrap):
    """The gradient of ``sum(unit(params, x))`` of the first unit of
    type ``name``, the unit under ``wrap``, at the chain's shapes."""
    fwd = [f for f in wf.forwards if type(f).__name__ == name][0]
    params = {k: jnp.asarray(a.map_read())
              for k, a in fwd.param_arrays().items()}
    x = jnp.asarray(numpy.random.default_rng(5).normal(
        size=fwd.input.shape), jnp.float32)

    def loss(p, v):
        return jnp.sum(wrap(lambda p, v: fwd.apply(p, v))(p, v))
    return jax.grad(loss, (0, 1)), params, x


@pytest.mark.parametrize("core", ["blockwise", "fused"])
def test_rematerialized_unit_runs_its_core_forward_once(monkeypatch, core):
    """In the jaxpr of a rematerialized latent-attention unit's
    gradient the core's forward stands once, as in the gradient of the
    unit without ``remat``: one forward kernel of three ``pallas_call``s
    (fused), one pass of ``exp`` over the blocks' scores (blockwise).
    Under a plain ``jax.checkpoint``, the control, it stands twice."""
    wf, _ = build(sizes=FUSED_SIZES if core == "fused" else {}, batch=2)
    mark = "pallas_call" if core == "fused" else " exp "
    counts = {}
    with traced_as(monkeypatch, core):
        for how, wrap in (("none", lambda fn: fn),
                          ("plain", jax.checkpoint), ("kept", keeping)):
            grad, params, x = unit_gradient(wf, ATTENTION, wrap)
            counts[how] = str(jax.make_jaxpr(grad)(params, x)).count(mark)
    blocks = TINY["positions"] // TINY["block"]
    forward, backward = (1, 2) if core == "fused" \
        else (2 * blocks, blocks)
    assert counts == {"none": forward + backward,
                      "plain": 2 * forward + backward,
                      "kept": forward + backward}


@pytest.mark.parametrize("name", ["MoEForward", "GatedMLPForward"])
def test_a_unit_that_keeps_nothing_lowers_as_under_a_plain_checkpoint(
        name):
    """The one policy of :func:`veles_tpu.remat.checkpoint` changes
    nothing for a unit that names nothing: the gradient lowers to the
    text a plain ``jax.checkpoint`` gives."""
    wf, _ = build()
    texts = []
    for wrap in (jax.checkpoint, keeping):
        grad, params, x = unit_gradient(wf, name, wrap)
        texts.append(jax.jit(grad).lower(params, x).as_text())
    assert "dot_general" in texts[0] and texts[0] == texts[1]


def test_snapshot_and_resume_of_the_new_state():
    """The selection bias and Adam's moments survive a snapshot: a run
    of one epoch, dumped, loaded and run for a second gives what two
    epochs in one process give."""
    from veles_tpu.snapshotter import dump_workflow, load_workflow

    def run(wf, epochs):
        trainer = FusedTrainer(wf)
        trainer.train(max_epochs=epochs)
        return trainer

    whole, _ = build(max_epochs=2)
    run(whole, 2)
    first, _ = build(max_epochs=2)
    run(first, 1)
    moe = first.forwards[4]
    assert numpy.abs(moe.select_bias.map_read()).max() > 0
    resumed = load_workflow(dump_workflow(first))
    resumed.workflow = DummyLauncher()
    resumed.initialize(device=Device(backend="cpu"))
    gd = [g for g in resumed.gds if g.forward is resumed.forwards[4]][0]
    assert float(gd.opt_state["t"]) == 2 and "gate" in gd.opt_state["m"]
    numpy.testing.assert_array_equal(
        resumed.forwards[4].select_bias.map_read(),
        moe.select_bias.map_read())
    run(resumed, 2)
    assert [h["epoch"] for h in resumed.decision.epoch_history] == [0, 1]
    for a, b in zip(whole.forwards, resumed.forwards):
        for name, arr in a.param_arrays().items():
            numpy.testing.assert_allclose(
                b.param_arrays()[name].map_read(), arr.map_read(),
                rtol=1e-4, atol=1e-6, err_msg="%s.%s" % (a.name, name))


def test_cli_trains_the_tiny_preset(tmp_path):
    """Launcher -> FusedRunner reaches the model: the one CLI line of
    the README."""
    import json

    from veles_tpu.__main__ import main
    result_file = str(tmp_path / "results.json")
    code = main(["veles_tpu/models/latent_moe_lm.py", "-s", "5",
                 "root.latent_moe_lm.max_epochs=2",
                 "--result-file", result_file])
    assert code == 0
    assert json.load(open(result_file))


def test_layer_types_are_registered():
    from veles_tpu.standard_workflow import LAYER_TYPES
    assert {"token_embedding", "rms_norm", "latent_attention",
            "gated_mlp", "token_merge", "vocabulary_head"} <= set(
                LAYER_TYPES)


@pytest.mark.parametrize("warmup,steps", [(None, 3), (10, 3), (2, 3)])
def test_adam_warm_up_scales_the_step(warmup, steps):
    """Adam's first steps move a weight by the whole learning rate
    whatever its gradient; ``warmup_steps`` scales step ``t`` by
    ``min(1, t / warmup_steps)``."""
    from veles_tpu.nn.optim import Adam
    hp = {"learning_rate": 0.5, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-12}
    if warmup:
        hp["warmup_steps"] = warmup
    params = {"w": jnp.zeros(3)}
    grads = {"w": jnp.asarray([1e-3, -2.0, 5.0])}
    state = Adam.init(params)
    for t in range(1, steps + 1):
        before = params["w"]
        params, state = Adam.update(params, grads, state, hp)
        scale = min(1.0, t / warmup) if warmup else 1.0
        numpy.testing.assert_allclose(
            params["w"] - before,
            -0.5 * scale * numpy.sign(grads["w"]), rtol=1e-5)


# -- what a sweep publishes, and the step the benchmark compares -----------

def test_units_publish_their_own_stats():
    """``train_class`` publishes once a sweep: the trainer the branch's
    loss, each sparse unit its own gauges through ``publish_stats``
    (the trainer names no expert); a unit without the hook nothing."""
    from veles_tpu.telemetry.registry import get_registry
    from veles_tpu.train.step import unit_tag
    wf, descr = build()
    trainer = FusedTrainer(wf)
    registry = get_registry()
    params, states = trainer.pull_params()
    params, _, _, _ = trainer.train_class(params, states)
    sparse = [unit_tag(i, fwd) for i, (d, fwd) in enumerate(
        zip(descr, wf.forwards)) if d["type"] == "moe"]
    routed = {labels["unit"]: child.value for labels, child in
              registry.get("veles_moe_routed_per_step").series()}
    assert {tag: routed[tag] for tag in sparse} == {
        tag: 4.0 * TINY["positions"] * TINY["top_k"] for tag in sparse}
    branches = {labels["branch"] for labels, _ in
                registry.get("veles_branch_loss").series()}
    assert branches == {"mtp"}
    bias = {labels["unit"]: child.value for labels, child in
            registry.get("veles_moe_select_bias_max").series()}
    assert all(bias[tag] > 0 for tag in sparse)
    # the default hook is there for every unit and publishes nothing
    norm = next(fwd for d, fwd in zip(descr, wf.forwards)
                if d["type"] == "rms_norm")
    assert norm.publish_stats(registry, "u99.none", {"x": 1}, {}) is None


@pytest.mark.parametrize("wrong", [None, "rate", "bias", "half"])
def test_one_step_of_the_trainer_is_the_references(wrong):
    """The comparison that decides ``correct`` in the benchmark's token
    cell, here in float32: one step of the trainer through
    ``train_class(skip=)`` (the builder's ``program_step``) against
    ``train_step`` of the reference; afterwards the workflow is as it
    was. A wrong optimizer's rate, a bias moved the wrong way and half
    the batch are told."""
    from benchmark.builders.moe_lm import program_step
    wf, descr = build(n_train=8, batch=4)
    optimizer = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95,
                 "epsilon": 1e-8, "warmup_steps": 0}
    if wrong == "rate":
        optimizer["learning_rate"] = 6e-4
    trainer = FusedTrainer(wf)
    host = host_params(wf)
    program, (tokens, labels) = program_step(trainer, descr, host,
                                             lambda line: None)
    if wrong == "half":
        tokens, labels = tokens[:2], labels[:2]
    if wrong == "bias":
        descr = [dict(d, bias_rate=-d["bias_rate"]) if d["type"] == "moe"
                 else d for d in descr]
    expected = ref.train_step(descr, host, tokens, labels, optimizer)
    numbers = ref.step_comparison(descr, program, expected)
    ok, report = ref.agreement(numpy.zeros(1), {
        "losses": numpy.zeros(1), "step": numbers})
    assert ok is (wrong is None), report
    if wrong is None:
        assert report["gradient_error"] < 1e-5
        assert max(report["loss_errors"].values()) < 1e-5
        assert report["routing_error"] == 0
    # the workflow put back: the same parameters, a fresh optimizer
    params, states = trainer.pull_params()
    for layer, values in zip(params, host):
        for name, value in values.items():
            numpy.testing.assert_array_equal(layer[name], value)
    assert all(float(s["t"]) == 0 for s in states if s)
