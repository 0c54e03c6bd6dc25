"""The latent-attention, sparse-expert language model with multi-token
prediction (``models/latent_moe_lm.py``) against its plain float32
reference ``benchmark/reference/moe_lm.py``: the checks every
token-model family runs (``token_models.py``), and the family's own
mechanisms: the units, the router and its selection bias, the dropless
layer's combine, Adam's warm-up, what a sweep publishes and the step
the benchmark compares."""

import contextlib

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.reference import moe_lm as ref
from token_models import (  # noqa: F401 (the fixtures)
    Family, TokenModelChecks, build, float32_highest, gauge, host_params,
    keeping, model, random_state)
from veles_tpu import remat
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.latent_moe_lm import (PUBLISHED, TINY,
                                            LatentMoELMWorkflow)
from veles_tpu.nn import precision
from veles_tpu.nn.mlp import gated_mlp
from veles_tpu.nn.moe import MoEForward
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.train import FusedTrainer


def published_facts(chain, tiny):
    """47 blocks, the first dense, and the MTP module beside them: a
    merge of shift 1 weighed 0.3, one block and its norm, read by the
    one head."""
    types = [d["type"] for d in chain]
    assert types.count("latent_attention") == 48
    assert types.count("gated_mlp") == 1 and types.count("moe") == 47
    assert [(d["type"], d.get("branch")) for d in chain[-6:]] == [
        ("rms_norm", None), ("token_merge", "mtp"),
        ("latent_attention", "mtp"), ("moe", "mtp"), ("rms_norm", "mtp"),
        ("vocabulary_head", None)]
    merge = chain[types.index("token_merge")]
    assert (merge["shift"], merge["objective_weight"]) == (1, 0.3)
    sparse = chain[types.index("moe")]
    assert (sparse["n_experts"], sparse["top_k"], sparse["hidden"],
            sparse["scale"], sparse["shared_experts"], sparse["scoring"],
            sparse["normalize"]) == (64, 4, 1536, 1.8, 1, "sigmoid", True)


def configuration_facts(config, chain, parameters):
    """Five blocks, experts 0..7, 19,360 vocabulary rows; the MTP
    module's weight is the file's assumption; a latent attention and
    its block's norm."""
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    merge = next(d for d in chain if d["type"] == "token_merge")
    assert merge["objective_weight"] == config["assumed"][
        "mtp_loss_weight_lambda"]
    assert parameters[1] == 21759232 + 2048


FAMILY = Family(
    workflow=LatentMoELMWorkflow, published=PUBLISHED, reference=ref,
    units=ref.UNITS,
    moe=lambda d, p, x, ctx, routed: ref.moe(d, p, x, shared=not routed),
    shares=(16, 4, 4),
    # this chip's share in most checks: experts 2..5 of 8
    sizes={"experts_held": (2, 4)},
    # the core's output in the compute dtype and a float32 statistic
    # (two on the fused path) for every row of every head
    kept=lambda fwd, batch, size, core: batch * size["heads"]
    * size["positions"] * (size["v_dim"] * 4
                           + 4 * (2 if core == "fused" else 1)),
    # sizes the fused kernel's tiling admits, as small as it admits them
    fused_sizes=dict(heads=1, qk_nope_dim=64, qk_rope_dim=64, v_dim=128,
                     positions=256, block=128),
    config="glm47flash-ep8share.json",
    cut=lambda config: dict(
        blocks=config["num_hidden_layers"], vocabulary=config["vocab_size"],
        positions=config["layers"][0]["positions"],
        experts_held=(0, config["n_routed_experts"]),
        dispatch_rows=next(d["dispatch_rows"] for d in config["layers"]
                           if d["type"] == "moe")),
    config_keys={
        "hidden_size": "dim", "num_attention_heads": "heads",
        "q_lora_rank": "q_rank", "kv_lora_rank": "kv_rank",
        "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
        "v_head_dim": "v_dim", "rope_theta": "rope_theta",
        "intermediate_size": "dense_hidden",
        "moe_intermediate_size": "expert_hidden",
        "num_experts_per_tok": "top_k", "routed_scaling_factor": "scale",
        "n_shared_experts": "shared_experts",
        "first_k_dense_replace": "dense_layers", "rms_norm_eps": "eps"},
    parameters=706518848, bias_atol=1e-7,
    published_facts=published_facts,
    configuration_facts=configuration_facts,
    # taken at ``31b8db1``, pinned since
    programs=(
        "13133eda9b325eddb336319dc520c7424365fd4016a6d1b82bc5fc753edfbae5",
        "c2c8f3b9ac9298200d7fca68e5539dac18f77a8341ff7e46a37f240df23ff72b"))


class TestLatent(TokenModelChecks):
    family = FAMILY


def unit_of(wf, descr, ltype, nth=0):
    index = [i for i, d in enumerate(descr) if d["type"] == ltype][nth]
    fwd = wf.forwards[index]
    return fwd, descr[index], {
        k: jnp.asarray(a.map_read()) for k, a in fwd.param_arrays().items()}


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
    loss, wrong = head.token_losses(hp, x, targets, contextlib.nullcontext)
    numpy.testing.assert_allclose(
        loss, ref.head_losses(hp["weights"], x, targets), rtol=2e-5)
    assert wrong.shape == targets.shape and wrong.dtype == jnp.bool_
    # the branch leaves the main path at the final norm's output
    norm = wf.forwards[wf.forwards.index(merge) - 1]
    assert merge.input is norm.output and norm.branch is None


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
    # past the bound the overflow runs in chunks (PR 39): an expert
    # matrix's gradient is then the chunks' float32 sums added, rounded
    # to bfloat16 once, as one pass rounds its sum over every row; the
    # sums are added in another order, which moves the odd sum across
    # a bfloat16 rounding boundary: one unit in the last place
    chunked = policy == "bfloat16" and dispatch_rows is not None \
        and routed > dispatch_rows
    for name, mine, theirs in [("x", grads[1], r_grads[1])] + [
            (k, grads[0][k], r_grads[0][k]) for k in sorted(params)]:
        assert numpy.asarray(theirs).any() or name == "select_bias", name
        if chunked and name in ("gate", "up", "down"):
            mine, theirs = (numpy.asarray(a, numpy.float32)
                            for a in (mine, theirs))
            off = ~numpy.isclose(mine, theirs, rtol=2e-4, atol=2e-5)
            assert off.mean() <= 1e-3, name
            assert (numpy.abs(mine - theirs)[off]
                    <= 2.0 ** -7 * numpy.abs(theirs)[off]).all(), name
            continue
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
    rows forward on each path, the dispatch and the combine, each with
    the other's kind as its transpose in the gradient; the gauge says
    how many rows that is."""
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
    # forward only, the overflow is the same code at ``full`` rows
    assert moved_rows(forward, OVER, COMBINE_DIM) == (1, 1)
    # the gradient: the bounded path's pair, the dispatch gather once
    # more (the recomputed forward needs no combine: it is linear) and
    # the two transposes; since PR 39 the overflow runs in chunks of as
    # many rows: its forward chunk, and in its backward a chunk's rows
    # gathered again, the combine's transpose (a gather) and the rows'
    # gradient scattered into one accumulator
    assert moved_rows(step, OVER, COMBINE_DIM) == (3 + 1 + 2, 2 + 1 + 1)
    assert gauge("veles_moe_combine_rows")["moe4"] == float(OVER)


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


@pytest.mark.parametrize("name", ["MoEForward", "GatedMLPForward"])
def test_a_unit_that_keeps_nothing_lowers_as_under_a_plain_checkpoint(
        name):
    """The one policy of :func:`veles_tpu.remat.checkpoint` changes
    nothing for a unit that names nothing: the gradient lowers to the
    text a plain ``jax.checkpoint`` gives."""
    wf, _ = build(FAMILY)
    texts = []
    for wrap in (jax.checkpoint, keeping):
        grad, params, x = unit_gradient(wf, name, wrap)
        texts.append(jax.jit(grad).lower(params, x).as_text())
    assert "dot_general" in texts[0] and texts[0] == texts[1]


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
    wf, descr = build(FAMILY)
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
    wf, descr = build(FAMILY, n_train=8, batch=4)
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
