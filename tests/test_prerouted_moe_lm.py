"""The pre-routed sparse language model (``models/prerouted_moe_lm.py``,
SmallThinker-21BA3B's block) against its plain float32 reference
``benchmark/reference/prerouted_moe_lm.py``: the checks every
token-model family runs (``token_models.py``), and the family's own
mechanisms: global NoPE and window layers, a router placed before
attention, the step the benchmark compares, and the chunked overflow
of the dropless layer against its unbounded path."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.reference import prerouted_moe_lm as ref
from token_models import (  # noqa: F401 (the fixtures)
    Family, TokenModelChecks, build, float32_highest, gauge, host_params,
    model, random_state)
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.prerouted_moe_lm import (PUBLISHED, TINY,
                                               PreRoutedMoELMWorkflow)
from veles_tpu.nn import precision
from veles_tpu.nn.attention import GroupedAttentionForward
from veles_tpu.nn.moe import MoEForward
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer
from veles_tpu.train.step import StepContext


def published_facts(chain, tiny):
    """52 layers, global NoPE and window 1:3 by the published layout,
    every one an attention unit and a sparse unit that routes on that
    attention unit's input, softmax top-6 of 64 ReGLU experts of 768,
    no shared expert, no gate, an untied head; the tiny window no
    divisor of the sequence."""
    attention, sparse = chain[1:-2:2], chain[2:-2:2]
    assert len(attention) == len(sparse) == 52
    assert [a["window"] for a in attention[:4]] == [None, 4096, 4096, 4096]
    assert [a["rope_theta"] for a in attention[:4]] == [None] + [1.5e6] * 3
    assert sum(a["window"] is None for a in attention) == 13
    for a, s in zip(attention, sparse):
        assert s["route_from"] == a["name"] and s["type"] == "moe"
        assert (a["heads"], a["kv_heads"], a["head_dim"], a["gated"],
                a["eps"]) == (28, 4, 128, False, 1e-6)
        assert (s["n_experts"], s["top_k"], s["hidden"], s["scoring"],
                s["normalize"], s["scale"], s["shared_experts"],
                s["activation"], s["eps"]) == (
                    64, 6, 768, "softmax", True, 1.0, 0, "relu", 1e-6)
    assert "tied_to" not in chain[-1] and chain[0]["vocabulary"] == 151936
    assert TINY["positions"] % TINY["window"]


def configuration_facts(config, chain, parameters):
    """Layers 0..3 of 52 (one whole period of the published layout), 8
    of 64 experts, 18,992 vocabulary rows, one sequence of 16,384; the
    parameters of a layer's parts; the bound of the sparse layers."""
    positions = config["layers"][0]["positions"]
    sparse = config["layers"][2]
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"], positions) == (4, 8, 18992, 16384)
    assert config["sliding_window_layout"] == config["rope_layout"] == \
        list(PUBLISHED["layout"])
    assert config["published"]["moe_num_primary_experts"] == \
        PUBLISHED["n_experts"] == sparse["n_experts"]
    assert config["published"]["vocab_size"] == PUBLISHED["vocabulary"]
    assert config["held_here"]["parameters_a_layer"] == {
        "attention": 20974080, "sparse_unit": 47352320}
    assert parameters[1:3] == [20974080, 47352320]
    assert sparse["dispatch_rows"] == 2 * positions * 6 * 8 // 64


FAMILY = Family(
    workflow=PreRoutedMoELMWorkflow, published=PUBLISHED, reference=ref,
    units={"grouped_attention": ref.grouped_attention,
           "rms_norm": lambda d, p, x: ref.rms_norm(x, p["weights"],
                                                    d["eps"])},
    moe=lambda d, p, x, ctx, routed: ref.moe(d, p, x, ref.router_input(
        d, ctx.params_of(d["route_from"]), ctx.input_of(d["route_from"])),
        residual=not routed),
    shares=(64, 6, 8),
    # the core's output and one float32 statistic a row of every query
    # head; the sparse unit keeps nothing of its own: the router's
    # stream is a boundary the step keeps
    kept=lambda fwd, batch, size, core: batch * fwd.heads
    * size["positions"] * (size["head_dim"] * 4 + 4),
    eager=False, remat_exact=False,
    config="smallthinker-21ba3b-ep8share.json",
    cut=lambda config: dict(
        layout=PUBLISHED["layout"][:config["num_hidden_layers"]],
        vocabulary=config["vocab_size"],
        positions=config["layers"][0]["positions"],
        experts_held=(0, config["moe_num_primary_experts"]),
        dispatch_rows=config["layers"][2]["dispatch_rows"],
        # the benchmark's band on the untrained loss forced half the other
        # token cells' 0.006 (the file's ``assumed.head_initial_std``)
        head_stddev=0.003),
    config_keys={
        "hidden_size": "dim", "num_attention_heads": "heads",
        "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
        "sliding_window_size": "window", "rope_theta": "rope_theta",
        "moe_ffn_hidden_size": "expert_hidden",
        "moe_num_active_primary_experts": "top_k", "rms_norm_eps": "eps"},
    parameters=370547200, counts_selection_bias=False,
    published_facts=published_facts,
    configuration_facts=configuration_facts,
    # at ``a798bf1``, before the families' checks became one
    programs=(
        "57c99ef11dd0949d6744a7bb3987bfc33b0d4b54bcb0f4dd323835c30fbb59ae",
        "597ef819c6131125c7bfaa2ecd7151b8fa392c3be38c4885111d3aff66130294"))


class TestPreRouted(TokenModelChecks):
    family = FAMILY


def context(attention, p_attention, x_attention):
    """A step's context that holds ``attention``'s input and
    parameters, as the fused step's would."""
    ctx = StepContext([attention], [p_attention], None, True)
    ctx.inputs[attention.name] = x_attention
    return ctx


# -- the layers --------------------------------------------------------------

@pytest.mark.parametrize("windowed", [False, True])
def test_nope_and_window_layers_match_reference(windowed):
    """A global layer rotates nothing and sees every key before it, a
    window layer rotates the whole head and sees ``window`` keys; each
    against the reference, output and gradients, and the gauge says
    how many dims a head turns."""
    wf, descr = build(FAMILY)
    index = 1 + 2 * (1 if windowed else 0)
    fwd, d = wf.forwards[index], descr[index]
    assert (d["window"] is not None) == windowed
    params = {k: jnp.asarray(a.map_read())
              for k, a in fwd.param_arrays().items()}
    x = random_state(1)
    probe = random_state(2)
    y, grads = jax.value_and_grad(
        lambda p, x: jnp.sum(fwd.apply(p, x) * probe), (0, 1))(params, x)
    r_y, r_grads = jax.value_and_grad(
        lambda p, x: jnp.sum(ref.grouped_attention(d, p, x) * probe),
        (0, 1))(params, x)
    numpy.testing.assert_allclose(y, r_y, rtol=1e-5)
    for name in params:
        numpy.testing.assert_allclose(grads[0][name], r_grads[0][name],
                                      rtol=2e-4, atol=2e-6, err_msg=name)
    numpy.testing.assert_allclose(grads[1], r_grads[1], rtol=2e-4,
                                  atol=2e-6)
    assert gauge("veles_attention_rotary_dims")[fwd.name] == \
        (TINY["head_dim"] if windowed else 0)
    # the global layer is blind to position but through its mask: the
    # same keys in another order give its last query the same output
    if not windowed:
        swapped = x.at[:, 0].set(x[:, 1]).at[:, 1].set(x[:, 0])
        numpy.testing.assert_allclose(fwd.apply(params, swapped)[:, -1],
                                      fwd.apply(params, x)[:, -1],
                                      rtol=1e-5, atol=1e-6)


def test_a_rotary_fraction_of_0_rotates_nothing():
    """``rotary_fraction=0`` is a NoPE layer as ``rope_theta=None``."""
    args = dict(heads=4, kv_heads=2, head_dim=8, gated=False, block=None)
    none = GroupedAttentionForward(DummyLauncher(), rope_theta=None,
                                   **args)
    zero = GroupedAttentionForward(DummyLauncher(), rotary_fraction=0.0,
                                   **args)
    assert none.rotary_dims == zero.rotary_dims == 0
    rng = numpy.random.default_rng(0)
    params = {name: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
              for name, (shape, _) in none.param_shapes((2, 6, 16)).items()}
    x = random_state(3, positions=6, dim=16)
    numpy.testing.assert_allclose(none.apply(params, x),
                                  zero.apply(params, x), rtol=1e-6)


def sparse_layer(wf, descr, index):
    """The sparse unit at ``index``, the attention unit it routes on,
    their descriptors and parameters."""
    fwd, attention = wf.forwards[index], wf.forwards[index - 1]
    params = [{k: jnp.asarray(a.map_read())
               for k, a in unit.param_arrays().items()}
              for unit in (fwd, attention)]
    return fwd, attention, descr[index], params[0], params[1]


def test_reglu_experts_routed_before_attention_match_reference():
    """The sparse unit alone: ReGLU experts over its own normed input,
    weighed by a router that reads the attention unit's normed input
    out of the step's context; output and every gradient, the
    attention unit's gain and the stream before it included."""
    wf, descr = build(FAMILY)
    fwd, attention, d, p, p_attn = sparse_layer(wf, descr, 2)
    assert fwd.activation == "relu" and fwd.route_from == attention.name
    before, after = random_state(4), random_state(5)
    probe = random_state(6)

    def mine(p, p_attn, before, after):
        ctx = context(attention, p_attn, before)
        y, stats = fwd.apply_step(p, after, ctx)
        return jnp.sum(y * probe), stats

    def theirs(p, p_attn, before, after):
        a = ref.router_input(d, p_attn, before)
        return jnp.sum(ref.moe(d, p, after, a) * probe)

    (y, stats), grads = jax.value_and_grad(mine, (0, 1, 2, 3),
                                           has_aux=True)(
        p, p_attn, before, after)
    r_y, r_grads = jax.value_and_grad(theirs, (0, 1, 2, 3))(
        p, p_attn, before, after)
    numpy.testing.assert_allclose(y, r_y, rtol=1e-5)
    for mine_g, their_g, what in zip(grads, r_grads,
                                     ("sparse", "attention", "before",
                                      "after")):
        for name, g in (mine_g.items() if isinstance(mine_g, dict)
                        else [("", mine_g)]):
            r = their_g[name] if name else their_g
            if name == "select_bias":
                assert not numpy.asarray(g).any()
                continue
            numpy.testing.assert_allclose(
                g, r, rtol=2e-4, atol=2e-6 * float(jnp.abs(r).max()),
                err_msg="%s %s" % (what, name))
    # the router reads the gain of ``norm`` of the attention unit and
    # nothing else of it
    assert numpy.abs(grads[1]["norm"]).max() > 0
    assert not any(numpy.asarray(grads[1][k]).any()
                   for k in grads[1] if k != "norm")
    # ReGLU, not SwiGLU: the same weights under silu give another layer
    silu = MoEForward(DummyLauncher(), **dict(
        {k: v for k, v in d.items() if k not in ("type", "remat", "name")},
        activation="silu"))
    silu.name = fwd.name
    assert not numpy.allclose(
        silu.apply_step(p, after, context(attention, p_attn, before))[0],
        fwd.apply_step(p, after, context(attention, p_attn, before))[0])
    assert numpy.asarray(stats["expert_counts"]).sum() == \
        before.shape[0] * before.shape[1] * TINY["top_k"]


def test_the_router_gradient_reaches_the_attention_input_and_gain():
    """The routing weights alone, differentiated: they move the
    attention unit's gain and the stream before attention, and not the
    sparse unit's own (post-attention) gain nor its input."""
    wf, descr = build(FAMILY)
    fwd, attention, d, p, p_attn = sparse_layer(wf, descr, 2)
    before, after = random_state(7), random_state(8)
    probe = jnp.asarray(numpy.random.default_rng(9).normal(
        size=(before.shape[0] * before.shape[1], TINY["top_k"])),
        jnp.float32)

    def weights_of(p, p_attn, before, after):
        h = after.reshape(-1, after.shape[-1])
        r = fwd.router_input(h, after, context(attention, p_attn, before))
        return jnp.sum(fwd.route(p, r)[1] * probe)

    grads = jax.grad(weights_of, (0, 1, 2, 3))(p, p_attn, before, after)
    r_grads = jax.grad(lambda p, p_attn, before: jnp.sum(ref.route(
        d, p, ref.router_input(d, p_attn, before))[1] * probe),
        (0, 1, 2))(p, p_attn, before)
    for mine, theirs in ((grads[1]["norm"], r_grads[1]["norm"]),
                         (grads[2], r_grads[2]),
                         (grads[0]["weights"], r_grads[0]["weights"])):
        assert numpy.abs(theirs).max() > 0
        numpy.testing.assert_allclose(
            mine, theirs, rtol=2e-4, atol=2e-6 * float(
                jnp.abs(theirs).max()))
    assert not numpy.asarray(grads[0]["norm"]).any()
    assert not numpy.asarray(grads[3]).any()


def test_a_router_placed_before_attention_needs_the_steps_context():
    wf, descr = build(FAMILY)
    fwd, _, _, p, _ = sparse_layer(wf, descr, 2)
    with pytest.raises(ValueError, match="StepContext"):
        fwd.apply(p, random_state(1))
    with pytest.raises(ValueError, match="dropless"):
        MoEForward(DummyLauncher(), activation="relu")
    with pytest.raises(ValueError, match="activation"):
        MoEForward(DummyLauncher(), capacity_factor=None,
                   activation="gelu")


def test_a_train_step_agrees_by_the_benchmarks_comparison():
    """One step of the train segment, laid out as the benchmark lays a
    program's step out, against the reference's step by the
    reference's own comparison, at float32: every number near 0,
    routing included; a router fed the post-attention state does
    not."""
    from benchmark.builders.moe_lm import last_minibatch, program_step
    wf, descr = build(FAMILY, sizes={"remat": True})
    initial = host_params(wf)
    trainer = FusedTrainer(wf)
    optimizer = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95,
                 "epsilon": 1e-8, "weights_decay": 0.0,
                 "warmup_steps": 0}
    tokens, labels = last_minibatch(wf.loader)
    expected = ref.train_step(descr, initial, tokens, labels, optimizer)
    program, served = program_step(trainer, descr, initial, print)
    numpy.testing.assert_array_equal(served[0], tokens)
    step = ref.step_comparison(descr, program, expected)
    assert step["gradient_error"] < 1e-4, step
    assert step["router_gradient_error"] < 1e-4, step
    assert step["update_scale_error"] < 1e-4, step
    assert step["routing_error"] == 0.0
    assert step["routed_per_token"] == [TINY["top_k"]] * 3
    # the wrong router input: the layer's own state after attention
    wrong = [dict(d, route_from=descr[i]["name"]) if d["type"] == "moe"
             else d for i, d in enumerate(descr)]
    wrong_step = ref.train_step(wrong, initial, tokens, labels, optimizer)
    # at the initial weights attention adds little to the stream, so
    # the wrong input routes nearly as the right one and moves the
    # routers and the gains nearly alike: at float32 both still tell
    # it apart from the program, by orders of magnitude
    wrong_step = ref.step_comparison(descr, wrong_step, expected)
    assert wrong_step["routing_error"] > 0
    wrong_error = wrong_step["router_gradient_error"]
    assert wrong_error > 1e-2 and wrong_error > 100 * step[
        "router_gradient_error"]


# -- the overflow in chunks of the bound --------------------------------------

#: a bound of 8 rows where a step routes 2 x 16 tokens x 3 = 96 to the
#: 16 experts all held: 12 chunks, and a routing that sends every token
#: to expert 5 (and two more), 32 rows for one expert
CHUNK_BOUND = 8


@pytest.mark.parametrize("routing", ["spread", "one_expert_takes_all"])
@pytest.mark.parametrize("policy", ["float32", "bfloat16"])
def test_the_overflow_in_chunks_equals_the_unbounded_path(routing, policy):
    """Past its bound the sparse unit runs its rows in chunks of the
    bound, one after the other: output and every gradient are the
    unbounded path's (bfloat16: each chunk's float32 sums are added
    and rounded once, as the one pass rounds its sum over every row),
    the stat counts the chunks past the first, and under the bound
    nothing of it runs."""
    precision.set_policy(policy)
    try:
        kwargs = dict(n_experts=16, hidden=8, capacity_factor=None,
                      top_k=3, scoring="softmax", normalize=True,
                      eps=1e-6, activation="relu")
        rng = numpy.random.default_rng(12)
        dim = 16
        params = {
            "weights": rng.normal(size=(dim, 16)) / 4,
            "norm": 1 + 0.1 * rng.normal(size=dim),
            "select_bias": numpy.zeros(16),
            "gate": rng.normal(size=(16, dim, 8)) / 4,
            "up": rng.normal(size=(16, dim, 8)) / 4,
            "down": rng.normal(size=(16, 8, dim)) / 3}
        if routing == "one_expert_takes_all":
            params["select_bias"][5] = 9.0
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        dtype = precision.get_policy().keep_dtype
        x = random_state(13, dim=dim).astype(dtype)
        probe = random_state(14, dim=dim)
        outputs = {}
        # and 8 of the 16 held under a bound of 95 rows, one under the
        # 96 possible, which a spread routing's ~48 never reach
        for bound, held in ((None, 16), (CHUNK_BOUND, 16), (95, 8)):
            fwd = MoEForward(DummyLauncher(), name="moe2",
                             dispatch_rows=bound, **dict(
                                 kwargs, experts_held=(0, held)))

            @jax.jit
            def run(p, x, fwd=fwd):
                def fn(p, x):
                    y, stats = fwd.apply_step(p, x, None)
                    return jnp.sum(y.astype(jnp.float32) * probe), (
                        y, stats)
                return jax.value_and_grad(fn, (0, 1), has_aux=True)(p, x)
            outputs[bound] = run(params if held == 16 else dict(params, **{
                k: params[k][:held] for k in ("gate", "up", "down")}), x)
    finally:
        precision.set_policy("float32")
    (_, (y, stats)), grads = outputs[CHUNK_BOUND]
    (_, (r_y, r_stats)), r_grads = outputs[None]
    counts = numpy.asarray(stats["expert_counts"])
    rows = x.shape[0] * x.shape[1] * 3
    assert counts.sum() == rows
    if routing == "one_expert_takes_all":
        assert counts[5] == x.shape[0] * x.shape[1]
    assert int(stats["overflow_chunks"]) == rows // CHUNK_BOUND - 1 >= 2
    assert "overflow_chunks" not in r_stats
    if routing == "spread":
        (_, (_, under)), _ = outputs[95]
        assert int(under["overflow_chunks"]) == 0
        assert numpy.asarray(under["expert_counts"])[:8].sum() < 95
    close = (dict(rtol=2e-5, atol=2e-6) if policy == "float32"
             else dict(rtol=2.0 ** -7, atol=2.0 ** -7))
    numpy.testing.assert_allclose(numpy.asarray(y, numpy.float32),
                                  numpy.asarray(r_y, numpy.float32), **close)
    for name, mine, theirs in [("x", grads[1], r_grads[1])] + [
            (k, grads[0][k], r_grads[0][k]) for k in sorted(params)]:
        mine, theirs = (numpy.asarray(a, numpy.float32)
                        for a in (mine, theirs))
        if name == "select_bias":
            assert not mine.any()
            continue
        assert theirs.any(), name
        numpy.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-6,
                                      err_msg=name)


def test_the_overflow_gauge_publishes_the_chunks_past_the_first():
    fwd = MoEForward(DummyLauncher(), name="moe9", capacity_factor=None,
                     n_experts=4, dispatch_rows=8)
    fwd.publish_stats(get_registry(), "u09.moe9", {
        "expert_counts": numpy.ones((3, 4)),
        "overflow_chunks": numpy.array([0, 2, 1])},
        {"select_bias": jnp.zeros(4)})
    assert gauge("veles_moe_overflow_chunks")["u09.moe9"] == 2.0
