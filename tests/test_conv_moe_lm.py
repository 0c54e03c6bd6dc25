"""The language model of gated short-convolution and grouped-query
attention blocks with sparse experts and one table for embedding and
head (``models/conv_moe_lm.py``) against its plain float32 reference
``benchmark/reference/conv_moe_lm.py``: the checks every token-model
family runs (``token_models.py``), and the family's own mechanisms:
the short convolution against a banded matrix, the tied table's two
readers, the router at the published width."""

import math
import re

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.reference import conv_moe_lm as ref
from token_models import (  # noqa: F401 (the fixtures)
    Family, TokenModelChecks, batch_of, build, float32_highest, gauge,
    model, objective, random_state)
from veles_tpu import remat
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.models.conv_moe_lm import (PUBLISHED, TINY,
                                          ConvMoELMWorkflow)
from veles_tpu.nn.attention import GroupedAttentionForward
from veles_tpu.nn.short_conv import ShortConvForward, causal_taps


def published_facts(chain, tiny):
    """The published list block by block: 18 convolution and 6
    attention blocks in the order no period gives, the first two
    blocks dense by count, a tied head; the tiny preset's blocks too
    in a list no period gives, dense blocks of each kind."""
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
    mixers = [d["type"] for d in tiny[1:-2:2]]
    gaps = numpy.diff([i for i, t in enumerate(mixers)
                       if t == "grouped_attention"])
    assert len(set(gaps)) > 1 or len(gaps) < 2 and mixers[0] != mixers[-1]
    assert TINY["positions"] % TINY["taps"] and TINY["head_dim"] < 128
    assert {mixers[i] for i in range(TINY["dense_layers"])} == \
        {"short_conv", "grouped_attention"}


def configuration_facts(config, chain, parameters):
    """Blocks 1..5 of the published list (the leading dense blocks
    counted once), 8 of 32 experts, 16,384 vocabulary rows; the
    selection bias apart from the parameters; the table once."""
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["held_here"]["blocks"]) == (
                5, 8, 16384, [1, 5])
    assert config["published"]["layer_types"] == \
        list(PUBLISHED["layer_types"]) == config["layer_types"]
    assert [d["type"] for d in chain[1:-2:2]] == [
        "short_conv", "grouped_attention", "short_conv", "short_conv",
        "short_conv"]
    assert [d["type"] for d in chain[2:-2:2]] == \
        ["gated_mlp"] + ["moe"] * 4
    assert config["published"]["num_experts"] == PUBLISHED["n_experts"] \
        == chain[4]["n_experts"]
    held = config["held_here"]
    biases = sum(d["n_experts"] for d in chain if d["type"] == "moe")
    assert biases == 128 and "128" in held["and_selection_bias"]
    parts = held["parameters_by_part"]
    assert parameters[0] == parts["table"] == 33554432
    assert parameters[1] + parameters[2] == \
        parts["dense_conv_block"] == 60827648
    assert parameters[3] + parameters[4] == \
        parts["sparse_attention_block"] == 98635904
    assert parameters[5] + parameters[6] == \
        parts["sparse_conv_block"] == 104933376
    assert parameters[-1] == 0


def cut(config):
    blocks = config["held_here"]["blocks"]
    return dict(
        layer_types=PUBLISHED["layer_types"][blocks[0]:blocks[1] + 1],
        dense_layers=PUBLISHED["dense_layers"] - blocks[0],
        vocabulary=config["vocab_size"],
        positions=config["layers"][0]["positions"],
        experts_held=(0, config["num_experts"]),
        dispatch_rows=config["layers"][4]["dispatch_rows"])


FAMILY = Family(
    workflow=ConvMoELMWorkflow, published=PUBLISHED, reference=ref,
    units=ref.UNITS,
    moe=lambda d, p, x, ctx, routed: ref.moe(d, p, x,
                                             residual=not routed),
    shares=(32, 4, 8),
    # a convolution unit keeps nothing across its rematerialization, an
    # attention unit its core's output and one statistic a row
    kept=lambda fwd, batch, size, core: batch * fwd.heads
    * size["positions"] * (size["head_dim"] * 4 + 4)
    if isinstance(fwd, GroupedAttentionForward) else 0,
    config="lfm2-8b-a1b-ep4share.json", cut=cut,
    config_keys={
        "hidden_size": "dim", "num_attention_heads": "heads",
        "num_key_value_heads": "kv_heads", "conv_L_cache": "taps",
        "intermediate_size": "dense_hidden",
        "moe_intermediate_size": "expert_hidden",
        "num_experts_per_tok": "top_k", "rope_theta": "rope_theta",
        "norm_eps": "eps"},
    parameters=507820160, counts_selection_bias=False, bias_atol=1e-9,
    published_facts=published_facts,
    configuration_facts=configuration_facts,
    # at ``a798bf1``, before the families' checks became one
    programs=(
        "9a4f7aeed115966cbeac5ca764ea18ce05e88aa0da564343f9a2e107e3019cf0",
        "cbc2f05e14db91d6eb7f93db0f863ec12752a99eddc0822db51b00ded9843d72"))


class TestConv(TokenModelChecks):
    family = FAMILY


def mat(rng, *shape):
    return jnp.asarray(rng.normal(size=shape) / numpy.sqrt(shape[-2]),
                       jnp.float32)


# -- the unit against the reference -------------------------------------------

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



# -- the tied table -----------------------------------------------------------

def test_an_unknown_block_kind_is_refused():
    with pytest.raises(KeyError):
        build(FAMILY, sizes={"layer_types": ("conv", "linear_attention")})


def test_the_eager_head_reads_the_table_as_it_is(model):
    """The head reads the embedding's table and owns nothing; a table
    that moved is read anew by the eager head."""
    wf, descr, trainer, host = model
    head, table = wf.forwards[-1], wf.forwards[0].weights
    assert head.tied_to == "embedding" and not head.has_weights
    assert host[-1] == {} and head.param_values() == {}
    tokens, _ = batch_of(wf, trainer, VALIDATION)
    wf.loader.minibatch_data.map_invalidate()[...] = tokens
    for fwd in wf.forwards:
        fwd.run()
    expected = jax.jit(lambda p, t: ref.logits(descr, p, t))(
        host, jnp.asarray(tokens))
    kept = numpy.array(table.map_read())
    table.map_invalidate()[...] = 2 * kept
    head.run()
    numpy.testing.assert_allclose(
        head.output.map_read(), jax.nn.softmax(2 * expected, -1),
        rtol=2e-4, atol=1e-7)
    table.map_invalidate()[...] = kept


def test_the_tables_gradient_is_its_two_readers(model):
    """THE TABLE'S GRADIENT is the sum of its two readers', the
    embedding's rows and the head's columns, each of which is most of
    it somewhere; the head has none of its own; the comparison on the
    chip tells a step that drops a reader."""
    wf, descr, trainer, host = model
    tokens, labels = batch_of(wf, trainer, TRAIN)
    params, _ = trainer.pull_params()
    grads = jax.jit(jax.grad(lambda p: objective(trainer, tokens, labels)(
        p)[0]))(params)
    r_grads = ref.gradients(descr, host, tokens, labels)
    assert grads[-1] == {} and gauge("veles_head_tied")[
        wf.forwards[-1].name] == 1.0
    # the two readers apart: the head given a copy of the table
    table = jnp.asarray(host[0]["weights"])
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


def test_a_snapshot_holds_the_table_once():
    """The head brings no array of its own into a snapshot, and the
    resumed head reads the resumed embedding's."""
    from veles_tpu.snapshotter import dump_workflow, load_workflow
    first, _ = build(FAMILY)
    resumed = load_workflow(dump_workflow(first))
    resumed.workflow = DummyLauncher()
    resumed.initialize(device=Device(backend="cpu"))
    head, embedding = resumed.forwards[-1], resumed.forwards[0]
    assert head.tied_to == "embedding" and not head.param_arrays()
    assert head.weights.mem is None
    assert head.table is embedding.weights
    for wf in (first, resumed):
        ends = [wf.forwards[0].param_arrays(), wf.forwards[-1].param_arrays()]
        assert [sorted(arrays) for arrays in ends] == [["weights"], []]
        assert wf.forwards[-1].weights.mem is None


def test_export_holds_the_table_once():
    """The two ends of a token chain as package entries: the
    embedding brings the table, the tied head a name and no array."""
    from veles_tpu.export.package import _MemberWriter, _unit_entry
    wf, _ = build(FAMILY)
    writer = _MemberWriter("float32")
    first = _unit_entry(wf.forwards[0], writer)
    last = _unit_entry(wf.forwards[-1], writer)
    assert first["data"]["weights"].startswith("@")
    assert last["data"] == {"vocabulary": TINY["vocabulary"],
                            "tied_to": "embedding"}
    assert len(writer.members) == 1


# -- routing at the published width -------------------------------------------

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
