"""The language model of grouped-query attention under a learned
selection of keys (an index and its KL objective) with sparse experts
(``models/indexed_moe_lm.py``) against its plain float32 reference
``benchmark/reference/indexed_moe_lm.py``: the checks every
token-model family runs (``token_models.py``), and the family's own
mechanism: the index's own term of the objective and its parameters,
what a rematerialized unit keeps of its search, the router at the
published width. The selected core is ``test_select_attention.py``'s."""


import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.reference import indexed_moe_lm as ref
from test_select_attention import INDEX, unit_and_reference, unit_objective
from token_models import (  # noqa: F401 (the fixtures)
    Family, TokenModelChecks, batch_of, build, float32_highest, keeping,
    model, random_state)
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN
from veles_tpu.models.indexed_moe_lm import (PUBLISHED, TINY,
                                             IndexedMoELMWorkflow)
from veles_tpu.nn.attention import GroupedAttentionForward
from veles_tpu.parallel import sequence
from veles_tpu.train.step import StepContext


def published_facts(chain, tiny):
    """48 blocks of one kind at the published sizes."""
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


def configuration_facts(config, chain, parameters):
    """The blocks it holds, experts 0..15, 18,992 vocabulary rows, one
    sequence of 8,192; the index as the published ``sa_config``; the
    parameters of a layer's parts."""
    assert (config["num_experts"], config["vocab_size"],
            config["layers"][0]["positions"]) == (16, 18992, 8192)
    assert 4 <= config["num_hidden_layers"] <= 6
    sa = config["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["indexer_num_kv_heads"], sa["topk"]) == (
        PUBLISHED["index_heads"], PUBLISHED["index_head_dim"], 1,
        PUBLISHED["index_top_k"])
    per_layer = config["held_here"]["parameters_a_layer"]
    assert (per_layer["attention"], per_layer["index"],
            per_layer["router_norm_and_16_experts"]) == (
        18876672, 2261120, 75761792)


FAMILY = Family(
    workflow=IndexedMoELMWorkflow, published=PUBLISHED, reference=ref,
    units={"grouped_attention": lambda d, p, x: ref.selected_attention(
               d, p, x)[0],
           "moe": ref.moe,
           "rms_norm": lambda d, p, x: ref.rms_norm(x, p["weights"],
                                                    d["eps"])},
    moe=lambda d, p, x, ctx, routed: ref.moe(d, p, x, shared=not routed),
    logits=lambda d, p, tokens: ref.states(d, p, tokens) @ p[-1]["weights"],
    shares=(128, 8, 16),
    # the core's output, a float32 statistic a row of every query head
    # and the searched blocks' masks, a byte a pair: blocks of 8
    # queries, both ending past top_k 6 and so both searched, 8 x 8 and
    # 8 x 16 pairs a sequence
    kept=lambda fwd, batch, size, core: batch * (
        fwd.heads * size["positions"] * (size["head_dim"] * 4 + 4)
        + 8 * 8 + 8 * 16),
    config="keye-vl2-ep8share.json",
    cut=lambda config: dict(
        blocks=config["num_hidden_layers"], vocabulary=config["vocab_size"],
        positions=config["layers"][0]["positions"],
        experts_held=(0, config["num_experts"]), dispatch_rows=16384),
    config_keys={
        "hidden_size": "dim", "num_attention_heads": "heads",
        "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
        "moe_intermediate_size": "expert_hidden",
        "num_local_experts": "n_experts", "num_experts_per_tok": "top_k",
        "rope_theta": "rope_theta"},
    parameters=465391616, term_rtol=2e-4,
    published_facts=published_facts,
    configuration_facts=configuration_facts,
    # taken at ``96a2645``, pinned since
    programs=(
        "457bac576ad5bdad37a4344263759d9f8e9c55c020b70c5b34ffefd04fb6e688",
        "394db08d34d1b5b793787161de4a536083775e3df56111504492ca52ffe8c6f2"))


class TestIndexed(TokenModelChecks):
    family = FAMILY


# -- the unit's own objective -------------------------------------------------

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



# -- the whole model through the trainer -----------------------------------

def index_terms(stats):
    """``{unit tag: L_I}`` of a step's stats."""
    return {tag: unit["index_loss"] for tag, unit in stats.items()
            if "index_loss" in unit}


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



def test_rematerialized_unit_runs_its_core_and_its_search_once():
    """What the selected core keeps is its output, its row statistics
    and the searched masks: the rematerialized unit's gradient makes
    the six projections (q, k, v and the index's three) a second time
    and nothing else: no product of the core or of the index scores,
    and the search (its tie-break's running count) once a searched
    block, as without ``remat``."""
    wf, _ = build(FAMILY)
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



def test_the_index_key_norm_starts_as_the_identity():
    """``gamma`` 1, ``beta`` 0; the q/k gains 1; the index's matrices
    filled as the others."""
    wf, _ = build(FAMILY)
    arrays = wf.forwards[1].param_arrays()
    assert set(arrays) == {"norm", "q", "k", "v", "o", "q_norm",
                           "k_norm"} | set(INDEX)
    for name in ("q_norm", "k_norm", "index_norm_gain"):
        assert (arrays[name].map_read() == 1).all()
    assert (arrays["index_norm_bias"].map_read() == 0).all()
    for name in ("index_q", "index_k", "index_w"):
        assert 0.5 * 0.02 < arrays[name].map_read().std() < 2 * 0.02



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

