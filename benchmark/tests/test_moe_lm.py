"""The token-model family of the benchmark: ``flops_lm.py`` pinned to
the hand-worked numbers of PERF.md section 4, the published
configuration's file against the program's own shapes, the harness
rehearsed on the CPU at a tiny size, and the reader ``trace_lm`` on
names the program emits."""

import ast
import json
import math
import os
import shutil
import time

import pytest

from benchmark import flops_lm, harness

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
ROOT = os.path.dirname(HOME)
CELL = "glm47flash-ep8share.pretrain4k"
SPEC = harness.load_json(ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(HOME, "configs", "glm47flash-ep8share.json")
LAYERS = CONFIG["layers"]
PEAKS = harness.load_json(HOME, "peaks.json")["devices"]["TPU v5 lite"]
MFLOP = 1e6


def by_type(ltype):
    return [c for c in flops_lm.layer_costs(LAYERS) if c["type"] == ltype]


# -- the arithmetic ----------------------------------------------------------

def test_a_sparse_block_is_114_mflop_a_token():
    """MLA projections 43.5, core 42.0, shared 18.9, routed 9.4 at
    their expectation (4 x 8 / 64 experts a token), router 0.3."""
    attention, sparse = by_type("latent_attention")[0], by_type("moe")[0]
    assert attention["parts"]["proj"] == 2.0 * 21757952
    assert attention["parts"]["proj"] / MFLOP == pytest.approx(43.5, abs=0.05)
    assert attention["parts"]["core"] / MFLOP == pytest.approx(42.0, abs=0.1)
    assert sparse["parts"]["shared"] / MFLOP == pytest.approx(18.9, abs=0.05)
    assert sparse["parts"]["experts"] / MFLOP == pytest.approx(9.4, abs=0.05)
    assert sparse["parts"]["router"] / MFLOP == pytest.approx(0.3, abs=0.05)
    block = sum(attention["parts"].values()) + sum(sparse["parts"].values())
    assert block / MFLOP == pytest.approx(114.0, abs=0.2)


def test_the_step_is_23_5_tflop():
    """8,192 tokens: the dense block, four sparse ones, the MTP module
    (merge and one block), the head twice; three times the forward."""
    assert by_type("gated_mlp")[0]["parts"]["mlp"] == 2.0 * 3 * 2048 * 10240
    assert by_type("vocabulary_head")[0]["passes"] == 2
    assert by_type("token_merge")[0]["parts"]["merge"] == 2.0 * 4096 * 2048
    per_sample = flops_lm.train_flops_per_sample(LAYERS)
    assert per_sample == 3.0 * 4096 * flops_lm.forward_flops_per_token(LAYERS)
    step = per_sample * CONFIG["batch"]
    assert step / 1e12 == pytest.approx(23.5, abs=0.05)
    assert step / PEAKS["bf16_flops_per_s"] == pytest.approx(0.12, abs=0.005)


def test_the_kernels_floors():
    sparse = next(d for d in LAYERS if d["type"] == "moe")
    # 4,096 rows, the expectation: compute-bound; no row: the weights
    # must still be read twice and their gradient written
    seconds, bound = flops_lm.expert_gemm_floor_s(sparse, 2048, 4096, PEAKS)
    assert bound == "compute"
    assert seconds == pytest.approx(
        3 * 4096 * 6 * 2048 * 1536 / 197e12)
    seconds, bound = flops_lm.expert_gemm_floor_s(sparse, 2048, 0, PEAKS)
    assert bound == "memory"
    assert seconds == pytest.approx(8 * 3 * 2048 * 1536 * 8 / 819e9)
    attention = next(d for d in LAYERS if d["type"] == "latent_attention")
    seconds, bound = flops_lm.attention_core_floor_s(
        attention, 4096, 2, PEAKS)
    assert bound == "compute"
    assert seconds == pytest.approx(
        3 * 8192 * 0.5 * 2 * 4096 * 20 * 512 / 197e12)


# -- the configuration's file ----------------------------------------------

def test_published_widths_are_unchanged():
    catalog_widths = {
        "hidden_size": 2048, "num_attention_heads": 20,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256,
        "intermediate_size": 10240, "moe_intermediate_size": 1536,
        "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
        "n_shared_experts": 1, "num_nextn_predict_layers": 1,
        "first_k_dense_replace": 1, "rope_theta": 1000000,
        "rms_norm_eps": 1e-05, "norm_topk_prob": True,
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1}
    for key, value in catalog_widths.items():
        assert CONFIG[key] == value, key
    entry = next(c for c in SPEC["configs"]
                 if c["name"] == "glm47flash-ep8share")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size", "dataset"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 8, 19360)
    assert CONFIG["published"]["n_routed_experts"] == 64
    for name in ("rotary_pairing", "mtp_concatenation_order", "mtp_state",
                 "mtp_loss_weight_lambda", "router_bias_update_rate_gamma",
                 "optimizer", "sequence_length", "initial_std",
                 "embedding_initial_std", "head_initial_std"):
        assert name in CONFIG["assumed"], name
    assert CONFIG["assumed"]["head_initial_std"] <= 0.0098
    assert CONFIG["optimizer"] == {
        "solver": "adam", "learning_rate": 3e-4, "beta1": 0.9,
        "beta2": 0.95, "epsilon": 1e-8, "weights_decay": 0.0,
        "warmup_steps": 40000}


def test_the_layers_are_the_published_ones_and_the_models():
    """The file's layer list is what ``models/latent_moe_lm.py``
    writes for the published sizes and this chip's share, and says
    what the catalog's keys say."""
    from veles_tpu.models.latent_moe_lm import PUBLISHED, layers
    expected = layers(**dict(
        PUBLISHED, blocks=5, vocabulary=19360, positions=4096,
        experts_held=(0, 8), block=512, head_chunk=2048,
        dispatch_rows=8192, stddev=0.02, embedding_stddev=1.0,
        head_stddev=0.006, remat=True))
    assert json.loads(json.dumps(expected)) == LAYERS
    attention = next(d for d in LAYERS if d["type"] == "latent_attention")
    assert (attention["heads"], attention["q_rank"], attention["kv_rank"],
            attention["qk_nope_dim"], attention["qk_rope_dim"],
            attention["v_dim"], attention["rope_theta"]) == (
        CONFIG["num_attention_heads"], CONFIG["q_lora_rank"],
        CONFIG["kv_lora_rank"], CONFIG["qk_nope_head_dim"],
        CONFIG["qk_rope_head_dim"], CONFIG["v_head_dim"],
        CONFIG["rope_theta"])
    sparse = [d for d in LAYERS if d["type"] == "moe"]
    assert len(sparse) == 5 and all(
        (d["n_experts"], d["experts_held"], d["top_k"], d["scale"],
         d["hidden"], d["shared_experts"], d["scoring"], d["normalize"],
         d["capacity_factor"]) == (64, [0, 8], 4, 1.8, 1536, 1, "sigmoid",
                                   True, None) for d in sparse)
    merge = next(d for d in LAYERS if d["type"] == "token_merge")
    assert merge["objective_weight"] == CONFIG["assumed"][
        "mtp_loss_weight_lambda"]
    assert LAYERS[-1]["weights_stddev"] == CONFIG["assumed"][
        "head_initial_std"]


def test_706_5_million_parameters_are_held():
    """By the program's own shapes, nothing allocated."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.standard_workflow import LAYER_TYPES
    wf = DummyWorkflow()
    shape, total, by_name = (2, 4098), 0, {}
    for i, descr in enumerate(LAYERS):
        descr = dict(descr)
        for key in ("remat", "branch"):
            descr.pop(key, None)
        unit = LAYER_TYPES[descr.pop("type")](wf, **descr)
        if hasattr(unit, "param_shapes") and unit.PARAMS:
            count = sum(math.prod(s) for s, _ in
                        unit.param_shapes(shape).values())
        elif descr.get("n_experts"):
            dim, hidden, held = shape[-1], unit.hidden, unit.experts_held[1]
            count = dim * unit.n_experts + unit.n_experts + dim \
                + (held + unit.shared_experts) * 3 * dim * hidden
        else:
            count = math.prod(unit.weights_shape_for(shape))
        by_name[i] = count
        total += count
        shape = unit.output_shape_for(shape)
    assert by_name[1] == 21759232 + 2048  # MLA and its block's norm
    assert by_name[4] == 106829120 - by_name[3]
    assert total == CONFIG["held_here"]["parameters"] == 706518848
    # 16 B a parameter: float32 value, gradient and Adam's two moments
    assert total * 16 / 1e9 == pytest.approx(11.30, abs=0.01)


def test_benchmark_json_gains_one_cell_and_eight_metrics():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm47flash-ep8share", "pretrain4k", 1)
    traffic = harness.load_json(HOME, "traffic", "pretrain4k.json")
    assert (traffic["driver"], traffic["stream"], traffic["n_train"],
            traffic["n_valid"], traffic["warm_epochs"],
            traffic["trace_epochs"]) == ("epochs", False, 32, 4, 2, 1)
    new = [m for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in new) == sorted([
        "mla_device_ms", "moe_device_ms", "moe_route_device_ms",
        "mtp_device_ms", "head_loss_device_ms", "expert_gemm_roofline",
        "mla_core_roofline", "expert_load_max_over_mean"])
    for metric in new:
        spec = harness.load_json(HOME, "layer_metrics",
                                 metric["name"] + ".json")
        assert spec["reader"] == "trace_lm"
        assert spec["unit"] == metric["unit"]
        assert metric["moves"] == "train_samples_per_s"
    accepted = ["alexnet227.resident", "alexnet227-dp4.resident"]
    for name in ("conv_device_ms", "fc_device_ms", "conv_roofline",
                 "fc_roofline", "conv_worst_roofline", "forward_device_ms",
                 "backward_device_ms", "update_device_ms",
                 "scope_coverage_pct"):
        metric = next(m for m in SPEC["per_layer"] if m["name"] == name)
        assert metric["workloads"] == accepted
    # the generic ones apply to the new cell as they stand
    bench = harness.Benchmark(ROOT)
    names = {m["name"] for m in bench.metrics("per_layer", cell)}
    assert {"train_step_device_ms", "eval_step_device_ms", "mfu_pct",
            "device_idle_pct", "epoch_gap_pct", "input_wait_pct"} <= names
    assert not names & {"conv_roofline", "forward_device_ms"}


# -- the harness, rehearsed --------------------------------------------------

@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("root"))
    shutil.copytree(HOME, os.path.join(path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "traffic", "tiny-tokens.json"),
                os.path.join(path, "benchmark", "traffic"))
    spec = dict(SPEC)
    spec["configs"] = [{
        "name": "tiny-lm", "source": "benchmark/tests", "reduced": [],
        "file": "benchmark/tests/configs/tiny-lm.json", "why": "toy"}]
    spec["workloads"] = [{"name": "tiny-lm.tokens", "config": "tiny-lm",
                          "traffic": "tiny-tokens", "chips": 1,
                          "why": "toy"}]
    spec["per_layer"] = [
        dict(m, workloads=["tiny-lm.tokens"]) if m.get("workloads") == [CELL]
        else m for m in SPEC["per_layer"]]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return harness.Benchmark(path)


def run(bench, trace):
    import jax
    lines = []
    result = harness.run_cell(bench, "tiny-lm.tokens", 2**31 + 5, 0.3,
                              trace, jax.devices(), time.time(),
                              log=lines.append)
    return result, lines


def test_the_tiny_cell_is_correct_but_for_the_device(bench):
    result, lines = run(bench, trace=False)
    assert set(result["metrics"]) == {
        "train_samples_per_s", "eval_samples_per_s", "peak_hbm_mb",
        "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    checks = json.loads(next(
        line for line in lines if line.startswith("checks: "))[8:])
    assert {k for k, ok in checks.items() if not ok} == {
        "platform_is_tpu", "device_in_peak_table"}
    report = ast.literal_eval(next(
        line for line in lines if "agreement" in line).split(": ", 1)[1])
    # float32 on both sides: one train step of the program is the
    # reference's, and the workflow was put back as it was (the
    # untrained validation sweep, run after it, is the reference's too)
    assert report["gradient_error"] < 1e-5
    assert report["update_error"] < 1e-3
    assert report["bias_error"] == 0 and report["routing_error"] == 0
    assert report["routed_per_token"] == report["top_k"] == [2, 2]
    assert max(report["loss_errors"].values()) < 1e-5
    assert report["validation_loss_error"] < 1e-5


# -- the comparison that decides ``correct`` ---------------------------------

@pytest.fixture(scope="module")
def tiny_step():
    """``(reference module, layers, losses, the reference's step)`` of
    the tiny configuration on seeded weights and ids."""
    import numpy
    ref = harness.load_module(HOME, "reference", "moe_lm")
    config = harness.load_json(HERE, "configs", "tiny-lm.json")
    layers = [dict(d) for d in config["layers"]]
    rng = numpy.random.default_rng(7)
    dim, vocabulary = layers[0]["dim"], layers[0]["vocabulary"]
    tokens = rng.integers(0, vocabulary, (4, layers[0]["positions"] + 2))
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.standard_workflow import StandardWorkflow
    from benchmark.seeded_tokens import SeededTokenLoader
    workflow = StandardWorkflow(
        DummyLauncher(), loader=lambda wf: SeededTokenLoader(
            wf, n_train=4, n_valid=4, length=tokens.shape[1],
            vocabulary=vocabulary, seed=3, minibatch_size=4),
        layers=[dict(d) for d in layers], loss="softmax", solver="adam",
        learning_rate=0.003, momentum=0.0, weights_decay=0.0)
    workflow.initialize(device=None)
    params = [{name: numpy.array(arr.map_read())
               for name, arr in fwd.param_arrays().items()}
              for fwd in workflow.forwards]
    for descr, fwd in zip(layers, workflow.forwards):
        descr["name"] = fwd.name
    assert dim == params[0]["weights"].shape[1]

    def step(layers=layers, tokens=tokens):
        return ref.train_step(layers, params, tokens, tokens[:, 1:],
                              config["optimizer"])
    return ref, layers, numpy.array([4.1, 4.2]), step


def scaled(step, **factors):
    """``step`` with each named part's arrays multiplied through."""
    out = dict(step)
    for part, factor in factors.items():
        out[part] = [{k: factor(k) * v for k, v in d.items()}
                     for d in step[part]]
    return out


CONTROLS = {
    "the reference itself": (lambda step, again: step, True),
    "no update at all": (lambda step, again: scaled(
        step, changes=lambda k: 0.0, moments=lambda k: 0.0), False),
    "a rate twice too large": (lambda step, again: scaled(
        step, changes=lambda k: 1.0 if k == "select_bias" else 2.0), False),
    "the selection bias moved the wrong way": (lambda step, again: scaled(
        step, changes=lambda k: -1.0 if k == "select_bias" else 1.0), False),
    "half the batch": (lambda step, again: again(half=True), False),
    "the shared expert left out": (lambda step, again: again(
        change={"shared_experts": 0}), False),
    "a token in four dropped": (lambda step, again: dict(
        step, counts=[c - c // 4 for c in step["counts"]]), False),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_agreement_tells_a_wrong_step(tiny_step, control):
    """Each control laid out as a program's step and taken through
    ``agreement``, the harness's own comparison: only the reference's
    step comes out correct."""
    ref, layers, losses, step = tiny_step
    expected = step()

    def again(half=False, change=None):
        changed = [dict(d, **change) if change and d["type"] == "moe"
                   else d for d in layers]
        if half:
            import numpy
            rng = numpy.random.default_rng(7)
            tokens = rng.integers(0, layers[0]["vocabulary"],
                                  (4, layers[0]["positions"] + 2))[:2]
            return step(changed, tokens)
        return step(changed)

    make, sound = CONTROLS[control]
    ok, report = ref.agreement(losses, {
        "losses": losses,
        "step": ref.step_comparison(layers, make(expected, again),
                                    expected)})
    assert ok is sound, report
    assert report["gradient_tolerance"] == ref.GRADIENT_TOLERANCE
    assert report["update_tolerance"] == ref.UPDATE_TOLERANCE


def test_the_limits_lie_between_their_readings():
    """The v5e's readings, as PERF.md section 6 has them: the limit on
    the gradient between the program's largest and the int8
    reference's, the limit on the update between the program's largest
    and 1 (a state left unchanged), the more room above."""
    ref = harness.load_module(HOME, "reference", "moe_lm")
    program, int8 = ref.READINGS["program"], ref.READINGS["int8"]
    assert max(program["gradient_error"]) * 1.5 < ref.GRADIENT_TOLERANCE \
        < int8["gradient_error"] / 1.5
    assert max(program["update_error"]) < ref.UPDATE_TOLERANCE < 1.0
    assert ref.UPDATE_TOLERANCE - max(program["update_error"]) \
        >= 1.0 - ref.UPDATE_TOLERANCE
    assert max(program["update_scale_error"]) * 100 \
        < ref.UPDATE_SCALE_TOLERANCE < 1.0


def test_the_traced_tiny_cell_reads_the_counter(bench):
    """No device plane on a CPU: the trace readers give nothing and
    raise nothing; the gauge is the program's and reads."""
    result, _ = run(bench, trace=True)
    assert "mla_device_ms" not in result["metrics"]
    assert "expert_gemm_roofline" not in result["metrics"]
    ratio = result["metrics"]["expert_load_max_over_mean"]
    assert ratio["unit"] == "ratio" and ratio["value"] >= 1.0
    from veles_tpu.telemetry.registry import get_registry
    routed = get_registry().get("veles_moe_routed_per_step")
    # no token dropped: tokens x top_k a step, every sparse layer
    assert {child.value for _, child in routed.series()} == {4 * 16 * 2.0}


def test_a_program_without_the_gauges_gives_nothing():
    from veles_tpu.telemetry.registry import get_registry
    reader = harness.load_module(HOME, "readers", "trace_lm")
    registry = get_registry()
    saved = dict(registry._metrics)
    registry.clear()
    try:
        context = {"trace": None, "traced": None, "counters": {},
                   "config": CONFIG, "peaks": PEAKS, "chips": 1,
                   "log": print}
        for metric in SPEC["per_layer"]:
            if metric.get("workloads") != [CELL]:
                continue
            spec = harness.load_json(HOME, "layer_metrics",
                                     metric["name"] + ".json")
            assert reader.read(context, **spec["args"]) is None
    finally:
        registry._metrics.update(saved)


# -- the reader's expressions -------------------------------------------------

BODY = "jit(train_segment)/while/body/closed_call/"
NAMES = [
    (BODY + "jvp(veles.u04.moe4)/cond/branch_0_fun/experts/ragged_dot",
     ((4, "moe4"), "forward"), {"experts"}, None),
    (BODY + "transpose(jvp(veles.u04.moe4))/jvp(veles.u04.moe4)/checkpoint/"
     "rematted_computation/cond/branch_1_fun/route/gather",
     ((4, "moe4"), "backward"), {"route"}, None),
    (BODY + "transpose(jvp(veles.u03.latent_attention3))/"
     "jvp(veles.u03.latent_attention3)/checkpoint/core/bhqk,bhqd->bhkd/"
     "dot_general", ((3, "latent_attention3"), "backward"), {"core"}, None),
    (BODY + "jvp(veles.u03.latent_attention3)/proj/dot_general",
     ((3, "latent_attention3"), "forward"), {"proj"}, None),
    (BODY + "jvp(veles.u16.vocabulary_head16)/mtp/checkpoint/while/body/"
     "dot_general", ((16, "vocabulary_head16"), "forward"), set(), "mtp"),
    (BODY + "jvp(veles.u16.vocabulary_head16)/main/checkpoint/while/body/"
     "veles.loss/main/jit(log_softmax)/reduce_max",
     ("veles.loss", None), set(), "main"),
    (BODY + "transpose(jvp(veles.loss))/mtp/mul", ("veles.loss", None),
     set(), "mtp"),
    (BODY + "veles.update.u04.moe4/sign", ((4, "moe4"), "update"), set(),
     None),
    # what the v5e compiler makes of lax.ragged_dot: no scope at all
    ("ragged-dot-none", ("<unscoped>", None), set(), None),
]


@pytest.mark.parametrize("name,row,parts,stream", NAMES)
def test_names_the_program_emits_are_read(name, row, parts, stream):
    reader = harness.load_module(HOME, "readers", "trace_lm")
    assert reader.parse(name) == row
    assert reader.sub_scopes(name, reader.UNIT_PARTS) == parts
    assert reader.stream_of(name, ["main", "mtp"]) == stream
    # the accepted reader still parses every one of them
    scopes = harness.load_module(HOME, "readers", "trace_scopes")
    assert scopes.parse(name)[0] == row[0]
