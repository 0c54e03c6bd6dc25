"""The gated short-convolution and grouped-query attention family of
the benchmark: ``flops_conv_lm.py`` pinned to the hand-worked numbers
of PERF.md section 4, the published configuration's file against the
catalog's row, the harness rehearsed on the CPU at a tiny size, the
comparison that decides ``correct`` against wrong steps, and the
reader ``trace_conv_lm`` on a program without its scopes and on a
trace recorded on the v5e."""

import ast
import json
import os
import time

import pytest

from benchmark import flops_conv_lm, harness, trace_reduce
from benchmark.tests import record_conv_lm

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
ROOT = os.path.dirname(HOME)
CELL = "lfm2-8b-a1b-ep4share.pretrain8k-1seq"
NAME = "lfm2-8b-a1b-ep4share"
SPEC = harness.load_json(ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(HOME, "configs", NAME + ".json")
LAYERS = CONFIG["layers"]
PEAKS = harness.load_json(HOME, "peaks.json")["devices"]["TPU v5 lite"]
MFLOP = 1e6
#: by ``trace_conv_lm``, and by ``trace_lm`` under names of this
#: cell's own (the accepted metrics' lists may not be edited)
CONV_METRICS = ["short_conv_device_ms", "short_conv_mix_device_ms",
                "short_conv_roofline", "gqa64_device_ms",
                "gqa64_core_roofline", "biased_moe_device_ms"]
SHARED_METRICS = ["biased_expert_load_max_over_mean",
                  "tied_head_loss_device_ms"]
NEW_METRICS = CONV_METRICS + SHARED_METRICS
FIXTURE = os.path.join(HOME, "fixtures", "tiny-conv.v5e-1.xplane.pb")
TINY = harness.load_json(HERE, "configs", "tiny-conv-lm.json")


# -- the arithmetic ----------------------------------------------------------

def test_a_token_is_432_mflop_forward():
    """By shapes, forward, MFLOP a token: a short-convolution block's
    two products 33.55 (2048 x 6144 and 2048 x 2048), four of them
    134.2 (31.0%, the largest part); attention 21.0 of projections
    (2048 x 2048 twice, 2048 x 512 twice) + 33.6 of core at 32 heads
    of 64 over the causal triangle (12.6%); the dense MLP 88.1
    (20.4%); the experts held 4 x 22.0 at their expectation (4 x 8 /
    32 a token; 20.4%); the head 67.1 over 16,384 rows (15.5%)."""
    positions = LAYERS[0]["positions"]
    assert positions == 8192
    assert flops_conv_lm.causal_pairs(8192) == 33558528
    costs = flops_conv_lm.layer_costs(LAYERS)
    assert [c["type"] for c in costs] == [d["type"] for d in LAYERS]
    convs = [c for c in costs if c["type"] == "short_conv"]
    attention = next(c for c in costs if c["type"] == "grouped_attention")
    dense = next(c for c in costs if c["type"] == "gated_mlp")
    sparse = [c for c in costs if c["type"] == "moe"]
    head = costs[-1]
    assert len(convs) == 4 and len(sparse) == 4
    assert convs[0]["parts"]["proj"] == 2.0 * (2048 * 6144 + 2048 * 2048)
    assert convs[0]["parts"]["proj"] / MFLOP == pytest.approx(33.55,
                                                               abs=0.01)
    assert convs[0]["parts"]["mix"] == 2.0 * 3 * 2048
    assert attention["parts"]["proj"] / MFLOP == pytest.approx(20.97,
                                                               abs=0.01)
    assert attention["parts"]["core"] == pytest.approx(
        4 * 64 * 32 * 33558528 / 8192)
    assert attention["parts"]["core"] / MFLOP == pytest.approx(33.56,
                                                               abs=0.01)
    assert dense["parts"]["mlp"] / MFLOP == pytest.approx(88.08, abs=0.01)
    assert sparse[0]["parts"]["experts"] / MFLOP == pytest.approx(
        22.02, abs=0.01)
    assert sparse[0]["parts"]["shared"] == 0
    assert head["parts"]["head"] / MFLOP == pytest.approx(67.11, abs=0.01)
    assert head["passes"] == 1
    total = flops_conv_lm.forward_flops_per_token(LAYERS)
    assert total / MFLOP == pytest.approx(432.6, abs=0.1)
    share = {
        "conv": sum(sum(c["parts"].values()) for c in convs) / total,
        "attention": sum(attention["parts"].values()) / total,
        "dense": dense["parts"]["mlp"] / total,
        "experts": sum(c["parts"]["experts"] for c in sparse) / total,
        "head": head["parts"]["head"] / total}
    assert share == pytest.approx({
        "conv": 0.310, "attention": 0.126, "dense": 0.204,
        "experts": 0.204, "head": 0.155}, abs=0.001)
    assert total * 8192 / 1e12 == pytest.approx(3.54, abs=0.01)
    assert flops_conv_lm.train_flops_per_sample(LAYERS) == \
        3.0 * total * 8192
    assert flops_conv_lm.train_flops_per_sample(LAYERS) / 1e12 == \
        pytest.approx(10.63, abs=0.01)


def test_the_floors():
    """A short-convolution unit's mix reads (8192, 6144) and writes
    (8192, 2048) in bfloat16 forward, 134 MB, and moves 235 MB
    backward: 0.45 ms a unit at 819 GB/s, 1.8 ms a step over the four;
    its products are 3 x 0.275 TFLOP, 4.19 ms at 197 TFLOP/s; the
    unit's floor is their sum. The core: 3 x 4 x 64 x 32 x 33,558,528
    pairs = 0.825 TFLOP, 4.19 ms, compute-bound (the least bytes, 6 x
    (32 + 8) heads x 8,192 x 64 x 2, take 0.31 ms)."""
    tokens = 8192
    assert 4 * 2048 * tokens * 2 == 134217728
    assert 7 * 2048 * tokens * 2 == 234881024
    assert flops_conv_lm.short_conv_mix_bytes(2048, tokens) == \
        134217728 + 234881024
    whole, mix = flops_conv_lm.short_conv_floor_s(LAYERS[1], 2048, 8192,
                                                  1, PEAKS)
    assert mix * 1e3 == pytest.approx(0.4507, abs=1e-3)
    assert 4 * mix * 1e3 == pytest.approx(1.80, abs=0.01)
    assert (whole - mix) * 1e3 == pytest.approx(
        3 * 8192 * 33554432 / 197e12 * 1e3) == pytest.approx(4.186,
                                                             abs=1e-3)
    # with the forward run again, the mix's bytes are 15 x dim a token
    assert (4 + 11) / 11 * 4 * mix * 1e3 == pytest.approx(2.46, abs=0.01)
    seconds, bound = flops_conv_lm.attention_core_floor_s(
        LAYERS[3], 8192, 1, PEAKS)
    assert bound == "compute"
    assert seconds * 1e3 == pytest.approx(
        3 * 4 * 64 * 32 * 33558528 / 197e12 * 1e3) == pytest.approx(
            4.186, abs=1e-3)
    assert 6 * (32 + 8) * 8192 * 64 * 2 / 819e9 * 1e3 == pytest.approx(
        0.307, abs=1e-3)


# -- the configuration's file -------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "LFM2-8B-A1B")


def test_published_widths_are_unchanged():
    row = catalog_row()
    entry = next(c for c in SPEC["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "dataset"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 8, 16384)
    assert CONFIG["published"]["num_hidden_layers"] == 24 == len(
        CONFIG["layer_types"])
    assert CONFIG["published"]["num_experts"] == 32
    assert 16384 * 4 == CONFIG["published"]["vocab_size"] == 65536
    first, last = CONFIG["held_here"]["blocks"]
    held = CONFIG["layer_types"][first:last + 1]
    assert held == ["conv", "full_attention", "conv", "conv", "conv"]
    assert [d["type"] for d in LAYERS[1:-2:2]] == [
        {"conv": "short_conv", "full_attention": "grouped_attention"}[t]
        for t in held]
    assert CONFIG["held_here"]["parameters"] == 507820160
    for name in ("tied_table", "table_initial_std", "split_order",
                 "conv_history", "rotary_pairing", "qk_norm",
                 "router_score_function", "selection_bias_rule",
                 "expert_placement", "dispatch_rows", "optimizer",
                 "sequence_length", "remat"):
        assert name in CONFIG["assumed"], name
    assert "8.34 B" in CONFIG["assumed"]["tied_table"]
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["optimizer"] == {
        "solver": "adam", "learning_rate": 3e-4, "beta1": 0.9,
        "beta2": 0.95, "epsilon": 1e-8, "weights_decay": 0.0,
        "warmup_steps": 40000}
    assert (CONFIG["batch"], CONFIG["precision"], CONFIG["trainer"]) == (
        1, "bfloat16", "fused")
    assert LAYERS[-1]["tied_to"] == LAYERS[0]["name"] == "embedding"
    assert LAYERS[0]["weights_stddev"] == 0.006
    assert "0.1" in CONFIG["assumed"]["table_initial_std"]
    sparse = [d for d in LAYERS if d["type"] == "moe"]
    assert {(d["n_experts"], d["top_k"], tuple(d["experts_held"]),
             d["hidden"], d["normalize_eps"], d["dispatch_rows"])
            for d in sparse} == {(32, 4, (0, 8), 1792, 1e-6, 16384)}


def test_benchmark_json_holds_the_configuration_the_cell_and_its_metrics():
    """What PR 37 added, as a SUBSET of the lists: a later PR appends
    its own and this stays true."""
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain8k-1seq", 1)
    assert "1,024 tokens a held expert" in cell["why"] and \
        "4x" in cell["why"]
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == NAME] == [CELL]
    mine = {m["name"]: m for m in SPEC["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS) <= set(mine)
    for name in NEW_METRICS:
        metric = mine[name]
        spec = harness.load_json(HOME, "layer_metrics", name + ".json")
        assert spec["reader"] == ("trace_conv_lm" if name in CONV_METRICS
                                  else "trace_lm")
        assert (spec["unit"], spec["layer"]) == (metric["unit"],
                                                 metric["layer"])
        assert metric["moves"] == "train_samples_per_s"
        assert metric["workloads"] == [CELL]
    assert {n for n in NEW_METRICS if mine[n]["layer"] == "Kernels"} == {
        "short_conv_roofline", "gqa64_core_roofline"}
    bench = harness.Benchmark(ROOT)
    for kind in ("builders", "reference"):
        harness.load_module(HOME, kind, CONFIG["family"])
    harness.load_module(HOME, "readers", "trace_conv_lm")
    names = {m["name"] for m in bench.metrics("per_layer", cell)}
    assert set(NEW_METRICS) | {
        "train_step_device_ms", "eval_step_device_ms", "mfu_pct",
        "device_idle_pct", "epoch_gap_pct", "input_wait_pct"} <= names
    assert {"train_samples_per_s", "eval_samples_per_s", "peak_hbm_mb",
            "setup_s"} <= {m["name"] for m in
                           bench.metrics("end_to_end", cell)}


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(HOME, "reference", "conv_moe_lm.py")) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        modules = [a.name for a in node.names] \
            if isinstance(node, ast.Import) else \
            [node.module] if isinstance(node, ast.ImportFrom) else []
        assert not any(m.startswith("veles_tpu") for m in modules)


def test_the_placement_is_the_indexed_familys():
    builder = harness.load_module(HOME, "builders", "conv_moe_lm")
    from benchmark.builders import indexed_moe_lm
    assert builder.place_experts is indexed_moe_lm.place_experts


# -- the harness, rehearsed --------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def a_registry_of_this_files_own():
    """The tiny cell's series go when this file is done: the accepted
    families' tests read the process's registry whole."""
    from veles_tpu.telemetry.registry import get_registry
    registry = get_registry()
    saved = dict(registry._metrics)
    registry.clear()
    yield
    registry.clear()
    registry._metrics.update(saved)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return record_conv_lm.tiny_root(str(tmp_path_factory.mktemp("root")))


def run(bench, trace):
    import jax
    lines = []
    result = harness.run_cell(bench, record_conv_lm.CELL, 2**31 + 5,
                              0.3, trace, jax.devices(), time.time(),
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
    # reference's, the table's two readers summed, the bias moved by
    # the rule, and the workflow was put back as it was
    assert report["gradient_error"] < 1e-5
    assert report["table_gradient_error"] < 1e-5
    assert report["taps_gradient_error"] < 1e-5
    assert report["update_error"] < 1e-3
    assert report["bias_error"] == 0 and report["routing_error"] == 0
    assert report["routed_per_token"] == report["top_k"] == [3, 3, 3]
    assert any("experts placed" in line for line in lines)
    assert any("(the table once)" in line for line in lines)


def test_the_traced_tiny_cell_reads_the_counter(bench):
    """No device plane on a CPU: the trace readers give nothing and
    raise nothing; the counter is the program's gauge."""
    result, _ = run(bench, trace=True)
    assert set(result["metrics"]) & set(NEW_METRICS) == {
        "biased_expert_load_max_over_mean"}
    ratio = result["metrics"]["biased_expert_load_max_over_mean"]
    assert ratio["unit"] == "ratio" and ratio["value"] >= 1


# -- the comparison that decides ``correct`` ---------------------------------

@pytest.fixture(scope="module")
def tiny_step():
    """``(reference module, layers, losses, the reference's step)`` of
    the tiny configuration on seeded weights and ids."""
    import numpy
    ref = harness.load_module(HOME, "reference", "conv_moe_lm")
    layers = [dict(d) for d in TINY["layers"]]
    rng = numpy.random.default_rng(7)
    vocabulary = layers[0]["vocabulary"]
    tokens = rng.integers(0, vocabulary, (4, layers[0]["positions"] + 1))
    from benchmark.seeded_tokens import SeededTokenLoader
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.standard_workflow import StandardWorkflow
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
    widen = {"token_embedding": {"weights": 10.0},
             "grouped_attention": dict.fromkeys("qkvo", 10.0),
             "short_conv": {"in": 10.0, "out": 10.0, "taps": 20.0},
             "moe": {"weights": 50.0}}
    for descr, fwd, p in zip(layers, workflow.forwards, params):
        descr["name"] = fwd.name
        # at a toy width a fill of 0.02 leaves every score near 0 and
        # every gate flat: widened, so that a norm, a tap or a reader
        # shows in the step as it does at 2,048
        for name, factor in widen.get(descr["type"], {}).items():
            p[name] *= factor

    def step(layers=layers, tokens=tokens):
        return ref.train_step(layers, params, tokens, tokens[:, 1:],
                              TINY["optimizer"])
    return ref, layers, numpy.array([4.1, 4.2]), step, params, tokens


def scaled(step, only=None, **factors):
    out = dict(step)
    for part, factor in factors.items():
        out[part] = [{k: factor * v if only is None or k in only else v
                      for k, v in d.items()} for d in step[part]]
    return out


def one_reader(step, moment):
    """``step`` with the table's first moment replaced."""
    moments = [dict(d) for d in step["moments"]]
    moments[0]["weights"] = moment
    return dict(step, moments=moments)


def taps_reversed(step):
    return dict(step, moments=[
        {k: v[:, ::-1] if k == "taps" else v for k, v in d.items()}
        for d in step["moments"]])


CONTROLS = {
    "the reference itself": (lambda step, again, layers: step, True),
    "no update at all": (lambda step, again, layers: scaled(
        step, changes=0.0, moments=0.0), False),
    "a rate twice too large": (lambda step, again, layers: scaled(
        step, changes=2.0), False),
    "half the batch": (lambda step, again, layers: again(half=True), False),
    "the head's gradient to the table dropped": (
        lambda step, again, layers: one_reader(
            step, again(reader="rows")), False),
    "the embedding's gradient to the table dropped": (
        lambda step, again, layers: one_reader(
            step, again(reader="head")), False),
    "the taps' gradient in the other order": (
        lambda step, again, layers: taps_reversed(step), False),
    "the taps' gradient at twice its size": (
        lambda step, again, layers: scaled(
            step, only=("taps",), moments=2.0), False),
    "one tap more": (lambda step, again, layers: again([
        dict(d, taps=4) if d["type"] == "short_conv" else d
        for d in layers], taps=4), False),
    "the q/k norm left out": (lambda step, again, layers: again([
        dict(d, qk_norm=False) if d["type"] == "grouped_attention" else d
        for d in layers]), False),
    "top-2 routing": (lambda step, again, layers: again([
        dict(d, top_k=2) if d["type"] == "moe" else d
        for d in layers]), False),
    "the selection bias moved the other way": (
        lambda step, again, layers: dict(step, changes=[
            {k: -v if k == "select_bias" else v for k, v in d.items()}
            for d in step["changes"]]), False),
    "a token in four dropped": (lambda step, again, layers: dict(
        step, counts=[c - c // 4 for c in step["counts"]]), False),
    "a step that says nothing of the table": (
        lambda step, again, layers: None, False),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_agreement_tells_a_wrong_step(tiny_step, control):
    """Each control laid out as a program's step and taken through
    ``agreement``, the harness's own comparison: only the reference's
    step comes out correct."""
    import jax
    import numpy
    ref, layers, losses, step, params, tokens = tiny_step
    expected = step()

    def again(other=None, half=False, reader=None, taps=None):
        if half:
            return step(layers, tokens[:2])
        if reader:
            # the table's first moment had one reader's gradient alone
            with jax.default_matmul_precision("highest"):
                rows, head = jax.grad(
                    lambda p, t: ref.objective(
                        layers, p, tokens, tokens[:, 1:],
                        head_table=t)[0], (0, 1))(
                            params, params[0]["weights"])
            part = rows[0]["weights"] if reader == "rows" else head
            return numpy.float32(0.1) * numpy.asarray(part)
        if taps:
            # a filter one tap longer: its gradient has another shape,
            # compared over the taps both have
            made = step([dict(d) for d in other])
            return dict(made, moments=[
                {k: v[:, -3:] if k == "taps" else v for k, v in d.items()}
                for d in made["moments"]], changes=[
                {k: v[:, -3:] if k == "taps" else v for k, v in d.items()}
                for d in made["changes"]])
        return step(other)

    make, sound = CONTROLS[control]
    if "nothing of the table" in control:
        comparison = ref.step_comparison(layers, expected, expected)
        del comparison["table_gradient_error"]
    else:
        made = make(expected, again, layers)
        comparison = ref.step_comparison(layers, made, expected)
    ok, report = ref.agreement(losses, {"losses": losses,
                                        "step": comparison})
    assert ok is sound, report
    for name in ("gradient", "table_gradient", "taps_gradient", "update",
                 "update_scale"):
        assert name + "_tolerance" in report
    if "to the table dropped" in control:
        assert report["table_gradient_error"] > 0.05
        assert report["taps_gradient_error"] == 0
    if control.startswith("the taps"):
        assert report["taps_gradient_error"] > 0.5
        assert report["table_gradient_error"] == 0


def test_the_limits_lie_between_their_readings():
    """The v5e's readings, as PERF.md section 6 has them: every limit
    of a precision between the program's largest reading over at least
    five seeds and the int8 reference's, with room on both sides; the
    limit on the update between the program's largest and 1 (a state
    left unchanged), the more room above."""
    ref = harness.load_module(HOME, "reference", "conv_moe_lm")
    program, int8 = ref.READINGS["program"], ref.READINGS["int8"]
    for name, limit in (
            ("gradient_error", ref.GRADIENT_TOLERANCE),
            ("table_gradient_error", ref.TABLE_GRADIENT_TOLERANCE),
            ("taps_gradient_error", ref.TAPS_GRADIENT_TOLERANCE)):
        assert len(program[name]) >= 5, name
        assert max(program[name]) * 1.3 < limit < int8[name] / 1.3, name
    assert max(program["update_error"]) < ref.UPDATE_TOLERANCE < 1.0
    assert ref.UPDATE_TOLERANCE - max(program["update_error"]) \
        >= 1.0 - ref.UPDATE_TOLERANCE
    assert max(program["update_scale_error"]) * 10 \
        < ref.UPDATE_SCALE_TOLERANCE < 1.0


# -- the reader ---------------------------------------------------------------

def context_of(config, trace=None, traced=None):
    lines = []
    return {"trace": trace, "traced": traced, "counters": {},
            "config": config, "peaks": PEAKS, "chips": 1,
            "log": lines.append}, lines


def test_a_program_without_the_scopes_gives_nothing():
    from veles_tpu.telemetry.registry import get_registry
    registry = get_registry()
    saved = dict(registry._metrics)
    registry.clear()
    try:
        context, _ = context_of(CONFIG)
        for name in NEW_METRICS:
            spec = harness.load_json(HOME, "layer_metrics", name + ".json")
            assert harness.load_module(
                HOME, "readers", spec["reader"]).read(
                    context, **spec["args"]) is None
    finally:
        registry._metrics.update(saved)


def test_the_reader_on_a_trace_recorded_on_the_v5e(monkeypatch):
    """``record_conv_lm.py``'s trace of the tiny configuration (train
    steps of 4 sequences of 16): every short-convolution unit has time
    under ``proj`` and under ``mix``, every attention unit under
    ``proj`` and ``core``; the parts are inside the units' whole; the
    metrics that read them come out, the shares under 100%."""
    if not os.path.isfile(FIXTURE):
        pytest.skip("no fixture recorded yet")
    assert os.path.getsize(FIXTURE) < 1e6
    reader = harness.load_module(HOME, "readers", "trace_conv_lm")
    from benchmark.readers import trace_scopes
    monkeypatch.setattr(trace_scopes, "trace_path", lambda: FIXTURE)
    context, lines = context_of(
        TINY, trace_reduce.reduce_file(FIXTURE),
        {"epochs": 1, "train_steps": 4, "eval_steps": 2, "compiled": 0})
    parts = reader.by_part(context)
    kinds = [d["type"] for d in TINY["layers"]]
    convs = [i for i, t in enumerate(kinds) if t == "short_conv"]
    cores = [i for i, t in enumerate(kinds) if t == "grouped_attention"]
    assert (convs, cores) == ([1, 5, 7], [3, 9])
    for i in convs:
        assert parts[i, "proj"] > 0 and parts[i, "mix"] > 0
        assert parts[i, "core"] == 0
    for i in cores:
        assert parts[i, "proj"] > 0 and parts[i, "core"] > 0
        assert parts[i, "mix"] == 0
    values = {}
    for name in NEW_METRICS:
        spec = harness.load_json(HOME, "layer_metrics", name + ".json")
        values[name] = harness.load_module(
            HOME, "readers", spec["reader"]).read(context, **spec["args"])
    assert values["short_conv_mix_device_ms"] == pytest.approx(1e3 * sum(
        parts[i, "mix"] for i in convs))
    assert 0 < values["short_conv_mix_device_ms"] \
        < values["short_conv_device_ms"]
    assert 1e3 * sum(parts[i, "core"] for i in cores) \
        < values["gqa64_device_ms"]
    assert values["biased_moe_device_ms"] > 0
    assert values["tied_head_loss_device_ms"] > 0
    assert 0 < values["short_conv_roofline"] < 100
    assert 0 < values["gqa64_core_roofline"] < 100
    assert any(line.startswith("mixers by sub-scope") for line in lines)
