"""The grouped-query attention under a learned selection of keys family
of the benchmark: ``flops_indexed_lm.py`` pinned to the hand-worked
numbers of PERF.md section 4, the published configuration's file
against the catalog's row, the harness rehearsed on the CPU at a tiny
size, the comparison that decides ``correct`` against wrong steps, and
the reader ``trace_indexed_lm`` on a program without its gauges and on
a trace recorded on the v5e."""

import ast
import json
import os
import re
import time

import pytest

from benchmark import flops_indexed_lm, harness, trace_reduce
from benchmark.tests import record_indexed_lm

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
ROOT = os.path.dirname(HOME)
CELL = "keye-vl2-ep8share.pretrain8k-1seq"
NAME = "keye-vl2-ep8share"
SPEC = harness.load_json(ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(HOME, "configs", NAME + ".json")
LAYERS = CONFIG["layers"]
PEAKS = harness.load_json(HOME, "peaks.json")["devices"]["TPU v5 lite"]
GFLOP = 1e9
#: by ``trace_indexed_lm``, and by ``trace_lm`` under names of this
#: cell's own (the accepted metrics' lists may not be edited)
DSA_METRICS = ["dsa_device_ms", "dsa_index_device_ms",
               "dsa_index_loss_device_ms", "dsa_core_roofline",
               "dsa_selected_over_causal", "routed_moe_device_ms"]
ROUTED_METRICS = ["routed_moe_route_device_ms",
                  "routed_expert_gemm_roofline",
                  "routed_expert_load_max_over_mean",
                  "routed_head_loss_device_ms"]
NEW_METRICS = DSA_METRICS + ROUTED_METRICS
FIXTURE = os.path.join(HOME, "fixtures", "tiny-indexed.v5e-1.xplane.pb")
TINY = harness.load_json(HERE, "configs", "tiny-indexed-lm.json")


# -- the arithmetic ----------------------------------------------------------

def test_a_layer_is_737_gflop_a_step_forward():
    """At 8,192 positions, a step of one sequence, forward, a layer:
    attention projections 309 (2048 x 4096 twice, 2048 x 512 twice),
    the index's projections 37 (2048 x (1024 + 64 + 16)), the index
    scores 69 over the CAUSAL pairs (16 heads of 64), the core 241 over
    the SELECTED pairs (14,681,088 of 33,558,528; the causal square
    would be 550), the experts held 77 at their expectation (8 x 16 /
    128 a token), the router 4."""
    positions = LAYERS[0]["positions"]
    assert positions == 8192
    assert flops_indexed_lm.selected_pairs(8192, 2048) == 14681088
    assert flops_indexed_lm.causal_pairs(8192) == 33558528
    assert 14681088 / 33558528 == pytest.approx(0.4375, abs=5e-5)
    assert flops_indexed_lm.selected_pairs(2048, 2048) == \
        flops_indexed_lm.causal_pairs(2048)
    costs = flops_indexed_lm.layer_costs(LAYERS)
    attention = next(c for c in costs if c["type"] == "grouped_attention")
    sparse = next(c for c in costs if c["type"] == "moe")
    head = costs[-1]
    step = {k: v * positions / GFLOP for k, v in attention["parts"].items()}
    assert attention["parts"]["proj"] == 2.0 * (
        2 * 2048 * 4096 + 2 * 2048 * 512)
    assert step["proj"] == pytest.approx(309.2, abs=0.1)
    assert step["index_proj"] == pytest.approx(37.0, abs=0.1)
    assert step["index_scores"] == pytest.approx(
        2 * 64 * 16 * 33558528 / GFLOP) == pytest.approx(68.7, abs=0.1)
    assert step["core"] == pytest.approx(
        4 * 128 * 32 * 14681088 / GFLOP) == pytest.approx(240.5, abs=0.1)
    assert 4 * 128 * 32 * 33558528 / GFLOP == pytest.approx(549.8, abs=0.1)
    assert sparse["parts"]["experts"] * positions / GFLOP == \
        pytest.approx(77.3, abs=0.1)
    assert sparse["parts"]["router"] * positions / GFLOP == \
        pytest.approx(4.3, abs=0.1)
    assert sparse["parts"]["shared"] == 0
    layer = sum(step.values()) + sum(sparse["parts"].values()) \
        * positions / GFLOP
    assert layer == pytest.approx(737.0, abs=1.0)
    assert (step["index_proj"] + step["index_scores"] + step["core"]) \
        / layer == pytest.approx(0.47, abs=0.01)
    assert sum(step.values()) / layer == pytest.approx(0.89, abs=0.01)
    assert head["parts"]["head"] * positions / GFLOP == \
        pytest.approx(637.3, abs=0.1)


def test_the_step_by_the_layers_held():
    """Forward = layers x 737 + the head's 637 GFLOP; trained 3x."""
    blocks = CONFIG["num_hidden_layers"]
    forward = flops_indexed_lm.forward_flops_per_token(LAYERS) * 8192
    assert forward / GFLOP == pytest.approx(blocks * 737.0 + 637.3, rel=2e-3)
    assert flops_indexed_lm.train_flops_per_sample(LAYERS) == 3.0 * forward
    if blocks == 6:
        assert forward / 1e12 == pytest.approx(5.06, abs=0.01)
    # a layer without an index counts its causal triangle
    plain = [dict(d, index=None) if d["type"] == "grouped_attention"
             else d for d in LAYERS]
    assert flops_indexed_lm.layer_costs(plain)[1]["parts"]["core"] \
        * 8192 / GFLOP == pytest.approx(549.8, abs=0.1)


def test_the_cores_floor():
    """3 passes x 4 x 128 x 32 x 14,681,088 pairs = 0.7216 TFLOP a
    unit: 3.663 ms at 197 TFLOP/s, compute-bound (the least bytes, 6 x
    (32 + 4) heads x 8,192 x 128 x 2, take 0.55 ms)."""
    seconds, bound = flops_indexed_lm.selected_core_floor_s(
        LAYERS[1], 8192, 1, PEAKS)
    assert bound == "compute"
    assert seconds == pytest.approx(
        3 * 4 * 128 * 32 * 14681088 / 197e12) == pytest.approx(
        3.663e-3, rel=1e-3)
    assert 6 * 36 * 8192 * 128 * 2 / 819e9 == pytest.approx(0.553e-3,
                                                             rel=1e-2)


# -- the configuration's file -------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "Keye-VL-2.0-30B-A3B")


def test_published_widths_are_unchanged():
    row = catalog_row()
    entry = next(c for c in SPEC["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "dataset"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert 4 <= CONFIG["num_hidden_layers"] <= 6
    # the depth the driver's record states is the depth that runs
    assert int(re.search(r"(\d+) layers of 48", entry["why"]).group(1)) \
        == CONFIG["num_hidden_layers"] == sum(
            d["type"] == "grouped_attention" for d in LAYERS)
    assert (CONFIG["num_experts"], CONFIG["vocab_size"]) == (16, 18992)
    assert CONFIG["published"]["num_experts"] == 128
    assert 18992 * 8 == CONFIG["published"]["vocab_size"] == 151936
    for name in ("qk_norm", "index_input", "index_key_norm",
                 "index_rotary", "index_scale", "index_chunks",
                 "topk_convention", "positions", "index_objective",
                 "index_precision", "router_score_function", "optimizer",
                 "sequence_length", "initial_std", "embedding_initial_std",
                 "head_initial_std", "dispatch_rows"):
        assert name in CONFIG["assumed"], name
    assert "depth_decision" in CONFIG and "MB" in CONFIG["depth_decision"]
    assert CONFIG["optimizer"] == {
        "solver": "adam", "learning_rate": 3e-4, "beta1": 0.9,
        "beta2": 0.95, "epsilon": 1e-8, "weights_decay": 0.0,
        "warmup_steps": 40000}
    assert (CONFIG["batch"], CONFIG["precision"], CONFIG["trainer"]) == (
        1, "bfloat16", "fused")


def test_benchmark_json_gains_one_configuration_one_cell_ten_metrics():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "pretrain8k-1seq", 1)
    assert [w["name"] for w in SPEC["workloads"]
            if w["config"] == NAME] == [CELL]
    traffic = harness.load_json(HOME, "traffic", "pretrain8k-1seq.json")
    assert (traffic["driver"], traffic["stream"], traffic["n_train"],
            traffic["n_valid"], traffic["warm_epochs"],
            traffic["trace_epochs"], traffic["zipf_exponent"]) == (
        "epochs", False, 16, 4, 2, 1, 1.0)
    new = [m for m in SPEC["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW_METRICS
    for metric in new:
        spec = harness.load_json(HOME, "layer_metrics",
                                 metric["name"] + ".json")
        assert spec["reader"] == ("trace_indexed_lm" if metric["name"]
                                  in DSA_METRICS else "trace_lm")
        assert (spec["unit"], spec["layer"]) == (metric["unit"],
                                                 metric["layer"])
        assert metric["moves"] == "train_samples_per_s"
    bench = harness.Benchmark(ROOT)
    for kind in ("builders", "reference"):
        harness.load_module(HOME, kind, CONFIG["family"])
    harness.load_module(HOME, "readers", "trace_indexed_lm")
    names = {m["name"] for m in bench.metrics("per_layer", cell)}
    assert names == set(NEW_METRICS) | {
        "train_step_device_ms", "eval_step_device_ms", "mfu_pct",
        "device_idle_pct", "epoch_gap_pct", "input_wait_pct"}
    assert {m["name"] for m in bench.metrics("end_to_end", cell)} == {
        "train_samples_per_s", "eval_samples_per_s", "peak_hbm_mb",
        "setup_s"}


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(HOME, "reference", "indexed_moe_lm.py")) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        modules = [a.name for a in node.names] \
            if isinstance(node, ast.Import) else \
            [node.module] if isinstance(node, ast.ImportFrom) else []
        assert not any(m.startswith("veles_tpu") for m in modules)


# -- the harness, rehearsed --------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def a_registry_of_this_files_own():
    """The tiny cell's series (``veles_moe_routed_per_step{unit=
    "u02.moe2"}`` 192, ...) go when this file is done: the accepted
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
    return record_indexed_lm.tiny_root(
        str(tmp_path_factory.mktemp("root")))


def run(bench, trace):
    import jax
    lines = []
    result = harness.run_cell(bench, record_indexed_lm.CELL, 2**31 + 5,
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
    # reference's, its selection the reference's, and the workflow was
    # put back as it was
    assert report["gradient_error"] < 1e-5
    assert report["index_gradient_error"] < 1e-5
    assert report["index_loss_error"] < 1e-5
    assert report["selection_error"] == 0
    assert report["timed_selection_error"] == 0
    assert report["selected_places_error"] == 0
    assert report["selected_as_ruled"] is True
    assert report["update_error"] < 1e-3
    assert report["bias_error"] == 0 and report["routing_error"] == 0
    assert report["routed_per_token"] == report["top_k"] == [3] * 2
    assert set(report["loss_errors"]) == {"main", "index0", "index1"}
    assert max(report["loss_errors"].values()) < 1e-5
    assert report["validation_loss_error"] < 1e-5
    # the experts were placed before either side read a weight: the 8
    # held of 16 get their half of the 4 x 16 x 3 assignments a step
    placed = re.findall(r"(\d+) -> (\d+) of (\d+)", next(
        line for line in lines if "experts placed" in line))
    assert len(placed) == 2
    for _, held, routed in placed:
        assert int(routed) == 192 and abs(int(held) - 96) <= 2


# -- the placement of the experts --------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_balanced_packing_gives_every_chip_the_same_rows(seed):
    """128 loads as skewed as the cell's (one expert with four times
    the mean): 8 bins of 16 whose sums lie within 2% of 8,192, the
    heaviest expert in bin 0, every expert in one bin."""
    import numpy
    builder = harness.load_module(HOME, "builders", "indexed_moe_lm")
    rng = numpy.random.default_rng(seed)
    loads = rng.gamma(4.0, 1.0, 128)
    loads[rng.integers(128)] = 4 * loads.mean()
    loads *= 65536 / loads.sum()
    packs = builder.balanced_packing(loads, 8)
    assert sorted(e for pack in packs for e in pack) == list(range(128))
    assert {len(pack) for pack in packs} == {16}
    assert packs[0][0] == int(numpy.argmax(loads))
    for pack in packs:
        assert abs(loads[pack].sum() - 8192) < 0.02 * 8192


def test_a_placement_only_relabels_the_experts():
    """``place_experts`` permutes a router's columns and nothing else:
    every column the seed dealt is still there once, the held places
    hold bin 0 of the packing of the loads the units' own forward
    counts, and the rows the held experts then get are a chip's
    share."""
    import jax
    import numpy
    from benchmark.seeded_tokens import SeededTokenLoader
    from veles_tpu import prng
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.standard_workflow import StandardWorkflow
    builder = harness.load_module(HOME, "builders", "indexed_moe_lm")
    set_policy(TINY["precision"])
    prng.get().seed(11)
    layers = [dict(d) for d in TINY["layers"]]
    workflow = StandardWorkflow(
        DummyLauncher(), loader=lambda wf: SeededTokenLoader(
            wf, n_train=8, n_valid=4,
            length=layers[0]["positions"] + 1,
            vocabulary=layers[0]["vocabulary"], seed=13,
            minibatch_size=TINY["batch"]),
        layers=[dict(d) for d in layers], loss="softmax", solver="adam",
        learning_rate=0.003, momentum=0.0, weights_decay=0.0)
    workflow.initialize(device=None)
    sparse = [fwd for d, fwd in zip(layers, workflow.forwards)
              if d["type"] == "moe"]
    before = [{name: numpy.array(arr.map_read())
               for name, arr in fwd.param_arrays().items()}
              for fwd in workflow.forwards]
    lines = []
    builder.place_experts(workflow, layers, TINY["batch"],
                          jax.devices()[0], lines.append)
    moved = 0
    for was, fwd in zip(before, workflow.forwards):
        for name, arr in fwd.param_arrays().items():
            now = numpy.asarray(arr.map_read())
            if fwd in sparse and name == "weights":
                assert sorted(map(tuple, now.T)) == \
                    sorted(map(tuple, was[name].T))
                moved += int(not numpy.array_equal(now, was[name]))
            else:
                assert numpy.array_equal(now, was[name]), (fwd.name, name)
    assert moved >= 1
    placed = re.findall(r"(\d+) -> (\d+) of (\d+)", lines[-1])
    assert len(placed) == len(sparse) == 2
    for _, held, routed in placed:
        assert int(routed) == 4 * 16 * 3
        assert abs(int(held) - int(routed) // 2) <= 2


def test_the_traced_tiny_cell_reads_the_counter(bench):
    """No device plane on a CPU: the trace readers give nothing and
    raise nothing; the gauge is the program's count on the device and
    reads the selection's share of the causal triangle: 6 keys of 16
    positions, (21 + 10 x 6) / 136."""
    result, _ = run(bench, trace=True)
    assert set(result["metrics"]) & set(NEW_METRICS) == {
        "dsa_selected_over_causal", "routed_expert_load_max_over_mean"}
    ratio = result["metrics"]["dsa_selected_over_causal"]
    assert ratio["unit"] == "ratio" and ratio["value"] == 81 / 136
    assert result["metrics"]["routed_expert_load_max_over_mean"][
        "value"] >= 1


# -- the comparison that decides ``correct`` ---------------------------------

@pytest.fixture(scope="module")
def tiny_step():
    """``(reference module, layers, losses, the reference's step)`` of
    the tiny configuration on seeded weights and ids."""
    import numpy
    ref = harness.load_module(HOME, "reference", "indexed_moe_lm")
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
    for descr, fwd, p in zip(layers, workflow.forwards, params):
        descr["name"] = fwd.name
        # at a toy width a fill of 0.02 leaves every score near 0 and
        # every softmax flat: widened, so that a norm, a selection or
        # an index shows in the step as it does at 2,048
        if descr["type"] == "grouped_attention":
            for name in ("q", "k", "v", "o", "index_q", "index_k",
                         "index_w"):
                p[name] *= 10.0
        elif descr["type"] == "moe":
            p["weights"] *= 50.0

    def step(layers=layers, tokens=tokens):
        return ref.train_step(layers, params, tokens, tokens[:, 1:],
                              TINY["optimizer"])
    return ref, layers, numpy.array([4.1, 4.2]), step


def scaled(step, only=None, **factors):
    out = dict(step)
    for part, factor in factors.items():
        out[part] = [{k: factor * v if only is None or k in only else v
                      for k, v in d.items()} for d in step[part]]
    return out


def with_index(layers, **change):
    return [dict(d, index=dict(d["index"], **change))
            if d["type"] == "grouped_attention" else d for d in layers]


INDEX = ("index_q", "index_k", "index_w", "index_norm_gain",
         "index_norm_bias")
CONTROLS = {
    "the reference itself": (lambda step, again, layers: step, True),
    "no update at all": (lambda step, again, layers: scaled(
        step, changes=0.0, moments=0.0), False),
    "a rate twice too large": (lambda step, again, layers: scaled(
        step, changes=2.0), False),
    "half the batch": (lambda step, again, layers: again(half=True), False),
    "the index's objective left out": (
        lambda step, again, layers: scaled(
            step, only=INDEX, changes=0.0, moments=0.0), False),
    "the index's objective at twice its weight": (
        lambda step, again, layers: scaled(
            step, only=INDEX, moments=2.0), False),
    "the index's objective a sum over the positions": (
        lambda step, again, layers: dict(
            scaled(step, only=INDEX, moments=16.0), losses={
                k: v * (16.0 if k.startswith("index") else 1.0)
                for k, v in step["losses"].items()}), False),
    "one key more a query": (lambda step, again, layers: again(
        with_index(layers, top_k=7)), False),
    "every key before the query": (lambda step, again, layers: again(
        with_index(layers, top_k=16)), False),
    "the q/k norm left out": (lambda step, again, layers: again([
        dict(d, qk_norm=False) if d["type"] == "grouped_attention" else d
        for d in layers]), False),
    "sigmoid scores": (lambda step, again, layers: again([
        dict(d, scoring="sigmoid") if d["type"] == "moe" else d
        for d in layers]), False),
    "a token in four dropped": (lambda step, again, layers: dict(
        step, counts=[c - c // 4 for c in step["counts"]]), False),
    "a query that selected one key too few": (
        lambda step, again, layers: dict(step, selected=[
            s - (s == s.max()) * (i == 0)
            for i, s in enumerate(step["selected"])]), False),
    "a timed step that selected the newest keys": (
        lambda step, again, layers: dict(step, selected_places=[
            newest(s) for s in step["selected"]]), False),
    "a step that says nothing of which keys": (
        lambda step, again, layers: {
            k: v for k, v in step.items() if k != "selected_places"},
        False),
}


def newest(selected):
    """The sums of the positions, had every query selected the keys
    just before it."""
    import numpy
    t = numpy.arange(selected.shape[-1])
    return selected * t - selected * (selected - 1) // 2


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_agreement_tells_a_wrong_step(tiny_step, control):
    """Each control laid out as a program's step and taken through
    ``agreement``, the harness's own comparison: only the reference's
    step comes out correct."""
    import numpy
    ref, layers, losses, step = tiny_step
    expected = step()

    def again(other=None, half=False):
        if half:
            rng = numpy.random.default_rng(7)
            tokens = rng.integers(0, layers[0]["vocabulary"],
                                  (4, layers[0]["positions"] + 1))[:2]
            made = step(layers, tokens)
            # the other half's selection is not this one's to answer for
            return dict(made, **{k: expected[k] for k in (
                "selection", "selected", "selected_places")})
        return step(other)

    make, sound = CONTROLS[control]
    made = make(expected, again, layers)
    ok, report = ref.agreement(losses, {
        "losses": losses,
        "step": ref.step_comparison(layers, made, expected)})
    assert ok is sound, report
    for name in ("gradient", "index_gradient", "index_loss", "selection",
                 "timed_selection", "update", "update_scale"):
        assert name + "_tolerance" in report
        assert name + "_error" in report or name == "timed_selection" \
            and "nothing" in control


def test_the_limits_lie_between_their_readings():
    """The v5e's readings, as PERF.md section 6 has them: every limit
    of a precision between the program's largest reading over at least
    five seeds and the int8 reference's, with room on both sides; the
    limit on the update between the program's largest and 1 (a state
    left unchanged), the more room above."""
    ref = harness.load_module(HOME, "reference", "indexed_moe_lm")
    program, int8 = ref.READINGS["program"], ref.READINGS["int8"]
    for name, limit in (
            ("gradient_error", ref.GRADIENT_TOLERANCE),
            ("index_gradient_error", ref.INDEX_GRADIENT_TOLERANCE),
            ("index_loss_error", ref.INDEX_LOSS_TOLERANCE),
            ("selection_error", ref.SELECTION_TOLERANCE)):
        assert len(program[name]) >= 5, name
        assert max(program[name]) * 1.3 < limit < int8[name] / 1.3, name
    assert max(program["update_error"]) < ref.UPDATE_TOLERANCE < 1.0
    assert ref.UPDATE_TOLERANCE - max(program["update_error"]) \
        >= 1.0 - ref.UPDATE_TOLERANCE
    assert max(program["update_scale_error"]) * 10 \
        < ref.UPDATE_SCALE_TOLERANCE < 1.0


# -- the reader ----------------------------------------------------------------

def context_of(config, trace=None, traced=None):
    lines = []
    return {"trace": trace, "traced": traced, "counters": {},
            "config": config, "peaks": PEAKS, "chips": 1,
            "log": lines.append}, lines


def test_a_program_without_the_gauges_gives_nothing():
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
    """``record_indexed_lm.py``'s trace of the tiny configuration (16
    train steps of 4 sequences of 16): every attention unit has time
    under each of its four sub-scopes, forward and backward; the parts
    are inside the units' whole; the metrics that read them come out."""
    if not os.path.isfile(FIXTURE):
        pytest.skip("no fixture recorded yet")
    assert os.path.getsize(FIXTURE) < 1e6
    reader = harness.load_module(HOME, "readers", "trace_indexed_lm")
    from benchmark.readers import trace_scopes
    monkeypatch.setattr(trace_scopes, "trace_path", lambda: FIXTURE)
    context, lines = context_of(
        TINY, trace_reduce.reduce_file(FIXTURE),
        {"epochs": 1, "train_steps": 4, "eval_steps": 2, "compiled": 0})
    parts = reader.by_part(context)
    units = [i for i, d in enumerate(TINY["layers"])
             if d["type"] == "grouped_attention"]
    assert units == [1, 3]
    for i in units:
        for part in reader.PARTS:
            assert parts[i, part] > 0, (i, part)
    values = {}
    for name in NEW_METRICS:
        spec = harness.load_json(HOME, "layer_metrics", name + ".json")
        values[name] = harness.load_module(
            HOME, "readers", spec["reader"]).read(context, **spec["args"])
    whole = values["dsa_device_ms"]
    assert whole > 0 and values["routed_head_loss_device_ms"] > 0
    assert 0 < values["routed_moe_route_device_ms"] \
        < values["routed_moe_device_ms"]
    assert values["dsa_index_device_ms"] == pytest.approx(1e3 * sum(
        parts[i, p] for i in units for p in ("index", "select")))
    assert values["dsa_index_loss_device_ms"] == pytest.approx(1e3 * sum(
        parts[i, "index_loss"] for i in units))
    assert sum(parts.values()) * 1e3 < whole
    assert 0 < values["dsa_core_roofline"] < 100
    assert any(line.startswith("attention units by sub-scope")
               for line in lines)
