"""The set-up metrics rehearsed on the CPU: the five metric files
resolve to the reader ``program_phases``, its table adds up, the tiny
cells report all five, and a program without start-up rows reports
none."""

import collections
import os

import pytest

from benchmark import harness
from test_harness import make_root, run, tiny_spec

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
ROOT = os.path.dirname(HOME)
METRICS = {
    "setup_data_stage_s": ("Loader and residency", "s", "lower",
                           "data_stage"),
    "setup_trace_lower_s": ("Step compiler", "s", "lower", "trace_lower"),
    "setup_build_s": ("Step compiler", "s", "lower", "build"),
    "setup_cost_harvest_s": ("Step compiler", "s", "lower",
                             "cost_harvest"),
    "setup_accounted_pct": ("Epoch loop", "%", "higher", "accounted"),
}
Row = collections.namedtuple("Row", "id name start end parent attrs")


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(HOME, "readers", "program_phases")


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The tiny cells under the metrics every cell reports: one that
    lists its cells reads a family's trace or a mesh's."""
    spec = tiny_spec()
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if "workloads" not in m]
    return make_root(tmp_path_factory.mktemp("root"), spec)


def test_the_five_metrics_are_the_last_entries_and_find_the_reader():
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    entries = spec["per_layer"][-5:]
    assert [m["name"] for m in entries] == list(METRICS)
    layers = {m["layer"] for m in spec["per_layer"][:-5]}
    for entry in entries:
        layer, unit, better, value = METRICS[entry["name"]]
        assert entry == {"name": entry["name"], "unit": unit,
                         "better": better, "source": "program_counter",
                         "layer": layer, "moves": "setup_s"}
        assert layer in layers  # a layer the benchmark already names
        file = harness.load_json(HOME, "layer_metrics",
                                 entry["name"] + ".json")
        assert file == {"name": entry["name"], "layer": layer,
                        "unit": unit, "reader": "program_phases",
                        "args": {"value": value}, "moves": "setup_s"}
    # nothing else of the benchmark moves setup_s, and nothing before
    # these entries was touched by them
    assert [m["name"] for m in spec["per_layer"]
            if m["moves"] == "setup_s"] == list(METRICS)


def stage(row_id, name, start, end, parent, program="seg", cause="call"):
    return Row(row_id, name, start, end, parent,
               {"program": program, "cause": cause})


def handmade():
    """A process that started at 100.0: a build phase with a data
    stage that builds a program, a first call, a harvest, a row of
    another thread across two of them, two warm-up epochs."""
    return [
        Row(1, "trainer_build", 103.0, 108.0, None, {}),
        Row(2, "dataset_stage", 103.5, 107.0, 1, {"bytes": 9}),
        stage(3, "trace", 104.0, 104.5, 2, "pack"),
        stage(4, "build", 104.5, 105.5, 2, "pack"),
        stage(5, "cache_read", 105.0, 105.4, 4, "pack"),
        Row(6, "dataset_shard", 107.0, 107.75, 1, {"shards": 4}),
        Row(7, "params_place", 108.0, 108.25, None, {}),
        Row(8, "epoch", 110.0, 120.0, None, {"epoch": 0, "builds": 2}),
        Row(9, "segment_first_call", 110.0, 114.0, 8, {"op": "t"}),
        stage(10, "trace", 110.0, 111.0, 9),
        stage(11, "lower", 111.0, 111.5, 9),
        stage(12, "build", 111.5, 114.0, 9),
        Row(13, "cost_harvest", 114.0, 117.0, 8, {"op": "t"}),
        stage(14, "lower", 114.0, 115.0, 13, cause="harvest"),
        stage(15, "build", 115.0, 117.0, 13, cause="harvest"),
        # a worker thread's row that lies across the call and the harvest
        Row(16, "dataset_load", 113.0, 116.0, None, {}),
        Row(17, "epoch", 120.0, 124.0, None, {"epoch": 1, "builds": 1}),
        Row(18, "epoch", 124.0, 128.0, None, {"epoch": 2, "builds": 0}),
        Row(19, "epoch", 128.0, 132.0, None, {"epoch": 3, "builds": 0}),
    ]


def test_the_table_adds_up_and_the_four_are_disjoint(reader):
    lines = []
    made = reader.account(handmade(), 100.0, "os", lines.append)
    assert made["span"] == 24.0  # to epoch 2's start
    assert made["head"] == 3.0
    own = made["self_times"]
    assert made["head"] + sum(own.values()) + made["rest"] == \
        pytest.approx(made["span"], abs=1e-3)
    # a row's self time: its duration less what its children cover
    assert own[1] == pytest.approx(5.0 - 3.5 - 0.75)
    assert own[2] == pytest.approx(3.5 - 0.5 - 1.0)
    assert own[4] == pytest.approx(1.0 - 0.4)
    assert own[8] == pytest.approx(10.0 - 4.0 - 3.0)
    # the other thread's row takes what it covers from where it
    # started on (the later start is the inner row)
    # (113-114 is its own, taken from build 12; from 114 on the
    # harvest's stages started later and are the inner rows)
    assert own[16] == pytest.approx(3.0 - 2.0)
    assert made["rest"] == pytest.approx(24.0 - 3.0 - (5.0 + 0.25 + 14.0))
    assert made["data_stage"] == pytest.approx(own[2] + own[6])
    assert made["trace_lower"] == pytest.approx(0.5 + 1.0 + 0.5)
    assert made["build"] == pytest.approx(1.0 + own[12])
    assert made["cost_harvest"] == pytest.approx(3.0)
    assert made["accounted"] == pytest.approx(100.0 * 19.25 / 24.0)
    assert made["data_stage"] + made["trace_lower"] + made["build"] \
        + made["cost_harvest"] <= made["covered"]
    text = "\n".join(lines)
    assert "first steady epoch (epoch 2): 24.000 s" in text
    assert "<head: before the first row>" in text
    assert "<rest: between the rows>" in text
    seg, = [line for line in lines if line.startswith("  seg ")]
    assert seg.split()[1:] == [
        "1.000", "0.500", "2.500", "(", "0.000)", "|",
        "0.000", "1.000", "2.000", "(", "0.000)"]
    pack, = [line for line in lines if line.startswith("  pack ")]
    assert pack.split()[1:6] == ["0.500", "0.000", "1.000", "(", "0.400)"]


def test_no_steady_epoch_gives_no_value(reader):
    lines = []
    rows = [row for row in handmade()
            if row.name != "epoch" or row.attrs["builds"]]
    assert reader.account(rows, 100.0, "os", lines.append) is None
    assert "no value" in lines[-1]


@pytest.mark.parametrize("cell", ["tiny.resident", "tiny-dp4.resident"])
def test_a_tiny_cell_reports_the_five_and_logs_the_table(bench, reader,
                                                         cell):
    from veles_tpu.telemetry import profiler
    profiler.reset_phases()
    profiler.reset_cost_book()
    result, lines = run(bench, cell, trace=True)
    values = {name: result["metrics"][name] for name in METRICS}
    for name, (_, unit, _, _) in METRICS.items():
        assert values[name]["unit"] == unit
        assert values[name]["value"] >= 0.0
    assert 0.0 < values["setup_accounted_pct"]["value"] <= 100.0
    assert values["setup_trace_lower_s"]["value"] > 0.0
    assert values["setup_build_s"]["value"] > 0.0
    assert values["setup_cost_harvest_s"]["value"] > 0.0
    assert values["setup_data_stage_s"]["value"] > 0.0
    text = "\n".join(lines)
    assert "set-up by the program's own phases" in text
    assert "trainer_build" in text and "train_segment" in text
    names = {row.name for row in profiler.phase_rows()}
    assert ("dataset_shard" in names) == (cell == "tiny-dp4.resident")
    # the same rows again, by hand: the parts make the whole
    started, source = profiler.process_started()
    made = reader.account(profiler.phase_rows(), started, source,
                          lambda line: None)
    assert made["head"] + sum(made["self_times"].values()) \
        + made["rest"] == pytest.approx(made["span"], abs=1e-3)
    for name, (_, _, _, value) in METRICS.items():
        assert made[value] == pytest.approx(values[name]["value"])
    seconds = sum(made[v] for v in ("data_stage", "trace_lower", "build",
                                    "cost_harvest"))
    assert seconds <= made["covered"] + 1e-9 <= made["span"]
    # every metric the cell printed before is still there
    assert {"epoch_gap_pct", "input_wait_pct"} <= set(result["metrics"])


def test_a_program_without_rows_gives_none_five_times(reader, monkeypatch):
    from veles_tpu.telemetry import profiler
    monkeypatch.delattr(profiler, "phase_rows")
    context = {"log": lambda line: None}
    for _, _, _, value in METRICS.values():
        assert reader.read(context, value) is None
