"""``benchmark/trace_reduce.py``: its interval arithmetic by hand, and
the whole reduction on traces recorded on a TPU v5e (PR 22), trimmed to
the lines it reads and kept under ``benchmark/fixtures``."""

import os

import pytest

from benchmark import trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def test_union_and_clip():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]
    assert trace_reduce._clip([(0, 3), (5, 8), (9, 12)], 2, 10) == [
        (2, 3), (5, 8), (9, 10)]


def test_self_time_takes_nested_operations_out():
    # a while of 100 holding two body ops of 30 and 20, one of which
    # holds a 5; then a plain op of 10
    events = [(0, 100, "while"), (10, 40, "a"), (50, 70, "b"),
              (55, 60, "c"), (100, 110, "d")]
    got = {name: self_ns for _, _, self_ns, name
           in trace_reduce._self_times(events)}
    assert got == {"while": 50, "a": 30, "b": 15, "c": 5, "d": 10}
    assert sum(got.values()) == 110  # nothing counted twice


def test_buckets_are_files_below_the_package():
    bucket = trace_reduce.bucket_of
    assert bucket("/root/repo/veles_tpu/nn/conv.py:190", "x") == "nn/conv.py"
    assert bucket("/x/veles_tpu/train/step.py:12", None) == "train/step.py"
    assert bucket("/venv/jax/_src/numpy/reductions.py:7", "") == \
        "reductions.py"
    assert bucket(None, "data formatting") == "<data formatting>"
    assert bucket("", None) == "<no source>"


# -- the whole reduction on a recorded trace --------------------------------
#
# ``tiny-dp4.v5e-2x2.xplane.pb``: one traced epoch (2 validation and 8
# train steps) of ``tests/configs/tiny-dp4.json`` through
# ``harness.run_cell`` on the four chips of a v5e host (PR 22), cut down
# to the device planes' ``XLA Ops`` and ``XLA Modules`` lines, the
# ``source`` and ``hlo_category`` stats and the host's ``bench:`` spans.
# The expected numbers were NOT made by this module: they come from a
# brute-force reduction of the same file with tensorflow's xplane proto
# (every operation against every other for nesting, picoseconds), run
# once in PR 22. ``ProfileData`` hands out nanoseconds, hence 1e-3.

FIXTURE = os.path.join(FIXTURES, "tiny-dp4.v5e-2x2.xplane.pb")
EXPECTED = {  # plane: (ops, busy_s, train self_s, train collective_s)
    "/device:TPU:0": (897, 0.000491491012, 0.000423472654, 0.000225175158),
    "/device:TPU:1": (879, 0.000482439608, 0.000422865546, 0.000223446952),
    "/device:TPU:2": (879, 0.000482008986, 0.000422585938, 0.000224147500),
    "/device:TPU:3": (879, 0.000481118826, 0.000421669452, 0.000223584766),
}
EXPECTED_BUCKETS_TPU0 = {"<collective>": 0.000263745,
                         "train/step.py": 0.000122797,
                         "nn/conv.py": 3.7186e-05, "nn/optim.py": 1.6817e-05}


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(FIXTURE)


def test_recorded_trace_devices_busy_and_programs(reduced):
    assert reduced.window_s == pytest.approx(0.018064798, rel=1e-6)
    assert sorted(name for name, _, _ in reduced.spans) == [
        "bench:epoch", "bench:eval_sweep", "bench:train_sweep"]
    assert [d.name for d in reduced.devices] == sorted(EXPECTED)
    for device in reduced.devices:
        ops, busy, train, collective = EXPECTED[device.name]
        assert len(device.ops) == ops
        assert device.busy_ns / 1e9 == pytest.approx(busy, rel=1e-3)
        assert device.self_seconds("train_segment") == pytest.approx(
            train, rel=1e-3)
        exposed, hidden = device.collective_seconds("train_segment")
        assert exposed == pytest.approx(collective, rel=1e-3)
        assert hidden == 0.0  # every collective here is synchronous
    assert reduced.busy_s == pytest.approx(
        sum(v[1] for v in EXPECTED.values()) / 4, rel=1e-3)
    # the device with most exposed collective time speaks for the cell
    assert reduced.collective_seconds("train_segment")[0] == \
        pytest.approx(0.000225175158, rel=1e-3)


def test_recorded_trace_buckets_and_breakdown(reduced):
    first = reduced.devices[0]
    for bucket, seconds in EXPECTED_BUCKETS_TPU0.items():
        assert first.self_seconds(bucket=bucket) == pytest.approx(
            seconds, rel=2e-3)
    # self times add up to busy time: nothing is counted twice, and a
    # collective is in no layer's bucket
    total = sum(op.self_ns for op in first.ops)
    assert total == pytest.approx(first.busy_ns, rel=1e-6)
    breakdown = reduced.breakdown()
    assert set(breakdown) == {"device_ops", "idle_gaps"}
    assert breakdown["device_ops"][0][0] == "<collective>"
    assert len(breakdown["device_ops"]) <= 10
    idle = dict(breakdown["idle_gaps"])
    assert set(idle) == {"bench:train_sweep", "bench:eval_sweep",
                         "bench:epoch_boundary"}
    assert sum(idle.values()) == pytest.approx(
        reduced.window_s - first.busy_ns / 1e9, rel=1e-6)


def test_readers_on_the_recorded_trace():
    from benchmark import harness
    reduced = trace_reduce.reduce_file(FIXTURE)  # changed below
    home = os.path.dirname(FIXTURES)
    lines = []
    context = {
        "trace": reduced, "traced": {"train_steps": 8, "eval_steps": 2},
        "counters": {"window_s": 2.0, "gap_s": 0.5, "input_wait_s": 0.0},
        "config": harness.load_json(home, "tests", "configs",
                                    "tiny-dp4.json"),
        "peaks": harness.load_json(home, "peaks.json")["devices"][
            "TPU v5 lite"],
        "chips": 4, "log": lines.append}

    def read(metric):
        spec = harness.load_json(home, "layer_metrics", metric + ".json")
        reader = harness.load_module(home, "readers", spec["reader"])
        return reader.read(context, **spec["args"])

    assert read("train_step_device_ms") == pytest.approx(
        sum(v[2] for v in EXPECTED.values()) / 4 / 8 * 1e3, rel=1e-3)
    assert read("collective_ms") == read("collective_exposed_ms") == \
        pytest.approx(0.000225175158 / 8 * 1e3, rel=1e-3)
    assert read("device_idle_pct") == pytest.approx(
        100 * (1 - reduced.busy_s / reduced.window_s))
    assert read("epoch_gap_pct") == 25.0
    assert read("input_wait_pct") == 0.0
    assert read("mfu_pct") is None  # the driver supplied no rate
    assert 0 < read("conv_roofline") < 100
    assert any("compute-bound" in line or "memory-bound" in line
               for line in lines)
    # a one-chip trace has no collective: the reader returns nothing
    for device in reduced.devices:
        device.ops = [op for op in device.ops
                      if op.bucket != trace_reduce.COLLECTIVE_BUCKET]
    assert read("collective_ms") is None


def test_metadata_is_read_from_the_wire_format():
    stats = trace_reduce.metadata_stats(FIXTURE)
    device = stats["/device:TPU:0"]
    sources = {s.get("source", "").rsplit(":", 1)[0].split("veles_tpu/")[-1]
               for s in device.values()}
    assert {"nn/conv.py", "nn/all2all.py", "nn/normalization.py",
            "nn/pooling.py", "train/step.py"} <= sources
    assert "all-reduce" in {s.get("hlo_category") for s in device.values()}
