"""Performance attribution + flight recorder + perf gate (ISSUE 7).

The math is pinned against hand-computed values: a GEMM whose FLOPs
are known exactly (2·M·N·K from XLA's cost model), roofline verdicts
around an env-forced ridge point, MFU from a synthetic cost/time pair.
The flight recorder's detectors are driven with injected NaN losses,
a gradient-norm spike, and a stalled sweep; every record they write
must be loadable JSON naming the offending step. The perf gate's
pass/fail/tolerance semantics run against in-memory baselines."""

import json
import os
import sys
import time

import numpy
import pytest

from veles_tpu.telemetry import flight, profiler, tracing
from veles_tpu.telemetry.registry import MetricsRegistry

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


@pytest.fixture
def peaks(monkeypatch):
    """Known device roofline: 1 TFLOP/s, 100 GB/s => ridge 10 FLOP/B
    (the peak table is the one source; give it a row for this host)."""
    monkeypatch.setattr(profiler, "DEVICE_SPECS",
                        (("cpu", (1.0, 100.0)),))
    profiler.reset_cost_book()
    yield 1e12, 100e9
    profiler.reset_cost_book()


@pytest.fixture
def fresh_book():
    profiler.reset_cost_book()
    yield profiler.get_cost_book()
    profiler.reset_cost_book()


# -- cost attribution --------------------------------------------------------


def test_gemm_cost_analysis_hand_computed(fresh_book):
    """XLA's cost model must report exactly 2·M·N·K FLOPs for a GEMM
    (the hand-computable anchor for every derived number)."""
    import jax

    M, K, N = 64, 32, 16
    fn = jax.jit(lambda a, b: a @ b)
    a = numpy.zeros((M, K), numpy.float32)
    b = numpy.zeros((K, N), numpy.float32)
    cost = profiler.harvest_cost_analysis(fn.lower(a, b).compile())
    assert cost is not None
    assert cost["flops"] == 2 * M * N * K
    # operands + result at least touch their own bytes once
    assert cost["bytes"] >= 4 * (M * K + K * N + M * N)


def test_costbook_harvest_and_report(fresh_book, peaks):
    """harvest_function() populates gauges + report rows for a jitted
    fn: the entry of a caller that holds no executable
    (serving/replica.py)."""
    import jax

    book = fresh_book
    a = numpy.zeros((64, 32), numpy.float32)
    b = numpy.zeros((32, 16), numpy.float32)
    fn = jax.jit(lambda a, b: a @ b)
    assert book.needs_harvest("gemm")
    book.harvest_function("gemm", fn, (a, b))
    assert not book.needs_harvest("gemm")  # once per op
    book.harvest_function("gemm", None, ())  # not even looked at
    assert book.cost("gemm")["flops"] == 2 * 64 * 32 * 16
    book.observe_ms("gemm", 0.001)
    rows = {r["op"]: r for r in book.report()["ops"]}
    assert rows["gemm"]["calls"] == 1
    assert rows["gemm"]["p50_ms"] == pytest.approx(1.0)


#: the data set of PR 24's trace (chiprun_out/call1/alexnet.xplane.pb.gz)
#: and what its two programs did with it on every call, as the profile
#: quotes them; then the same program in ``compiled.as_text()``'s
#: spelling, and the minibatch gather inside the scan's body
DATASET = (17024, 58, 2784)
PADDED = 17024 * 64 * 2816 * 2  # {2,1,0:T(8,128)}: 58 -> 64, 2784 -> 2816
PARENT_TRAIN = (
    "%copy.16 = bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)} "
    "copy(bf16[17024,58,2784]{0,2,1:T(8,128)(2,1)} %data_args_0_.1)")
PARENT_EVAL = (
    "%copy.31 = bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)} "
    "copy(bf16[17024,58,2784]{0,2,1:T(8,128)(2,1)} %data_args_0_.1)")
GATHER = (
    "%fusion.134 = bf16[128,58,2784]{2,1,0:T(8,128)(2,1)S(1)} "
    "fusion(bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)} "
    "%get-tuple-element.648, s32[1024] %pad_clamp_fusion.2), "
    "kind=kCustom")
BY_NAME = """
ENTRY %main.1 (data_args_0_.1: bf16[17024,58,2784]) -> f32[5] {
  %data_args_0_.1 = bf16[17024,58,2784]{0,2,1:T(8,128)(2,1)} parameter(0), metadata={op_name="data_args[0]"}
  %copy.16 = bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)} copy(%data_args_0_.1), backend_config={"flag_configs":[]}
  %tuple.20 = (s32[]{:T(128)}, bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)}) tuple(%constant.60, %copy.16)
  %while.1 = (s32[]{:T(128)}, bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)}) while(%tuple.20), condition=%cond, body=%body
}
"""
IN_PLACE = """
%body (arg: (s32[], bf16[17024,16,10112])) -> (s32[], bf16[17024,16,10112]) {
  %arg = (s32[]{:T(128)}, bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.83 = bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %fusion.133 = bf16[128,16,10112]{2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.83, %pad_clamp_fusion.2), kind=kCustom, calls=%fused_computation.1
  %bitcast.7 = bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)} bitcast(%get-tuple-element.83)
  ROOT %tuple.19 = (s32[]{:T(128)}, bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)}) tuple(%add.15, %get-tuple-element.83)
}
ENTRY %main.1 (data_args_0_.1: bf16[17024,16,10112]) -> f32[5] {
  %data_args_0_.1 = bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)} parameter(0)
  %tuple.20 = (s32[]{:T(128)}, bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)}) tuple(%constant.60, %data_args_0_.1)
  %while.1 = (s32[]{:T(128)}, bf16[17024,16,10112]{2,1,0:T(8,128)(2,1)}) while(%tuple.20), condition=%cond, body=%body
}
"""
ASYNC = """
  %data.1 = bf16[17024,58,2784]{0,2,1:T(8,128)(2,1)} parameter(0)
  %copy-start.1 = (bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)}, bf16[17024,58,2784]{0,2,1:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%data.1)
  %copy-done.1 = bf16[17024,58,2784]{2,1,0:T(8,128)(2,1)} copy-done(%copy-start.1)
"""


@pytest.mark.parametrize("text,dataset,want", [
    (PARENT_TRAIN + "\n" + PARENT_EVAL, DATASET, 2 * PADDED),
    (PARENT_EVAL, DATASET, PADDED),
    (GATHER, DATASET, 0),
    (BY_NAME, DATASET, PADDED),
    (IN_PLACE, (17024, 16, 10112), 0),
    (ASYNC, DATASET, PADDED),
    (PARENT_TRAIN, (17024, 16, 10112), 0),
    ("%param.4 = f32[64,8,384]{1,2,0} parameter(0)\n"
     "%copy.2 = f32[64,8,384]{2,1,0} copy(%param.4)", (64, 8, 384),
     64 * 8 * 384 * 4),
], ids=["both-parent-programs", "one-parent-program", "gather-in-scan",
        "operand-by-name", "read-in-place", "async-pair-counts-once",
        "no-such-parameter", "untiled"])
def test_relayout_bytes_in_text(text, dataset, want):
    """The detector behind ``veles_dataset_relayout_bytes``: the padded
    bytes of every instruction that makes a full-size array out of
    the full-size data set; what only hands the buffer on, and the
    gather of a minibatch, count nothing."""
    assert profiler.relayout_bytes_in_text(text, dataset) == want


def test_dataset_relayout_gauge_set_once_per_op(fresh_book, monkeypatch):
    """The harvest thunk of each segment sets the gauge, once, under
    the trainer's op names; a CPU keeps every array in the order it
    is written, so both read 0."""
    from veles_tpu.telemetry.registry import get_registry
    from test_fused_trainer import build_s2d

    calls = []
    detect = profiler.dataset_relayout_bytes

    def counting(compiled, shape):
        calls.append(tuple(shape))
        return detect(compiled, shape)

    monkeypatch.setattr(profiler, "dataset_relayout_bytes", counting)
    trainer = build_s2d()
    assert len(trainer.train()) == 2  # two epochs, one harvest an op
    assert calls == [tuple(trainer._data_args[0].shape)] * 2
    gauge = get_registry().gauge("veles_dataset_relayout_bytes", "",
                                 labels=("op",))
    values = {labels["op"]: child.value
              for labels, child in gauge.series()}
    assert values["train_segment"] == values["eval_segment"] == 0


def test_report_roofline_math(fresh_book, peaks):
    """Achieved TFLOP/s, arithmetic intensity and the bound verdict
    from hand-computed numbers on a known roofline."""
    peak_flops, peak_bw = peaks
    book = fresh_book
    # op A: 1 GFLOP over 50 MB -> AI=20 FLOP/B >= ridge 10 -> compute
    book.note_cost("opA", 1e9, 5e7)
    book.observe_ms("opA", 0.002)  # 2ms -> 0.5 TFLOP/s, 50% util
    # op B: 1 MFLOP over 1 MB -> AI=1 < 10 -> memory bound
    book.note_cost("opB", 1e6, 1e6)
    book.observe_ms("opB", 0.001)
    report = book.report()
    assert report["device"]["ridge_flops_per_byte"] == pytest.approx(10.0)
    rows = {r["op"]: r for r in report["ops"]}
    assert rows["opA"]["arithmetic_intensity"] == pytest.approx(20.0)
    assert rows["opA"]["bound"] == "compute"
    assert rows["opA"]["achieved_tflops"] == pytest.approx(0.5)
    assert rows["opA"]["utilization"] == pytest.approx(0.5)
    assert rows["opB"]["bound"] == "memory"
    assert rows["opB"]["achieved_gbps"] == pytest.approx(1.0)


def test_step_mfu(fresh_book, peaks):
    """MFU = flops / time / peak; unknown cost or peak -> None."""
    book = fresh_book
    book.note_cost("train_segment", 5e9, 1e9)
    # 5 GFLOP in 10 ms on a 1 TFLOP/s device = 50% MFU
    assert book.record_step_mfu("train_segment", 0.010) == \
        pytest.approx(0.5)
    assert book.report()["step_mfu"] == pytest.approx(0.5)
    assert book.record_step_mfu("no_such_op", 0.010) is None


def test_device_spec_comes_from_the_table_alone(monkeypatch):
    """A device_kind the table does not list is unknown — no env
    value stands in for it or overrides a listed one."""
    monkeypatch.setenv("VELES_PEAK_TFLOPS", "1")
    monkeypatch.setenv("VELES_HBM_GBPS", "100")
    assert profiler.device_spec() == (None, None)  # CPU backend

    class V5e(object):
        device_kind = "TPU v5 lite"

    assert profiler.device_spec(V5e()) == (197e12, 819e9)


def test_memory_sampler_tolerates_malformed_env(monkeypatch):
    """An unparsable VELES_MEMORY_SAMPLE_S disables sampling instead
    of aborting the CLI entrypoints at startup."""
    monkeypatch.setenv("VELES_MEMORY_SAMPLE_S", "fast")
    assert profiler.start_memory_sampler() is None
    monkeypatch.setenv("VELES_MEMORY_SAMPLE_S", "0")
    assert profiler.start_memory_sampler() is None


def test_timed_op_records(fresh_book):
    with profiler.timed_op("tick", book=fresh_book):
        time.sleep(0.01)
    rows = {r["op"]: r for r in fresh_book.report()["ops"]}
    assert rows["tick"]["p50_ms"] >= 10.0


# -- startup phases ----------------------------------------------------------


def test_phases_accumulate_and_order():
    profiler.reset_phases()
    with profiler.phase("offload_plan"):
        time.sleep(0.01)
    with profiler.phase("offload_plan"):
        time.sleep(0.01)
    profiler.record_phase("dataset_load", 0.5)
    profiler.record_phase("first_step", 0.25)
    profiler.record_phase("zcustom", 0.1)
    report = profiler.phase_report()
    # canonical order first, extras appended
    assert list(report) == ["dataset_load", "offload_plan", "first_step",
                            "zcustom"]
    assert report["offload_plan"] >= 20.0       # two sleeps ACCUMULATE
    assert report["dataset_load"] == pytest.approx(500.0)
    profiler.reset_phases()


def test_canonical_phases_keep_their_order_and_each_has_a_recorder():
    """The names ``phase_report`` had before set-up rows, in the order
    it had them (``warmup`` is gone: nothing recorded it), and no name
    of ``PHASES`` that no code of the package records."""
    before = ["dataset_generate", "dataset_load", "compile",
              "replica_warmup", "pipeline_fill", "offload_plan",
              "first_step"]
    assert "warmup" not in profiler.PHASES
    assert len(set(profiler.PHASES)) == len(profiler.PHASES)
    kept = [name for name in profiler.PHASES if name in before]
    assert sorted(kept) == sorted(before)
    # offload_plan moved beside the residency phase that contains it;
    # the others stand as they stood
    kept.remove("offload_plan")
    before.remove("offload_plan")
    assert kept == before
    package = os.path.join(os.path.dirname(SCRIPTS), "veles_tpu")
    sources = []
    for folder, _, files in os.walk(package):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and not path.endswith(
                    os.path.join("telemetry", "profiler.py")):
                with open(path) as f:
                    sources.append(f.read())
    own = set(profiler.BUILD_STAGES.values()) | {
        "cache_read", "compile", "segment_first_call"}
    for name in profiler.PHASES:
        assert name in own or any(
            '"%s"' % name in text for text in sources), name


# -- a phase is a row: start, end, parent, attributes ----------------------


@pytest.fixture
def rows():
    profiler.reset_phases()
    yield profiler.phase_rows
    profiler.reset_phases()


def test_rows_carry_start_end_parent_and_attrs(rows):
    before = time.time()
    with profiler.phase("trainer_build", kind="t") as outer:
        with profiler.phase("dataset_stage") as inner:
            inner.attrs["bytes"] = 12
            time.sleep(0.005)
        profiler.record_phase("first_step", 0.001, step=3)
    after = time.time()
    got = {row.name: row for row in rows()}
    assert list(got) == ["dataset_stage", "first_step", "trainer_build"]
    build, stage, step = (got["trainer_build"], got["dataset_stage"],
                          got["first_step"])
    assert (build.parent, stage.parent, step.parent) == (
        None, build.id, build.id)
    assert build.id == outer.id < stage.id < step.id
    assert (build.attrs, stage.attrs, step.attrs) == (
        {"kind": "t"}, {"bytes": 12}, {"step": 3})
    # the tracing ring's wall clock, so that rows lie against time.time
    assert before - 0.01 <= build.start <= stage.start <= stage.end
    assert stage.end <= step.end <= build.end <= after + 0.01
    assert stage.end - stage.start >= 0.005
    assert step.end - step.start == pytest.approx(0.001, abs=1e-6)
    report = profiler.phase_report()
    assert list(report) == ["trainer_build", "dataset_stage", "first_step"]
    assert report["dataset_stage"] == pytest.approx(
        (stage.end - stage.start) * 1e3, abs=0.01)


def test_rows_nest_by_thread(rows):
    import threading

    inside = threading.Event()
    release = threading.Event()

    def other():
        with profiler.phase("dataset_load"):
            inside.set()
            release.wait(5)

    thread = threading.Thread(target=other)
    with profiler.phase("trainer_build"):
        thread.start()
        assert inside.wait(5)
        with profiler.phase("dataset_stage"):
            pass
        release.set()
        thread.join(5)
    assert not thread.is_alive()
    got = {row.name: row for row in rows()}
    assert got["dataset_load"].parent is None  # not the main thread's
    assert got["dataset_stage"].parent == got["trainer_build"].id


def test_a_phase_inside_one_of_its_name_is_part_of_it(rows):
    """A subclass's wrapped method calls its parent's: one row, and
    the total counts the time once."""
    @profiler.phased("params_place")
    def base():
        time.sleep(0.002)
        return 7

    @profiler.phased("params_place")
    def derived():
        return base() + 1

    assert derived() == 8
    assert [row.name for row in rows()] == ["params_place"]
    row, = rows()
    assert profiler.phase_report()["params_place"] == pytest.approx(
        (row.end - row.start) * 1e3, abs=0.01)


def test_an_exception_leaves_the_threads_stack_in_order(rows):
    with pytest.raises(KeyError):
        with profiler.phase("trainer_build"):
            profiler.phase("dataset_stage").__enter__()  # never closed
            raise KeyError("x")
    with profiler.phase("params_place"):
        pass
    got = {row.name: row for row in rows()}
    assert got["params_place"].parent is None


def test_rows_are_bounded_and_the_first_are_kept(rows, monkeypatch):
    monkeypatch.setattr(profiler, "MAX_PHASE_ROWS", 3)
    for i in range(5):
        profiler.record_phase("first_step", 0.001, i=i)
    assert [row.attrs["i"] for row in rows()] == [0, 1, 2]
    assert profiler.phase_report()["first_step"] == pytest.approx(5.0)


def test_process_started_is_before_the_import_and_says_its_source():
    started, source = profiler.process_started()
    assert source in ("os", "import")
    assert started <= tracing.to_wall_s(time.perf_counter())
    if source == "os":
        with open("/proc/self/stat") as f:
            assert f.read().split()[0] == str(os.getpid())
        assert started <= tracing._WALL_EPOCH + 0.02
    assert profiler.process_started() == (started, source)  # read once


# -- JAX's own stages of a build, as rows ----------------------------------


def stage_rows(program):
    return [(row.name, row.attrs["cause"]) for row in sorted(
        profiler.phase_rows(), key=lambda row: row.start)
        if row.attrs.get("program") == program]


def test_a_jit_called_twice_gives_its_stages_once(rows):
    import jax
    import jax.numpy as jnp

    profiler.watch_builds()
    profiler.watch_builds()  # once a process, however often asked

    def twice_called_program(x):
        return jnp.sin(x) * 3.0 + jnp.take(x, jnp.arange(2)).sum()

    fn = jax.jit(twice_called_program)
    x = numpy.arange(8, dtype=numpy.float32)
    built = profiler.build_count()
    fn(x).block_until_ready()
    first = stage_rows("twice_called_program")
    # a jitted helper traced inside (take, sum) is part of the trace
    assert [name for name, _ in first if name != "cache_read"] == [
        "trace", "lower", "build"]
    assert {cause for _, cause in first} == {"call"}
    assert profiler.build_count() == built + 1
    counted = len(rows())
    fn(x).block_until_ready()
    assert len(rows()) == counted
    assert profiler.build_count() == built + 1
    for row in rows():
        assert row.start <= row.end
        assert row.parent is None or row.name == "cache_read"
    # compile is what calls caused, whole stages only
    report = profiler.phase_report()
    assert report["compile"] == pytest.approx(
        report["trace"] + report["lower"] + report["build"], abs=0.01)


def test_a_stage_inside_a_stage_is_part_of_it(rows):
    """What JAX reports while a stage is open (a helper traced inside
    the trace, the functions a lowering rule traces, a build inside
    either) leaves no row and no object; a build among it is counted;
    the thread's stack is as it was."""
    trace, lower, build = (
        event for event, _ in sorted(profiler.BUILD_STAGES.items(),
                                     key=lambda kv: ("trace", "lower",
                                                     "build").index(kv[1])))
    built = profiler.build_count()
    depth = len(profiler._stack())
    with profiler.phase("trainer_build") as outer:
        profiler._stage_opened(lower, 10.0, fun_name="jit(seg)")
        for name in ("less", "add"):
            profiler._stage_opened(trace, 10.1, fun_name=name)
            profiler._stage_opened(trace, 10.2, fun_name="inner")
            profiler._stage_closed(trace, 10.2, 10.3, fun_name="inner")
            profiler._stage_closed(trace, 10.1, 10.4, fun_name=name)
        profiler._stage_opened(build, 10.5, fun_name="jit(tiny)")
        profiler._cache_read(profiler.CACHE_READ_EVENT, 0.01)
        profiler._stage_closed(build, 10.5, 10.6, fun_name="jit(tiny)")
        # one whose start was never announced, inside an open stage
        profiler._stage_closed(trace, 10.6, 10.7, fun_name="late")
        profiler._stage_closed(lower, 10.0, 11.0, fun_name="jit(seg)")
        # and one with nothing of JAX's open: a row under the phase
        profiler._stage_closed(trace, 11.0, 11.5, fun_name="alone")
    assert len(profiler._stack()) == depth
    assert profiler.build_count() == built + 1
    got = [(row.name, row.attrs.get("program"), row.parent)
           for row in rows() if row.name != "trainer_build"]
    assert got == [("lower", "seg", outer.id), ("trace", "alone", outer.id)]
    report = profiler.phase_report()
    assert report["lower"] == pytest.approx(1000.0)
    assert report["compile"] == pytest.approx(1500.0)
    assert "build" not in report and "cache_read" not in report


def test_stages_inside_a_cost_harvest_carry_its_cause(rows, fresh_book):
    import jax
    import jax.numpy as jnp

    profiler.watch_builds()

    def harvested_program(a, b):
        return jnp.tanh(a @ b)

    fn = jax.jit(harvested_program)
    a = numpy.ones((8, 4), numpy.float32)
    b = numpy.ones((4, 2), numpy.float32)
    fn(a, b).block_until_ready()
    called = profiler.phase_report()["compile"]
    shapes = (jax.ShapeDtypeStruct(a.shape, a.dtype),
              jax.ShapeDtypeStruct((4, 3), b.dtype))  # another program
    # a caller without an executable builds one for the reading
    with profiler.phase("cost_harvest", op="harvested") as harvest:
        fresh_book.harvest_function("harvested", fn, shapes)
    assert fresh_book.cost("harvested")["flops"] == 2 * 8 * 4 * 3
    by_cause = {}
    for row in rows():
        if row.attrs.get("program") == "harvested_program":
            by_cause.setdefault(row.attrs["cause"], []).append(row)
    assert {row.name for row in by_cause["call"]} >= {
        "trace", "lower", "build"}
    assert {row.name for row in by_cause["harvest"]} >= {
        "trace", "lower", "build"}
    assert all(row.parent == harvest.id for row in by_cause["harvest"]
               if row.name != "cache_read")
    assert all(row.parent is None for row in by_cause["call"]
               if row.name != "cache_read")
    # the harvest's own stages are in no "compile"
    assert profiler.phase_report()["compile"] == called
    series = {(labels["stage"], labels["cause"]): child.value
              for labels, child in profiler.get_registry().get(
                  "veles_program_builds_total").series()}
    assert series[("build", "harvest")] >= 1
    assert series[("build", "call")] >= 1
    # the phase wrote the one span the ring gets of a harvest
    harvest_row, = [r for r in rows() if r.name == "cost_harvest"]
    assert harvest_row.attrs == {"op": "harvested"}


def test_a_harvest_from_an_executable_builds_nothing(rows, fresh_book):
    """What a trainer does: it hands the harvest the executable it
    built and runs."""
    import jax
    import jax.numpy as jnp

    profiler.watch_builds()

    def held_program(data, w):
        return jnp.tanh(data @ w)

    data = numpy.ones((64, 32), numpy.float32)
    w = numpy.ones((32, 16), numpy.float32)
    compiled = jax.jit(held_program).lower(data, w).compile()
    builds, seen = profiler.build_count(), len(rows())
    with profiler.phase("cost_harvest", op="held") as harvest:
        fresh_book.harvest("held", compiled, dataset_shape=data.shape)
    assert profiler.build_count() == builds
    assert [row.name for row in rows()[seen:]] == ["cost_harvest"]
    assert not [row for row in rows() if row.parent == harvest.id
                or row.attrs.get("cause") == "harvest"]
    cost = fresh_book.cost("held")
    assert cost["flops"] >= 2 * 64 * 32 * 16
    assert cost == dict(profiler.harvest_cost_analysis(compiled),
                        collective_bytes=0, collective_count=0)
    gauges = profiler.get_registry()
    assert gauges.get("veles_op_flops").labels(op="held").value \
        == cost["flops"]
    assert gauges.get("veles_op_bytes").labels(op="held").value \
        == cost["bytes"]
    # nothing re-lays the (64, 32) operand out at full size
    assert gauges.get("veles_dataset_relayout_bytes").labels(
        op="held").value == 0
    # once an op, and never fatal: nothing to read is an empty entry
    assert not fresh_book.needs_harvest("held")
    fresh_book.harvest("nothing", None)
    assert fresh_book.cost("nothing") is None
    assert not fresh_book.needs_harvest("nothing")


def test_a_partitioned_executables_collectives_are_still_read(fresh_book):
    """A program over a mesh of 4 host devices: the harvest reads the
    compiler's collectives off the executable it is handed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(numpy.asarray(jax.devices()[:4]), ("data",))
    rows_sharded = NamedSharding(mesh, PartitionSpec("data"))
    replicated = NamedSharding(mesh, PartitionSpec())
    fn = jax.jit(lambda x, w: jnp.sum(x @ w, axis=0),
                 in_shardings=(rows_sharded, replicated),
                 out_shardings=replicated)
    x = jax.device_put(numpy.ones((64, 32), numpy.float32), rows_sharded)
    w = jax.device_put(numpy.ones((32, 16), numpy.float32), replicated)
    compiled = fn.lower(x, w).compile()
    fresh_book.harvest("sharded", compiled,
                       dataset_shape=rows_sharded.shard_shape(x.shape))
    cost = fresh_book.cost("sharded")
    assert cost["collective_count"] >= 1
    # the all-reduce of the 16 float32 sums, at least
    assert cost["collective_bytes"] >= 16 * 4
    assert cost["collective_bytes"] == \
        profiler.collective_bytes_estimate(compiled)["bytes"]
    assert profiler.get_registry().get("veles_op_collective_bytes").labels(
        op="sharded").value == cost["collective_bytes"]
    assert cost["flops"] > 0
    numpy.testing.assert_allclose(compiled(x, w), numpy.full(16, 64 * 32.0))


def test_the_harvest_writes_no_span_of_its_own(fresh_book):
    import jax

    buffer = tracing.enable(tracing.TraceBuffer())
    try:
        fn = jax.jit(lambda a: a * 2.0)
        with profiler.phase("cost_harvest", op="double"):
            fresh_book.harvest_function(
                "double", fn, (numpy.ones(4, numpy.float32),))
        names = [e["name"] for e in buffer.events()]
    finally:
        tracing.disable()
        profiler.reset_phases()
    assert names.count("phase:cost_harvest") == 1
    assert "cost_harvest" not in names
    event, = [e for e in buffer.events()
              if e["name"] == "phase:cost_harvest"]
    assert event["args"]["op"] == "double"


# -- memory ------------------------------------------------------------------


def test_memory_sample_host_rss():
    sample = profiler.sample_memory(MetricsRegistry())
    # CPU devices expose no memory_stats; host RSS is always there
    assert sample["host_rss_bytes"] > 0


def test_profile_report_shape(fresh_book):
    report = profiler.profile_report()
    for key in ("ops", "device", "step_mfu", "phases_ms", "memory",
                "flight_record"):
        assert key in report
    json.dumps(report)  # must be wire-clean as-is


# -- flight recorder ---------------------------------------------------------


@pytest.fixture
def recorder(tmp_path):
    rec = flight.FlightRecorder(out_dir=str(tmp_path),
                                min_dump_interval_s=0.0)
    yield rec
    rec.stop()


def test_nan_loss_trips_and_names_step(recorder):
    losses = numpy.array([0.5, 0.4, numpy.nan, 0.3])
    path = recorder.check_losses(losses, epoch=7, phase="train")
    assert path is not None and os.path.exists(path)
    record = flight.load_record(path)
    assert record["reason"] == "non_finite_loss"
    assert record["context"]["batch"] == 2
    assert "epoch 7 batch 2" in record["context"]["step"]
    # clean losses do not trip
    assert recorder.check_losses(numpy.ones(4), epoch=8) is None


def test_nan_dumps_are_rate_limited(tmp_path):
    rec = flight.FlightRecorder(out_dir=str(tmp_path),
                                min_dump_interval_s=3600.0)
    try:
        bad = numpy.array([numpy.inf])
        assert rec.check_losses(bad, epoch=0) is not None
        assert rec.check_losses(bad, epoch=1) is None  # suppressed
    finally:
        rec.stop()


def test_grad_norm_divergence(recorder):
    recorder.observe_grad_norms(numpy.full(40, 1.0), epoch=0)
    path = recorder.observe_grad_norms(
        numpy.array([1.0, 1.0, 1000.0]), epoch=1)
    assert path is not None
    record = flight.load_record(path)
    assert record["reason"] == "grad_norm_divergence"
    assert record["context"]["batch"] == 2
    assert record["context"]["norm"] == pytest.approx(1000.0)


def test_grad_norm_non_finite(recorder):
    path = recorder.observe_grad_norms(
        numpy.array([1.0, numpy.nan]), epoch=3)
    record = flight.load_record(path)
    assert record["reason"] == "non_finite_grad_norm"
    assert record["context"]["batch"] == 1


def test_grad_norm_needs_history(recorder):
    """A big first batch is a cold start, not a divergence."""
    assert recorder.observe_grad_norms(
        numpy.array([1e6]), epoch=0) is None


def test_stall_watchdog_fires_with_stacks(tmp_path):
    rec = flight.FlightRecorder(
        out_dir=str(tmp_path), stall_factor=1.0, stall_min_s=0.05,
        poll_s=0.02, min_dump_interval_s=0.0)
    try:
        for _ in range(4):  # build the rolling p95
            rec.observe_step("train", 0.01)
        rec.step_begin("train sweep epoch 1")
        deadline = time.time() + 5.0
        while rec.last_record_path() is None and time.time() < deadline:
            time.sleep(0.02)
        path = rec.last_record_path()
        assert path is not None, "watchdog never fired"
        record = flight.load_record(path)
        assert record["reason"] == "stall"
        assert record["context"]["step"] == "train sweep epoch 1"
        # the all-thread stack dump was written FIRST, next door
        assert record["stacks_file"] and os.path.exists(
            record["stacks_file"])
        with open(record["stacks_file"]) as f:
            assert "Thread" in f.read()
    finally:
        rec.stop()


def test_stall_watchdog_silent_on_completion(tmp_path):
    rec = flight.FlightRecorder(
        out_dir=str(tmp_path), stall_factor=10.0, stall_min_s=10.0,
        poll_s=0.02, min_dump_interval_s=0.0)
    try:
        for _ in range(4):
            rec.observe_step("train", 0.01)
        rec.step_begin("train sweep")
        rec.step_end()  # completed inside budget
        time.sleep(0.1)
        assert rec.last_record_path() is None
    finally:
        rec.stop()


def test_record_embeds_ring_and_logs(recorder):
    import logging
    recorder.observe_step("train", 0.25, loss=1.5, epoch=2)
    logging.getLogger("probe").error("the probe line")
    path = recorder.record_exception(ValueError("boom"), step="epoch 2")
    record = flight.load_record(path)
    assert record["context"]["exception"] == "ValueError"
    notes = [n for n in record["notes"] if n["kind"] == "step"]
    assert notes and notes[-1]["ms"] == pytest.approx(250.0)
    assert any("the probe line" in line["message"]
               for line in record["log_tail"])


def test_load_record_rejects_garbage(tmp_path):
    bad = tmp_path / "not_a_record.json"
    bad.write_text(json.dumps({"hello": 1}))
    with pytest.raises(ValueError):
        flight.load_record(str(bad))


def test_injected_nan_run_writes_flight_record(tmp_path, monkeypatch):
    """End-to-end: a training run whose data carries a NaN must leave
    a flight record naming the offending sweep (the acceptance-
    criterion path, in-process)."""
    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.models.mnist import MnistWorkflow

    monkeypatch.setenv("VELES_FLIGHT_DIR", str(tmp_path))
    flight.reset_recorder()
    rng = numpy.random.RandomState(0)
    x = rng.rand(80, 6, 6).astype(numpy.float32)
    y = (x.reshape(80, -1).sum(1) > 18).astype(numpy.int32)
    x[5, 0, 0] = numpy.nan  # train sample 5: first sweep goes NaN
    prng.get().seed(42)
    prng.get("loader").seed(43)
    launcher = Launcher(graphics=False)
    wf = MnistWorkflow(
        launcher,
        provider=lambda: (x[:60], y[:60], x[60:], y[60:]),
        layers=(8,), minibatch_size=20, max_epochs=2)
    launcher.initialize()
    try:
        launcher.run()
        path = flight.last_record_path()
        assert path is not None, "no flight record written"
        record = flight.load_record(path)
        assert record["reason"] in ("non_finite_loss",
                                    "non_finite_grad_norm")
        assert "batch" in record["context"]
        assert "step" in record["context"]
    finally:
        flight.reset_recorder()


# -- perf gate ---------------------------------------------------------------


@pytest.fixture
def perf_gate():
    sys.path.insert(0, SCRIPTS)
    try:
        import perf_gate
        yield perf_gate
    finally:
        sys.path.remove(SCRIPTS)


def _snap(**metrics):
    return {"metrics": metrics}


def _base(**metrics):
    return {"metrics": metrics}


def test_gate_passes_within_tolerance(perf_gate):
    failures, lines = perf_gate.compare(
        _snap(loss=0.30),
        _base(loss={"value": 0.28, "tolerance": 0.25,
                    "direction": "lower", "gate": "hard"}))
    assert failures == []


def test_gate_fails_beyond_tolerance(perf_gate):
    failures, _ = perf_gate.compare(
        _snap(loss=0.40),
        _base(loss={"value": 0.28, "tolerance": 0.25,
                    "direction": "lower", "gate": "hard"}))
    assert len(failures) == 1 and "loss" in failures[0]


def test_gate_direction_higher(perf_gate):
    base = _base(qps={"value": 100.0, "tolerance": 0.1,
                      "direction": "higher", "gate": "hard"})
    assert perf_gate.compare(_snap(qps=95.0), base)[0] == []
    failures, _ = perf_gate.compare(_snap(qps=80.0), base)
    assert len(failures) == 1


def test_gate_report_only_never_fails(perf_gate):
    failures, lines = perf_gate.compare(
        _snap(ms=999.0),
        _base(ms={"value": 10.0, "tolerance": 0.1,
                  "direction": "lower", "gate": "report"}))
    assert failures == []
    assert any("REGRESS" in line for line in lines)


def test_gate_missing_hard_metric_fails(perf_gate):
    failures, _ = perf_gate.compare(
        _snap(),
        _base(loss={"value": 0.3, "tolerance": 0.1,
                    "direction": "lower", "gate": "hard"}))
    assert len(failures) == 1 and "MISSING" in failures[0]


def test_gate_zero_tolerance_exact(perf_gate):
    base = _base(epochs={"value": 4.0, "tolerance": 0.0,
                         "direction": "higher", "gate": "hard"})
    assert perf_gate.compare(_snap(epochs=4.0), base)[0] == []
    assert len(perf_gate.compare(_snap(epochs=3.0), base)[0]) == 1


def test_gate_head_passes_committed_regressed_fails(perf_gate,
                                                    tmp_path):
    """The CI contract, minus the probe run: a snapshot matching the
    committed baseline passes; the regressed fixture rejects it."""
    baseline = json.load(open(os.path.join(SCRIPTS,
                                           "perf_baseline.json")))
    snap = {"metrics": {name: policy["value"]
                        for name, policy in
                        baseline["metrics"].items()}}
    assert perf_gate.compare(snap, baseline)[0] == []
    regressed = json.load(open(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures",
        "perf_baseline_regressed.json")))
    failures, _ = perf_gate.compare(snap, regressed)
    assert failures, "regressed fixture must reject a HEAD snapshot"
