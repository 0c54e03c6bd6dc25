"""Where a process's time goes before its first steady epoch: a tiny
trainer of each kind, driven through the public ``run_epoch`` on forced
host devices, leaves the start-up rows the benchmark's reader
``program_phases`` and an operator's ``veles_startup_s`` stand on."""

import time

import jax
import jax.monitoring
import pytest

from test_gspmd import _build_wf

from veles_tpu.parallel.dp import DataParallelTrainer
from veles_tpu.parallel.gspmd import GSPMDTrainer
from veles_tpu.parallel.mesh import build_mesh
from veles_tpu.telemetry import profiler, tracing
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer

#: every event JAX reports while a test runs, whoever listens
EVENTS = []
STAGES = ("trace", "lower", "build", "cache_read")


@pytest.fixture(scope="module", autouse=True)
def jax_events():
    def event(name, *_, **__):
        EVENTS.append(name)
    jax.monitoring.register_event_listener(event)
    jax.monitoring.register_scalar_listener(event)
    jax.monitoring.register_event_duration_secs_listener(event)
    jax.monitoring.register_event_time_span_listener(event)
    yield
    jax.monitoring.unregister_event_listener(event)
    jax.monitoring.unregister_scalar_listener(event)
    jax.monitoring.unregister_event_duration_listener(event)
    jax.monitoring.unregister_event_time_span_listener(event)


def make_trainer(kind, workflow):
    if kind == "fused":
        return FusedTrainer(workflow)
    if kind == "streamed":
        return FusedTrainer(workflow, stream=True)
    if kind == "offloaded":
        return FusedTrainer(workflow, offload=True)
    if kind == "dp":
        return DataParallelTrainer(
            workflow, mesh=build_mesh(devices=jax.devices()[:4]))
    return GSPMDTrainer(workflow)


class Run(object):
    """Four epochs of a fresh trainer of ``kind`` and what they left."""

    def __init__(self, kind):
        workflow = _build_wf()
        profiler.reset_phases()
        profiler.reset_cost_book()  # a harvest is once an op a process
        self.trainer = make_trainer(kind, workflow)
        state = self.trainer.pull_params()
        self.steady_events = None
        self.startup = []
        for epoch in range(4):
            seen, rows = len(EVENTS), len(profiler.phase_rows())
            params, states, _ = self.trainer.run_epoch(*state, epoch)
            state = (params, states)
            self.startup.append(startup_gauge())
            if epoch == 3:
                self.steady_events = EVENTS[seen:]
                self.steady_rows = profiler.phase_rows()[rows:]
        self.trainer.shutdown()
        self.rows = profiler.phase_rows()
        self.report = profiler.phase_report()
        self.by_id = {row.id: row for row in self.rows}
        profiler.reset_phases()
        profiler.reset_cost_book()

    def named(self, name):
        return sorted((row for row in self.rows if row.name == name),
                      key=lambda row: row.start)

    def parent(self, row):
        return self.by_id.get(row.parent)


def startup_gauge():
    try:
        return get_registry().get("veles_startup_s").value
    except (KeyError, ValueError, AttributeError):
        return None


@pytest.fixture(scope="module", params=["fused", "streamed", "offloaded",
                                        "dp", "gspmd"])
def run(request):
    return Run(request.param), request.param


def test_the_trainers_build_is_one_row_over_its_parts(run):
    run, kind = run
    build, = run.named("trainer_build")  # a subclass's is part of it
    assert build.parent is None
    stage, = run.named("dataset_stage")
    residency, = run.named("model_residency")
    assert run.parent(stage) is build and run.parent(residency) is build
    assert build.start <= stage.start <= stage.end <= residency.start
    assert residency.end <= build.end
    assert stage.attrs["bytes"] == run.trainer.dataset_bytes > 0
    assert stage.attrs["streaming"] == (kind == "streamed")
    plans = run.named("offload_plan")
    assert len(plans) == (kind == "offloaded")
    for plan in plans:
        assert run.parent(plan) is residency
    shards = run.named("dataset_shard")
    assert len(shards) == (kind in ("dp", "gspmd"))
    for shard in shards:
        assert run.parent(shard) is build and shard.start >= residency.end
        assert shard.attrs == {
            "shards": 4 if kind == "dp" else 8,
            "bytes": sum(a.nbytes for a in run.trainer._data_args)}
    place, = run.named("params_place")
    assert place.parent is None and place.start >= build.end


def test_each_built_segment_has_one_first_call_with_its_stages(run):
    run, kind = run
    prefix = "gspmd_" if kind == "gspmd" else ""
    calls = run.named("segment_first_call")
    assert {call.attrs["op"] for call in calls} == {
        prefix + "eval_segment", prefix + "train_segment"}
    for call in calls:
        assert run.parent(call).name == "epoch"
        assert call.attrs["builds"] >= 1
        built = [row for row in run.rows if row.parent == call.id
                 and row.name == "build"]
        # the offload engine walks layer groups on threads of its own
        assert built or kind == "offloaded"
        for row in built:
            assert row.attrs["cause"] == "call"
            assert call.start <= row.start <= row.end <= call.end + 1e-3
    if kind == "offloaded":
        assert not any(call.attrs["committed"] for call in calls)
        return
    programs = {row.attrs["program"] for call in calls
                for row in run.rows if row.parent == call.id}
    assert {"eval_segment_pure", "train_segment"} <= programs
    train = [call for call in calls
             if call.attrs["op"].endswith("train_segment")]
    # the first call's optimizer state was made on the host side; a
    # later call that builds again was handed a program's outputs
    assert train[0].attrs["committed"] is (kind in ("dp", "gspmd"))
    assert all(call.attrs["committed"] for call in train[1:])


def test_a_cost_harvest_names_its_own_stages(run):
    run, kind = run
    harvests = run.named("cost_harvest")
    if kind == "offloaded":  # the engine counts its transfers itself
        return
    assert len(harvests) == 2
    for harvest in harvests:
        inside = [row for row in run.rows if row.parent == harvest.id]
        assert inside and {row.name for row in inside} <= set(STAGES)
        assert {row.attrs["cause"] for row in inside} == {"harvest"}
    outside = [row for row in run.rows if row.name in STAGES
               and row.attrs["cause"] == "harvest"
               and run.parent(row).name not in ("cost_harvest",) + STAGES]
    assert not outside


def test_epoch_rows_fall_to_no_builds_and_startup_is_set_once(run):
    run, _ = run
    epochs = run.named("epoch")
    assert [row.attrs["epoch"] for row in epochs] == [0, 1, 2, 3]
    builds = [row.attrs["builds"] for row in epochs]
    assert builds[0] > 0 and builds[2:] == [0, 0]
    steady = next(row for row in epochs if not row.attrs["builds"])
    started, _ = profiler.process_started()
    first = steady.attrs["epoch"]
    assert run.startup[first] == pytest.approx(steady.start - started,
                                               abs=1e-6)
    assert all(value == run.startup[first]
               for value in run.startup[first:])
    assert "epoch" not in run.report  # a row, no start-up total


def test_a_steady_epoch_calls_no_listener(run):
    run, kind = run
    assert run.steady_events == []
    assert [row.name for row in run.steady_rows] == ["epoch"] or (
        kind == "streamed" and {row.name for row in run.steady_rows}
        == {"epoch", "pipeline_fill"})


def test_compile_is_what_calls_built(run):
    run, _ = run
    whole = [row for row in run.rows
             if row.name in ("trace", "lower", "build")
             and (run.parent(row) is None
                  or run.parent(row).name not in STAGES)]
    called = sum(row.end - row.start for row in whole
                 if row.attrs["cause"] == "call")
    assert run.report["compile"] == pytest.approx(called * 1e3, abs=0.05)
    # the harvest's are in the stages' own totals and in no compile
    harvested = sum(row.end - row.start for row in whole
                    if row.attrs["cause"] == "harvest")
    assert (harvested > 0) == bool(run.named("cost_harvest"))
    assert sum(run.report[name] for name in ("trace", "lower", "build")) \
        >= run.report["compile"] + harvested * 1e3 - 0.05


def test_rows_stand_on_the_tracing_clock(run):
    run, _ = run
    now = tracing.to_wall_s(time.perf_counter())
    started, _ = profiler.process_started()
    for row in run.rows:
        assert started <= row.start <= row.end <= now
