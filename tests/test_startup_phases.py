"""Where a process's time goes before its first steady epoch: a tiny
trainer of each kind, driven through the public ``run_epoch`` on forced
host devices, leaves the start-up rows the benchmark's reader
``program_phases`` and an operator's ``veles_startup_s`` stand on."""

import time

import jax
import jax.monitoring
import numpy
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


KINDS = ["fused", "streamed", "offloaded", "dp", "gspmd"]


def make_trainer(kind, workflow, **kwargs):
    if kind == "fused":
        return FusedTrainer(workflow, **kwargs)
    if kind == "streamed":
        return FusedTrainer(workflow, stream=True, **kwargs)
    if kind == "offloaded":
        return FusedTrainer(workflow, offload=True, **kwargs)
    if kind == "dp":
        return DataParallelTrainer(
            workflow, mesh=build_mesh(devices=jax.devices()[:4]), **kwargs)
    return GSPMDTrainer(workflow, **kwargs)


def builds_by_stage_and_cause():
    """``veles_program_builds_total`` as ``{(stage, cause): count}``."""
    try:
        counter = get_registry().get("veles_program_builds_total")
    except KeyError:
        return {}
    return {(labels["stage"], labels["cause"]): child.value
            for labels, child in counter.series()}


def executables_gauge(op):
    return get_registry().get("veles_segment_executables").labels(
        op=op).value


def watch_segments(trainer):
    """``[(op, jitted function, operands as shapes with their
    shardings)]`` of every segment call that left one more executable
    held: the real operands are donated by the call."""
    seen = []
    call_segment = trainer._call_segment

    def watched(name, jit_fn, args, state):
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), args)
        held = len(trainer._executables)
        out = call_segment(name, jit_fn, args, state)
        if len(trainer._executables) > held:
            seen.append((trainer._op(name), jit_fn, shapes))
        return out

    trainer._call_segment = watched
    return seen


class Run(object):
    """Four epochs of a fresh trainer of ``kind`` and what they left."""

    def __init__(self, kind):
        workflow = _build_wf()
        profiler.reset_phases()
        profiler.reset_cost_book()  # a harvest is once an op a process
        counted = builds_by_stage_and_cause()
        self.trainer = make_trainer(kind, workflow)
        self.segments = watch_segments(self.trainer)
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
        self.counted = {
            key: value - counted.get(key, 0)
            for key, value in builds_by_stage_and_cause().items()}
        book = profiler.get_cost_book()
        self.costs = {op: book.cost(op) for op, _, _ in self.segments}
        self.held = {op: executables_gauge(op)
                     for op, _, _ in self.segments}
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


@pytest.fixture(scope="module", params=KINDS)
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


def test_a_segment_signature_has_one_first_call_with_its_stages(run):
    run, kind = run
    prefix = "gspmd_" if kind == "gspmd" else ""
    calls = run.named("segment_first_call")
    assert {call.attrs["op"] for call in calls} == {
        prefix + "eval_segment", prefix + "train_segment"}
    for call in calls:
        assert run.parent(call).name == "epoch"
        assert run.parent(call).attrs["epoch"] == 0
        assert call.attrs["builds"] >= 1
        built = [row for row in run.rows if row.parent == call.id
                 and row.name == "build"]
        # the offload engine walks layer groups on threads of its own
        assert built or kind == "offloaded"
        for row in built:
            assert row.attrs["cause"] == "call"
            assert call.start <= row.start <= row.end <= call.end + 1e-3
    if kind == "offloaded":  # not _call_segment's: as it was
        assert not any(call.attrs["committed"] for call in calls)
        assert not run.trainer._executables and not run.segments
        return
    # four epochs: one first call, one executable and ONE build of the
    # segment's program for every signature of operands that came
    assert len(calls) == len(run.trainer._executables) == len(run.segments)
    for call in calls:
        program = ("train_segment"
                   if call.attrs["op"].endswith("train_segment")
                   else "eval_segment_pure")
        inside = [row.name for row in run.rows if row.parent == call.id
                  and row.attrs.get("program") == program]
        assert sorted(inside) == ["build", "lower", "trace"]
    assert sum(row.name == "build" and row.attrs["program"] in (
        "train_segment", "eval_segment_pure") for row in run.rows) \
        == len(run.segments)
    # what the executable was built from: the first call's optimizer
    # state was made on the host side. No call builds again for a
    # state that comes back committed
    train = [call for call in calls
             if call.attrs["op"].endswith("train_segment")]
    assert [call.attrs["committed"] for call in train] == [
        kind in ("dp", "gspmd")] * len(train)
    assert len(train) == 1 or kind == "streamed"  # a shorter last shard


def test_a_cost_harvest_builds_nothing(run):
    run, kind = run
    harvests = run.named("cost_harvest")
    assert not [row for row in run.rows
                if row.attrs.get("cause") == "harvest"]
    assert not any(count for (_, cause), count in run.counted.items()
                   if cause == "harvest")
    if kind == "offloaded":  # the engine counts its transfers itself
        assert not harvests
        return
    assert len(harvests) == 2
    for harvest in harvests:
        assert run.parent(harvest).name == "epoch"
        assert not [row for row in run.rows if row.parent == harvest.id]
        # it follows the first call that built what it reads
        call = max((row for row in run.named("segment_first_call")
                    if row.end <= harvest.start + 1e-3),
                   key=lambda row: row.end)
        assert call.attrs["op"] == harvest.attrs["op"]


def test_the_costs_are_the_called_executables_own(run):
    run, kind = run
    if kind == "offloaded":
        assert not run.costs and not run.held
        return
    ops = [op for op, _, _ in run.segments]
    for op in set(ops):
        assert run.held[op] == ops.count(op)
        # once an op: of the first executable built for it
        jit_fn, args = next((fn, args) for name, fn, args in run.segments
                            if name == op)
        costs = jit_fn.lower(*args).compile().cost_analysis()
        costs = costs[0] if isinstance(costs, (list, tuple)) else costs
        assert run.costs[op]["flops"] == costs["flops"] > 0
        assert run.costs[op]["bytes"] == costs["bytes accessed"] > 0
        if kind in ("dp", "gspmd"):
            assert run.costs[op]["collective_count"] > 0
        assert get_registry().get("veles_op_flops").labels(
            op=op).value == costs["flops"]
        assert get_registry().get("veles_op_bytes").labels(
            op=op).value == costs["bytes accessed"]


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
    # no build has another cause: a cost harvest reads what was built
    assert {row.attrs["cause"] for row in whole} == {"call"}
    assert sum(run.report[name] for name in ("trace", "lower", "build")) \
        >= run.report["compile"] - 0.05


def test_rows_stand_on_the_tracing_clock(run):
    run, _ = run
    now = tracing.to_wall_s(time.perf_counter())
    started, _ = profiler.process_started()
    for row in run.rows:
        assert started <= row.start <= row.end <= now


def fresh_trainer(kind, **kwargs):
    profiler.reset_cost_book()
    trainer = make_trainer(kind, _build_wf(), **kwargs)
    return trainer, trainer.pull_params()


def held_by_op(trainer):
    return sorted(op for op, _ in trainer._executables)


@pytest.mark.parametrize("kind", KINDS)
def test_a_one_step_sweep_and_a_whole_one_are_two_executables(kind):
    from veles_tpu.loader.base import TRAIN
    trainer, (params, states) = fresh_trainer(kind)
    train = trainer._op("train_segment")
    loader = trainer.loader
    try:
        builds = profiler.build_count()
        params, states, losses, _ = trainer.train_class(
            params, states,
            skip=loader.class_lengths[TRAIN] - loader.max_minibatch_size)
        assert losses.shape == (1,)
        assert profiler.build_count() > builds
        if kind == "offloaded":
            assert not trainer._executables
            return
        assert held_by_op(trainer) == [train]
        assert executables_gauge(train) == 1
        builds = profiler.build_count()
        params, states, losses, _ = trainer.train_class(params, states)
        assert losses.shape == (5,)
        # the whole sweep's scan is another program: one more
        assert held_by_op(trainer) == [train, train]
        assert executables_gauge(train) == 2
        assert profiler.build_count() > builds
        # and either runs again from what is held
        builds = profiler.build_count()
        params, states, _, _ = trainer.train_class(params, states)
        trainer.train_class(
            params, states,
            skip=loader.class_lengths[TRAIN] - loader.max_minibatch_size)
        assert profiler.build_count() == builds
        assert held_by_op(trainer) == [train, train]
    finally:
        trainer.shutdown()
        profiler.reset_phases()
        profiler.reset_cost_book()


@pytest.mark.parametrize("kind", KINDS)
def test_a_held_executable_trains_as_the_jitted_function_does(kind):
    """Two epochs through the held executables against two through
    plain calls of the same jitted functions: the same bits, and the
    state a call was given is donated to it."""
    states = []
    for direct in (False, True):
        trainer, state = fresh_trainer(kind, donate=True)
        if direct:
            trainer._call_segment = \
                lambda name, jit_fn, args, state: jit_fn(*args)
        try:
            for epoch in range(2):
                given = jax.tree_util.tree_leaves(state)
                params, opt_states, _ = trainer.run_epoch(*state, epoch)
                state = (params, opt_states)
                if kind != "offloaded":  # host masters there
                    assert given and all(
                        leaf.is_deleted() for leaf in given)
            states.append(jax.device_get(state))
            assert direct or kind == "offloaded" or trainer._executables
        finally:
            trainer.shutdown()
            profiler.reset_phases()
            profiler.reset_cost_book()
    held, direct = (jax.tree_util.tree_leaves(state) for state in states)
    assert len(held) == len(direct) > 0
    for ours, theirs in zip(held, direct):
        numpy.testing.assert_array_equal(ours, theirs)
