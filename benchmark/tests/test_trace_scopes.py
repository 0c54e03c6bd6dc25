"""Reader ``trace_scopes``: its expression on ``op_name``s pasted from
the chip's trace and on every name the program emits (a CPU
rehearsal), and the whole reader on a trace of ``tests/configs/
tiny.json`` recorded on a TPU v5e with the scopes in (PR 24), cut by
``cut_xplane.py`` beside this file."""

import os
import re

import pytest

from benchmark import harness, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
FIXTURE = os.path.join(HOME, "fixtures", "tiny.v5e-1.scopes.xplane.pb")
FIXTURE_PR22 = os.path.join(HOME, "fixtures", "tiny-dp4.v5e-2x2.xplane.pb")
TRACED = {"epochs": 1, "train_steps": 8, "eval_steps": 2}
NEW_METRICS = ("forward_device_ms", "backward_device_ms",
               "update_device_ms", "conv_worst_roofline",
               "scope_coverage_pct")
#: unit names and the units with parameters of tests/configs/tiny.json
TINY_UNITS = ["conv_str0", "norm1", "max_pooling2", "conv_str3",
              "avg_pooling4", "all2all_str5", "dropout6", "softmax7"]
TINY_WITH_PARAMS = {0, 3, 5, 7}


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(HOME, "readers", "trace_scopes")


def context_of(fixture, config, chips, traced=TRACED):
    lines = []
    return {
        "trace": trace_reduce.reduce_file(fixture), "traced": traced,
        "counters": {}, "chips": chips, "log": lines.append,
        "config": harness.load_json(HERE, "configs", config + ".json"),
        "peaks": harness.load_json(HOME, "peaks.json")["devices"][
            "TPU v5 lite"]}, lines


def read_metric(reader, context, metric):
    spec = harness.load_json(HOME, "layer_metrics", metric + ".json")
    assert spec["reader"] == "trace_scopes"
    return reader.read(context, **spec["args"])


# -- the expression ----------------------------------------------------------

# ``tf_op`` of XLA Ops events, pasted from the v5e traces of PR 24's first
# chip call (AlexNet-227 and tiny.json); the last four from the CPU's
# compiled text and by hand
BODY = "jit(train_segment)/while/body/closed_call/"
EVAL_BODY = "jit(eval_segment_pure)/while/body/closed_call/"
PASTED = [
    (BODY + "jvp(veles.u00.conv_str0)/conv_general_dilated:",
     ((0, "conv_str0"), "forward")),
    (BODY + "transpose(jvp(veles.u00.conv_str0))/conv_general_dilated:",
     ((0, "conv_str0"), "backward")),
    (BODY + "veles.update.u00.conv_str0/add:", ((0, "conv_str0"), "update")),
    (BODY + "veles.update.u14.softmax14/add:", ((14, "softmax14"), "update")),
    (EVAL_BODY + "veles.u00.conv_str0/jit(_pad)/pad:",
     ((0, "conv_str0"), "forward")),
    (BODY + "jvp(veles.u11.dropout11)/jit(uniform)/jit(_uniform)/"
     "iota_2x32_shape:", ((11, "dropout11"), "forward")),
    (BODY + "veles.in/jit(_take)/gather:", ("veles.in", None)),
    (BODY + "transpose(jvp(veles.loss))/jit(log_softmax)/reduce_sum:",
     ("veles.loss", None)),
    (EVAL_BODY + "veles.loss/jit(take_along_axis)/gather:",
     ("veles.loss", None)),
    # one fusion, two names joined by XLA
    (BODY + "transpose(jvp(veles.loss))/mul;transpose(jvp(veles.loss))/"
     "broadcast_in_dim:", ("veles.loss", None)),
    ("jit(train_segment)/while/body/dynamic_slice:", ("<unscoped>", None)),
    ("jit(train_segment)/while:", ("<unscoped>", None)),
    ("params_list[14]['weights']:", ("<unscoped>", None)),
    # XLA:TPU fuses the norm's reduction away; the CPU keeps it
    (BODY + "veles.gradnorm/reduce_sum", ("veles.gradnorm", None)),
    # the primitive transpose is no wrapper
    (BODY + "jvp(veles.u00.conv_str0)/transpose",
     ((0, "conv_str0"), "forward")),
    ("", ("<unscoped>", None)),
    (None, ("<unscoped>", None)),
]


@pytest.mark.parametrize("op_name,expected", PASTED)
def test_expression_on_op_names(reader, op_name, expected):
    assert reader.parse(op_name) == expected


def test_expression_knows_every_name_the_program_emits(reader, monkeypatch):
    """A CPU rehearsal: the trainer of ``tiny.json`` as the harness
    builds it, the names of its lowered train and eval segments (from
    the text with debug info, not from an executable a cache may have
    kept), every one through ``parse``."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.loader.base import TRAIN, VALIDATION
    from veles_tpu.train import FusedTrainer
    jitted = {}
    for kind in ("train", "eval"):
        hook = getattr(FusedTrainer, "_compile_" + kind)

        def capture(self, fn, hook=hook, kind=kind):
            jitted[kind] = hook(self, fn)
            return jitted[kind]
        monkeypatch.setattr(FusedTrainer, "_compile_" + kind, capture)
    trainer = harness.load_module(HOME, "builders", "convnet").build(
        harness.load_json(HERE, "configs", "tiny.json"),
        harness.load_json(HERE, "traffic", "tiny-resident.json"), 5,
        jax.devices()[:1], harness.load_module(HOME, "reference", "convnet"),
        lambda line: None).trainer
    assert [f.name for f in trainer.forwards] == TINY_UNITS
    params, states = trainer.pull_params()
    idx = jnp.asarray(trainer._segment_indices(TRAIN))
    args = {"train": (trainer._data_args, params, states, idx,
                      jax.random.split(jax.random.PRNGKey(0), idx.shape[0])),
            "eval": (trainer._data_args, params,
                     jnp.asarray(trainer._segment_indices(VALIDATION)))}
    seen = {}
    for kind in args:
        text = jitted[kind].lower(*args[kind]).as_text(debug_info=True)
        names = set(re.findall(r'"([^"]*veles\.[^"]*)"', text))
        assert names
        seen[kind] = {reader.parse(name) for name in names}
        # whatever mentions a scope is recognised as one
        assert ("<unscoped>", None) not in seen[kind]
    everything = set(range(len(TINY_UNITS)))
    assert {row for row, which in seen["train"] if which == "forward"} == {
        (i, TINY_UNITS[i]) for i in everything}
    assert {row[0] for row, which in seen["train"]
            if which == "update"} == TINY_WITH_PARAMS
    assert TINY_WITH_PARAMS <= {row[0] for row, which in seen["train"]
                                if which == "backward"}
    assert {("veles.in", None), ("veles.loss", None),
            ("veles.gradnorm", None)} <= seen["train"]
    assert seen["eval"] == {((i, TINY_UNITS[i]), "forward")
                            for i in everything - {6}} | {
        ("veles.in", None), ("veles.loss", None)}


# -- the whole reader on the recorded trace ----------------------------------


@pytest.fixture()
def recorded(reader, monkeypatch):
    monkeypatch.setattr(reader, "trace_path", lambda: FIXTURE)
    return context_of(FIXTURE, "tiny", 1)


def test_rows_add_up_to_the_program(reader, recorded):
    context, lines = recorded
    made = reader.tables(context)
    assert set(made) == {"train_segment", "eval_segment"}
    for program, steps, _ in reader.PROGRAMS:
        table, _ = made[program]
        expected = context["trace"].self_seconds(program) / TRACED[steps]
        rows = sum(sum(unit.values()) for unit in table.units.values()) \
            + sum(table.plain.values())
        # every operation is in exactly one row
        assert rows == pytest.approx(expected, rel=1e-6)
        assert table.total == pytest.approx(expected, rel=1e-6)
        assert sum(table.unscoped_categories.values()) == pytest.approx(
            table.plain[reader.UNSCOPED], rel=1e-6)
    train, floors = made["train_segment"]
    assert {index for index, _ in train.units} <= set(range(len(TINY_UNITS)))
    assert all(unit == TINY_UNITS[index] for index, unit in train.units)
    assert {row[0] for row in floors} == TINY_WITH_PARAMS
    assert {floors[row][0] for row in floors} == {"conv", "dense"}
    evaluated, _ = made["eval_segment"]
    assert not evaluated.of_pass("backward")
    assert not evaluated.of_pass("update")
    assert evaluated.of_pass("forward") > 0
    # one table a program, logged once however often it is asked for
    reader.tables(context)
    assert sum(line.startswith("units of ") for line in lines) == 2
    assert any(line.lstrip().startswith("total") for line in lines)


def test_the_five_metrics_read_the_recorded_trace(reader, recorded):
    context, lines = recorded
    values = {name: read_metric(reader, context, name)
              for name in NEW_METRICS}
    assert all(isinstance(v, float) and v > 0 for v in values.values())
    train, _ = reader.tables(context)["train_segment"]
    others = sum(train.plain.values())
    step_ms = harness.load_module(HOME, "readers", "trace_buckets").read(
        context, "train_segment", "*", "train_steps")
    assert values["forward_device_ms"] + values["backward_device_ms"] \
        + values["update_device_ms"] + others * 1e3 == pytest.approx(
            step_ms, rel=1e-6)
    assert 0 < values["scope_coverage_pct"] <= 100
    assert 0 < values["conv_worst_roofline"] < 100
    assert any(line.startswith("conv_worst_roofline: u0") for line in lines)
    with pytest.raises(ValueError):
        reader.read(context, "train_segment", "no-such-value")


def test_a_trace_without_scopes_gives_no_value(reader, monkeypatch):
    monkeypatch.setattr(reader, "trace_path", lambda: FIXTURE_PR22)
    context, lines = context_of(FIXTURE_PR22, "tiny-dp4", 4)
    for name in NEW_METRICS:
        assert read_metric(reader, context, name) is None
    assert not lines
    # and so does a run without a device trace at all (a CPU)
    context["trace"] = None
    del context[reader.KEY]
    assert read_metric(reader, context, "forward_device_ms") is None


def test_four_chips_are_averaged_and_collectives_kept_apart(reader):
    """PR 22's four-chip trace has no scope: its conv operations and
    its collectives are named by hand here."""
    trace = trace_reduce.reduce_file(FIXTURE_PR22)
    conv, update = ((0, "conv_str0"), "backward"), ((0, "conv_str0"), "update")
    rows = {device.name: {
        op.name: conv if op.bucket == "nn/conv.py" else update
        for op in device.ops
        if op.bucket in ("nn/conv.py", trace_reduce.COLLECTIVE_BUCKET)}
        for device in trace.devices}
    table = reader.Table(trace, rows, "train_segment", 8)

    def by_file(bucket):
        return trace.self_seconds("train_segment", bucket) / 8
    assert table.units[0, "conv_str0"]["backward"] == pytest.approx(
        by_file("nn/conv.py"), rel=1e-9)
    # a collective stays out of the scope that named it
    assert table.units[0, "conv_str0"]["update"] == 0
    assert table.plain[trace_reduce.COLLECTIVE_BUCKET] == \
        table.named_collective[0, "conv_str0"] == pytest.approx(
            by_file(trace_reduce.COLLECTIVE_BUCKET), rel=1e-9)
    assert table.total == pytest.approx(by_file("*"), rel=1e-9)
    assert table.scoped == pytest.approx(by_file("nn/conv.py"), rel=1e-9)


def test_fixture_is_small_and_cut_to_what_is_read():
    assert os.path.getsize(FIXTURE) < 1 << 20
    stats = trace_reduce.metadata_stats(
        FIXTURE, wanted=("source", "hlo_category", "tf_op"))
    device = stats["/device:TPU:0"]
    assert any("veles.u00.conv_str0" in s.get("tf_op", "")
               for s in device.values())
    assert any(s.get("source") for s in device.values())
    assert set(stats) == {"/device:TPU:0", "/host:CPU"}
