"""The names the fused step's operations carry on the device
(``train/step.py`` ``device_scope``): every unit, pass and update is
named once, by its absolute index, in every trainer that applies
units through ``_forward_range``; and the names are metadata only —
the program without them is the same bytes.

Scopes are read from the lowered text with debug info, never from an
executable (the persistent cache's key leaves locations out, so a
cached executable may carry another build's names)."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.models.alexnet import AlexNetWorkflow, SyntheticImageLoader
from veles_tpu.parallel.gspmd import GSPMDTrainer, parse_mesh_spec
from veles_tpu.train import FusedTrainer, step

LAYERS = [
    {"type": "conv_str", "n_kernels": 8, "kx": 5, "ky": 5,
     "sliding": (4, 4), "padding": 2, "space_to_depth": True},
    {"type": "norm", "n": 5, "alpha": 1e-4, "beta": 0.75},
    {"type": "max_pooling", "kx": 2, "ky": 2},
    {"type": "all2all_str", "output_sample_shape": 32},
    {"type": "dropout", "dropout_ratio": 0.5},
    {"type": "softmax", "output_sample_shape": 10},
]
NAMES = ["conv_str0", "norm1", "max_pooling2", "all2all_str3", "dropout4",
         "softmax5"]
WITH_PARAMS = {0, 3, 5}
DROPOUT = 4
KINDS = ("fused", "gspmd")
SEGMENTS = ("train", "eval")

#: a scope as the lowered text quotes it, with the wrappers JAX adds
SCOPE = re.compile(
    r'["/]((?:transpose\()?(?:jvp\()?veles\.[\w.\-]+\)*)[/"]')
UNIT = re.compile(r"^(transpose\()?(?:jvp\()?veles\.(update\.)?u(\d+)\."
                  r"([\w.\-]+?)\)*$")


class Lowered(object):
    """A trainer and its two segments, lowered at the shapes a sweep
    passes."""

    def __init__(self, kind, layers=LAYERS):
        jitted = {}
        base = {"fused": FusedTrainer, "gspmd": GSPMDTrainer}[kind]

        class Capturing(base):
            def _compile_train(self, fn):
                jitted["train"] = super()._compile_train(fn)
                return jitted["train"]

            def _compile_eval(self, fn):
                jitted["eval"] = super()._compile_eval(fn)
                return jitted["eval"]

        prng.get().seed(7)
        prng.get("loader").seed(8)
        wf = AlexNetWorkflow(
            DummyLauncher(),
            loader_factory=lambda w: SyntheticImageLoader(
                w, n_train=48, n_valid=16, side=21, n_classes=10,
                minibatch_size=16),
            layers=[dict(layer) for layer in layers], max_epochs=1)
        wf.initialize(device=Device(backend="cpu"))
        kwargs = {"mesh": parse_mesh_spec(
            "4x1", devices=jax.devices()[:4])} if kind == "gspmd" else {}
        self.trainer = trainer = Capturing(wf, **kwargs)
        self.jitted = jitted
        params, states = trainer.pull_params()
        train_idx = jnp.asarray(trainer._segment_indices(TRAIN))
        keys = jax.random.split(jax.random.PRNGKey(0), train_idx.shape[0])
        self.args = {
            "train": (trainer._data_args, params, states, train_idx, keys),
            "eval": (trainer._data_args, params,
                     jnp.asarray(trainer._segment_indices(VALIDATION)))}

    def text(self, segment, debug_info):
        return self.jitted[segment].lower(*self.args[segment]).as_text(
            debug_info=debug_info)

    def scopes(self, segment):
        return set(SCOPE.findall(self.text(segment, True)))


def units(scopes):
    """``{(index, name, pass)}`` of the unit scopes among ``scopes``."""
    found = set()
    for scope in scopes:
        m = UNIT.match(scope)
        if m:
            backward, update, index, name = m.groups()
            found.add((int(index), name,
                       "update" if update else
                       "backward" if backward else "forward"))
    return found


@pytest.fixture(scope="module")
def lowered():
    cache = {}

    def get(kind):
        if kind not in cache:
            cache[kind] = Lowered(kind)
        return cache[kind]
    return get


@pytest.mark.parametrize("segment", SEGMENTS)
@pytest.mark.parametrize("kind", KINDS)
def test_every_unit_that_works_is_named_once(lowered, kind, segment):
    low = lowered(kind)
    assert [f.name for f in low.trainer.forwards] == NAMES
    forward = {(i, name) for i, name, which
               in units(low.scopes(segment)) if which == "forward"}
    # dropout does nothing in a forward-only sweep: no operation, no name
    working = [i for i in range(len(NAMES))
               if segment == "train" or i != DROPOUT]
    assert forward == {(i, NAMES[i]) for i in working}


@pytest.mark.parametrize("kind", KINDS)
def test_train_segment_names_backward_and_update(lowered, kind):
    scopes = lowered(kind).scopes("train")
    found = units(scopes)
    assert {i for i, _, which in found if which == "update"} == WITH_PARAMS
    backward = {i for i, _, which in found if which == "backward"}
    assert WITH_PARAMS <= backward <= set(range(len(NAMES)))
    # forward under value_and_grad is jvp(...), the update is bare
    assert "jvp(veles.u00.conv_str0)" in scopes
    assert "transpose(jvp(veles.u05.softmax5))" in scopes
    assert "veles.update.u03.all2all_str3" in scopes
    assert not units(lowered(kind).scopes("eval")) - {
        (i, NAMES[i], "forward") for i in range(len(NAMES))}


@pytest.mark.parametrize("segment", SEGMENTS)
@pytest.mark.parametrize("kind", KINDS)
def test_gather_loss_and_gradnorm_are_named(lowered, kind, segment):
    scopes = lowered(kind).scopes(segment)
    assert "veles.in" in scopes
    if segment == "train":
        assert lowered(kind).trainer.track_grad_norms
        assert {"jvp(veles.loss)", "transpose(jvp(veles.loss))",
                "veles.gradnorm"} <= scopes
    else:
        assert "veles.loss" in scopes
        assert "veles.gradnorm" not in scopes


@pytest.mark.parametrize("segment", SEGMENTS)
@pytest.mark.parametrize("kind", KINDS)
def test_program_is_the_same_bytes_without_the_scopes(
        lowered, monkeypatch, kind, segment):
    scoped = lowered(kind)
    monkeypatch.setattr(step, "device_scope",
                        lambda *parts: contextlib.nullcontext())
    bare = Lowered(kind)
    assert not bare.scopes(segment)
    assert scoped.scopes(segment)
    assert bare.text(segment, False) == scoped.text(segment, False)


@pytest.mark.parametrize("kind", KINDS)
def test_a_range_of_units_keeps_absolute_indices(monkeypatch, kind):
    """The offload engine walks groups through ``_forward_range(lo,
    hi)`` and updates them in ``_apply_group_updates``; a range holds
    its own units' parameters only, in a partitioned trainer too."""
    monkeypatch.setenv("VELES_OFFLOAD", "1")
    monkeypatch.setenv("VELES_OFFLOAD_GROUP_MB", "0.001")
    trainer = Lowered(kind).trainer
    try:
        engine = trainer._offload_engine
        assert trainer.offloaded and engine.plan.n_groups >= 3
        lo, hi = next(
            (lo, hi) for lo, hi in engine.plan.groups
            if lo > 0 and WITH_PARAMS & set(range(lo, hi))
            and hi < len(NAMES))
        params, states = trainer.pull_params()
        x = jax.eval_shape(
            lambda p, x: trainer._forward_range(p, x, None, False, 0, lo),
            params[:lo], jax.ShapeDtypeStruct(
                (16,) + trainer._data_args[0].shape[1:], jnp.float32))
        cot = jax.eval_shape(
            lambda p, x: trainer._forward_range(p, x, None, False, lo, hi),
            params[lo:hi], x)
        text = jax.jit(engine._build_bwd(lo, hi)).lower(
            params[lo:hi], states[lo:hi], x, cot,
            jnp.zeros((16,), jnp.int32), jax.random.PRNGKey(0)).as_text(
                debug_info=True)
    finally:
        trainer.shutdown()
    found = units(set(SCOPE.findall(text)))
    assert {i for i, _, _ in found} == set(range(lo, hi))
    assert {(i, name) for i, name, _ in found} == {
        (i, NAMES[i]) for i in range(lo, hi)}
    assert {i for i, _, which in found if which == "update"} == \
        WITH_PARAMS & set(range(lo, hi))


def test_a_second_trainer_leaves_the_firsts_names_alone(lowered):
    first = lowered("fused")
    before = {seg: first.scopes(seg) for seg in SEGMENTS}
    # no LRN: every later unit moves down by one and is renamed
    second = Lowered("fused", [LAYERS[0]] + LAYERS[2:])
    assert (1, "max_pooling1", "forward") in units(second.scopes("train"))
    assert (1, "norm1", "forward") not in units(second.scopes("train"))
    for seg in SEGMENTS:
        assert first.scopes(seg) == before[seg]
    assert (1, "norm1", "forward") in units(first.scopes("train"))
    assert not any(name == "max_pooling1"
                   for _, name, _ in units(first.scopes("train")))
