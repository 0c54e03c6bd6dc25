"""Fused step compiler: parity with the eager unit-graph path."""

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.mnist import MnistWorkflow
from veles_tpu.train import FusedTrainer

from test_mnist_e2e import synthetic_digits


def build(max_epochs=3, seed=42):
    prng.get().seed(seed)
    prng.get("loader").seed(seed + 1)
    wf = MnistWorkflow(DummyLauncher(), provider=synthetic_digits(),
                       layers=(32,), minibatch_size=60,
                       learning_rate=0.08, max_epochs=max_epochs)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def test_fused_trains_and_improves():
    wf = build()
    trainer = FusedTrainer(wf)
    history = trainer.train()
    assert len(history) == 3
    assert history[-1]["validation"]["normalized"] < \
        history[0]["validation"]["normalized"]
    assert history[-1]["validation"]["normalized"] < 0.25
    assert bool(wf.stopped)


def test_fused_matches_eager_loss_curve():
    """Fused execution must track the eager unit-graph numerics.

    Both paths: same init, same shuffle stream, same update rule. Eager
    evaluates validation with the params as of the start of the epoch
    (same as fused, which evals before training the segment)."""
    wf_eager = build()
    wf_eager.run()
    eager = [e["validation"]["normalized"]
             for e in wf_eager.decision.epoch_history]

    wf_fused = build()
    trainer = FusedTrainer(wf_fused)
    history = trainer.train()
    fused = [e["validation"]["normalized"] for e in history]
    numpy.testing.assert_allclose(fused, eager, atol=0.03)


def test_fused_pushes_params_back():
    wf = build(max_epochs=2)
    before = numpy.array(wf.forwards[0].weights.map_read()).copy()
    FusedTrainer(wf).train()
    after = numpy.asarray(wf.forwards[0].weights.map_read())
    assert not numpy.allclose(before, after)
    # pushed params serve eager inference directly
    wf.forwards[0].jax_run()


def test_fused_matches_eager_with_short_tail_batch():
    """Train size not divisible by minibatch: padded-batch gradient
    normalization must match the eager evaluator exactly."""
    def build2():
        prng.get().seed(5)
        prng.get("loader").seed(6)
        wf = MnistWorkflow(DummyLauncher(),
                           provider=synthetic_digits(n_train=610,
                                                     n_valid=130),
                           layers=(16,), minibatch_size=60,
                           learning_rate=0.08, max_epochs=2)
        wf.initialize(device=Device(backend="cpu"))
        return wf

    wf_eager = build2()
    wf_eager.run()
    eager = [e["validation"]["normalized"]
             for e in wf_eager.decision.epoch_history]
    wf_fused = build2()
    fused = [e["validation"]["normalized"]
             for e in FusedTrainer(wf_fused).train()]
    numpy.testing.assert_allclose(fused, eager, atol=0.03)


def test_fused_respects_fail_iterations():
    wf = build(max_epochs=None)
    wf.decision.fail_iterations = 1
    trainer = FusedTrainer(wf)
    history = trainer.train(max_epochs=50)
    assert len(history) < 50  # stopped early by no-improvement rule


S2D_LAYERS = [
    {"type": "conv_str", "n_kernels": 8, "kx": 5, "ky": 5,
     "sliding": (4, 4), "padding": 2, "space_to_depth": True},
    {"type": "max_pooling", "kx": 2, "ky": 2},
    {"type": "all2all_str", "output_sample_shape": 32},
    {"type": "softmax", "output_sample_shape": 10},
]

#: side -> does the stored sample end in zero padding? A 21x21x3
#: sample packs to 7*7*48 = 2,352 floats, three 8x128 tiles less 720;
#: a 25x25x3 one to 8*8*48 = 3,072, three tiles exactly
S2D_SIDES = {"pad": (21, True), "no-pad": (25, False)}


def build_s2d(side=21, trainer=FusedTrainer, **kw):
    from veles_tpu.models.alexnet import (AlexNetWorkflow,
                                          SyntheticImageLoader)
    prng.get().seed(7)
    prng.get("loader").seed(8)
    wf = AlexNetWorkflow(
        DummyLauncher(),
        loader_factory=lambda w: SyntheticImageLoader(
            w, n_train=48, n_valid=16, side=side, n_classes=10,
            minibatch_size=16),
        layers=S2D_LAYERS, max_epochs=2)
    wf.initialize(device=Device(backend="cpu"))
    return trainer(wf, **kw)


def assert_histories_equal(first, second):
    """The staging test's tolerance, never looser."""
    assert len(first) == len(second) > 0
    for a, b in zip(first, second):
        for klass in ("validation", "train"):
            numpy.testing.assert_allclose(
                a[klass]["normalized"], b[klass]["normalized"],
                rtol=0, atol=1e-6)


@pytest.mark.parametrize("case", sorted(S2D_SIDES))
def test_s2d_dataset_staging_exact(case):
    """VERDICT r3 #1: packing the dataset to patch-channel layout at
    staging (one-time) must reproduce the per-step space-to-depth
    numbers exactly — packing is row-wise linear, so it commutes with
    the minibatch gather and the invalid-row mask. The stored sample
    is whole device tiles (``staged_row_shape``), zero-padded or not."""
    from veles_tpu.train.step import staged_row_shape
    side, padded = S2D_SIDES[case]
    staged = build_s2d(side)
    assert staged._staged_s2d
    # packed dataset replaced the raw one in the compiled graph's
    # args; _staged_sample_shape stays the CONV's packed sample
    packed_sample = staged.forwards[0].s2d_packed_shape((side, side, 3))
    assert staged._staged_sample_shape == packed_sample
    flat = int(numpy.prod(packed_sample))
    data = staged._data_args[0]
    rows, cols = staged_row_shape(flat, data.dtype)
    assert data.shape == (48 + 16, rows, cols)
    assert (rows * cols > flat) == padded
    h_staged = staged.train()  # train right after build: both runs
    # must consume identically-seeded loader shuffle streams
    per_step = build_s2d(side, stage_s2d=False)
    assert not per_step._staged_s2d
    assert_histories_equal(h_staged, per_step.train())


@pytest.mark.parametrize("case", sorted(S2D_SIDES))
def test_s2d_staged_rows_unstage_bit_for_bit(case):
    """What ``_apply_unit`` hands the entry conv, the stored rows
    sliced and reshaped, is ``s2d_pack_input(raw)`` to the bit; the
    rest of each stored sample is exactly zero."""
    side, _ = S2D_SIDES[case]
    staged = build_s2d(side)
    raw = staged.loader.original_data.map_read()
    data = staged._data_args[0]
    flat = int(numpy.prod(staged._staged_sample_shape))
    tail = numpy.asarray(data).reshape(len(raw), -1)[:, flat:]
    assert not tail.any()
    want = numpy.asarray(staged.forwards[0].s2d_pack_input(
        jnp.asarray(raw)))
    got = numpy.asarray(staged._unstage(data))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("n_elements,dtype,want", [
    (58 * 58 * 48, "bfloat16", (16, 10112)),   # 78.84 tiles -> 79
    (58 * 58 * 48, "float32", (8, 20224)),
    (7 * 7 * 48, "float32", (8, 384)),
    (8 * 8 * 48, "float32", (8, 384)),         # whole tiles: no pad
    (1, "uint8", (32, 128)),
])
def test_staged_row_shape(n_elements, dtype, want):
    """Whole tiles of 128 lanes by one 32-bit sublane group of rows,
    side by side, the fewest that hold the sample; under 1% of zeros
    on a sample of a hundred tiles or more."""
    from veles_tpu.train.step import staged_row_shape
    rows, cols = staged_row_shape(n_elements, jnp.dtype(dtype))
    assert (rows, cols) == want
    assert cols % 128 == 0
    assert 0 <= rows * cols - n_elements < rows * 128


def test_s2d_streamed_trainer_still_trains():
    """A streamed trainer of the same workflow does not stage: its
    shards reach the same jitted segments raw, in the default layout,
    and the entry conv packs them per step — same histories."""
    streamed = build_s2d(stream=True)
    assert streamed.streaming and not streamed._staged_s2d
    h_streamed = streamed.train()
    streamed.shutdown()
    assert_histories_equal(h_streamed, build_s2d().train())


def test_donation_defaults_off_on_cpu(monkeypatch):
    """The eager-vs-fused flake's root cause: donating scan-carried
    params on this jaxlib's CPU client intermittently corrupts the
    glibc heap (free(): invalid next size / segfaults / garbled
    weights, allocator-layout dependent). Donation must stay an
    accelerator-only optimization unless explicitly forced."""
    monkeypatch.delenv("VELES_DONATE", raising=False)
    assert FusedTrainer._resolve_donate(None) is False  # CPU backend
    # explicit argument always wins
    assert FusedTrainer._resolve_donate(True) is True
    assert FusedTrainer._resolve_donate(False) is False
    # env overrides the platform default both ways
    monkeypatch.setenv("VELES_DONATE", "1")
    assert FusedTrainer._resolve_donate(None) is True
    monkeypatch.setenv("VELES_DONATE", "0")
    assert FusedTrainer._resolve_donate(None) is False
