"""Async input pipeline (ISSUE 8): prefetch machinery + out-of-core
streamed training parity with the device-resident path."""

import threading
import time

import numpy
import pytest

from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader import prefetch
from veles_tpu.models.mnist import MnistWorkflow
from veles_tpu.train import FusedTrainer
from veles_tpu.train.runner import FusedRunner

from test_mnist_e2e import synthetic_digits


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("veles-prefetch")]


# -- PrefetchPipeline unit behavior ------------------------------------------


def test_pipeline_ordered_and_bounded():
    in_flight = []
    peak = [0]
    lock = threading.Lock()

    def produce(i):
        with lock:
            in_flight.append(i)
            peak[0] = max(peak[0], len(in_flight))
        time.sleep(0.005)
        with lock:
            in_flight.remove(i)
        return i * 10

    pipe = prefetch.PrefetchPipeline(produce, 12, depth=2, workers=1,
                                     name="t").start()
    got = [pipe.get()[0] for _ in range(12)]
    pipe.close()
    assert got == [i * 10 for i in range(12)]
    # depth bounds produced-but-unconsumed items; with one worker at
    # most one produce runs at a time
    assert peak[0] <= 2
    assert not _prefetch_threads()


def test_pipeline_depth_bound_holds_with_slow_consumer():
    produced = []

    def produce(i):
        produced.append(i)
        return i

    pipe = prefetch.PrefetchPipeline(produce, 10, depth=3, workers=2,
                                     name="t").start()
    time.sleep(0.2)  # consumer idle: workers must stall at the bound
    assert len(produced) <= 3
    for i in range(10):
        assert pipe.get()[0] == i
    pipe.close()


def test_pipeline_worker_exception_propagates():
    """A broken loader fails the step loop loudly — no silent hang."""
    def produce(i):
        if i == 2:
            raise ValueError("etl broke on shard 2")
        return i

    pipe = prefetch.PrefetchPipeline(produce, 6, depth=2, workers=1,
                                     name="t").start()
    assert pipe.get()[0] == 0
    assert pipe.get()[0] == 1
    with pytest.raises(ValueError, match="shard 2"):
        pipe.get()
    # the error closed the pipeline and joined its threads
    assert not _prefetch_threads()


def test_pipeline_close_joins_all_threads():
    release = threading.Event()

    def produce(i):
        release.wait(5)
        return i

    pipe = prefetch.PrefetchPipeline(produce, 50, depth=4, workers=3,
                                     name="t").start()
    assert _prefetch_threads()
    release.set()
    pipe.close()
    assert not _prefetch_threads()


def test_pipeline_depth_zero_is_synchronous():
    """VELES_PREFETCH=0: produce runs inline on the consumer thread —
    the exact pre-pipeline path, threads never created."""
    calls = []
    consumer = threading.current_thread()

    def produce(i):
        calls.append((i, threading.current_thread() is consumer))
        return i

    pipe = prefetch.PrefetchPipeline(produce, 4, depth=0, name="t")
    pipe.start()
    assert not _prefetch_threads()
    assert [pipe.get()[0] for _ in range(4)] == [0, 1, 2, 3]
    assert calls == [(i, True) for i in range(4)]
    pipe.close()


def test_pipeline_env_depth(monkeypatch):
    monkeypatch.setenv("VELES_PREFETCH", "5")
    assert prefetch.default_depth() == 5
    monkeypatch.setenv("VELES_PREFETCH", "0")
    assert prefetch.default_depth() == 0
    monkeypatch.setenv("VELES_PREFETCH", "junk")
    assert prefetch.default_depth() == 2


def test_shutdown_all_closes_leaked_pipelines():
    pipe = prefetch.PrefetchPipeline(lambda i: i, 100, depth=1,
                                     workers=1, name="leak").start()
    pipe.get()
    assert _prefetch_threads()
    prefetch.shutdown_all()
    assert not _prefetch_threads()


# -- host ETL helpers --------------------------------------------------------


def test_gather_rows_padding_contract():
    data = numpy.arange(12, dtype=numpy.float32).reshape(6, 2)
    truth = numpy.arange(6, dtype=numpy.int32) * 100
    idx = numpy.array([[4, -1], [0, 5]], numpy.int32)
    rows, t = prefetch.gather_rows(data, truth, idx)
    numpy.testing.assert_array_equal(
        rows, [[8, 9], [0, 0], [0, 1], [10, 11]])
    # truth at max(idx, 0) — masking is the loss math's job (same as
    # the on-device gather)
    numpy.testing.assert_array_equal(t, [400, 0, 0, 500])
    local = prefetch.local_indices(idx)
    numpy.testing.assert_array_equal(local, [[0, -1], [2, 3]])


def test_residency_plan(monkeypatch):
    monkeypatch.delenv("VELES_STREAM", raising=False)
    monkeypatch.setenv("VELES_DEVICE_BUDGET_MB", "1")
    assert prefetch.plan_residency(2e6) == "streamed"
    assert prefetch.plan_residency(0.5e6) == "resident"
    monkeypatch.setenv("VELES_STREAM", "0")
    assert prefetch.plan_residency(2e6) == "resident"
    monkeypatch.setenv("VELES_STREAM", "1")
    assert prefetch.plan_residency(10.0) == "streamed"
    monkeypatch.delenv("VELES_STREAM", raising=False)
    monkeypatch.delenv("VELES_DEVICE_BUDGET_MB", raising=False)
    # CPU: no bytes_limit -> unknown budget -> resident (the
    # pre-pipeline behavior, which is what keeps tier-1 unchanged)
    assert prefetch.plan_residency(1e15) == "resident"


def test_shard_batches_budget(monkeypatch):
    monkeypatch.setenv("VELES_SHARD_MB", "10")
    assert prefetch.shard_batches(1e6, depth=2) == 10
    # budget shrinks the shard so depth+2 resident shards fit
    assert prefetch.shard_batches(1e6, depth=2, budget_bytes=8e6) == 2
    monkeypatch.delenv("VELES_SHARD_MB", raising=False)


# -- streamed training parity ------------------------------------------------


def build_wf(seed=42, n_train=720, n_valid=120, mb=60, max_epochs=3):
    prng.get().seed(seed)
    prng.get("loader").seed(seed + 1)
    wf = MnistWorkflow(DummyLauncher(),
                       provider=synthetic_digits(n_train=n_train,
                                                 n_valid=n_valid),
                       layers=(32,), minibatch_size=mb,
                       learning_rate=0.08, max_epochs=max_epochs)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def _curve(history):
    return [e["validation"]["normalized"] for e in history]


def test_streamed_matches_incore_bitexact(monkeypatch):
    """Out-of-core run on a 'too big' dataset == in-core run, over
    multiple epochs (epoch wrap + reshuffle happen mid-prefetch)."""
    incore = _curve(FusedTrainer(build_wf()).train())
    monkeypatch.setenv("VELES_SHARD_MB", "0.1")
    trainer = FusedTrainer(build_wf(), stream=True)
    assert trainer.streaming
    assert trainer._batches_per_shard < 12  # several shards per sweep
    streamed = _curve(trainer.train())
    numpy.testing.assert_array_equal(incore, streamed)
    assert not _prefetch_threads()


def test_streamed_budget_cap_triggers(monkeypatch):
    """The artificial device budget (VELES_DEVICE_BUDGET_MB) forces a
    dataset 'exceeding HBM' out-of-core — the ISSUE 8 acceptance
    scenario — and the result still matches the in-core run."""
    incore = _curve(FusedTrainer(build_wf(max_epochs=2)).train())
    monkeypatch.setenv("VELES_DEVICE_BUDGET_MB", "0.05")  # ~50 KB cap
    trainer = FusedTrainer(build_wf(max_epochs=2))  # stream=None: AUTO
    assert trainer.streaming
    streamed = _curve(trainer.train())
    numpy.testing.assert_array_equal(incore, streamed)


def test_streamed_short_tail_batch(monkeypatch):
    """n_train not divisible by mb: the padded tail minibatch streams
    through a short final shard with identical loss math."""
    incore = _curve(FusedTrainer(
        build_wf(n_train=610, n_valid=130, max_epochs=2)).train())
    monkeypatch.setenv("VELES_SHARD_MB", "0.1")
    streamed = _curve(FusedTrainer(
        build_wf(n_train=610, n_valid=130, max_epochs=2),
        stream=True).train())
    numpy.testing.assert_array_equal(incore, streamed)


def test_prefetch_zero_reproduces_synchronous_path(monkeypatch):
    """VELES_PREFETCH=0 must give the identical result with zero
    pipeline threads (the synchronous fallback contract)."""
    monkeypatch.setenv("VELES_SHARD_MB", "0.1")
    async_curve = _curve(FusedTrainer(build_wf(), stream=True).train())
    monkeypatch.setenv("VELES_PREFETCH", "0")
    sync_curve = _curve(FusedTrainer(build_wf(), stream=True).train())
    assert not _prefetch_threads()
    numpy.testing.assert_array_equal(async_curve, sync_curve)


def test_streamed_worker_exception_reaches_step_loop(monkeypatch):
    """An ETL crash inside a worker thread must unwind the training
    call — not hang the run."""
    monkeypatch.setenv("VELES_SHARD_MB", "0.1")
    trainer = FusedTrainer(build_wf(), stream=True)
    calls = [0]
    real = prefetch.gather_rows

    def broken(data, truth, indices):
        calls[0] += 1
        if calls[0] >= 3:
            raise RuntimeError("disk fell over")
        return real(data, truth, indices)

    monkeypatch.setattr(prefetch, "gather_rows", broken)
    params, states = trainer.pull_params()
    with pytest.raises(RuntimeError, match="disk fell over"):
        for _ in range(4):  # eval shards may precede the failure
            trainer.train_class(params, states)
    assert not _prefetch_threads()


def test_streamed_runner_end_to_end(monkeypatch):
    """FusedRunner drives a streamed workflow: decision bookkeeping,
    telemetry (input-wait histogram + starvation gauge) and clean
    pipeline shutdown all happen through the production path."""
    from veles_tpu.telemetry.registry import get_registry
    registry = get_registry()
    for name in ("veles_step_input_wait_ms",
                 "veles_input_starvation_fraction"):
        metric = registry.get(name)
        if metric is not None:
            metric.reset()
    incore = _curve(FusedTrainer(build_wf(max_epochs=2)).train())
    monkeypatch.setenv("VELES_SHARD_MB", "0.1")
    wf = build_wf(max_epochs=2)
    runner = FusedRunner(wf, trainer=FusedTrainer(wf, stream=True))
    runner.run()
    assert _curve(wf.decision.epoch_history) == incore
    wait = registry.get("veles_step_input_wait_ms").labels()
    assert wait.count > 0
    gauge = registry.get("veles_input_starvation_fraction")
    phases = {labels["phase"] for labels, _ in gauge.series()}
    assert {"train", "eval", "epoch"} <= phases
    assert not _prefetch_threads()


def test_streamed_confusion_matrix(monkeypatch):
    """Confusion accumulation rides the streamed eval scan too."""
    monkeypatch.setenv("VELES_SHARD_MB", "0.1")
    wf = build_wf(max_epochs=1)
    wf.evaluator.compute_confusion = True
    trainer = FusedTrainer(wf, stream=True)
    params, _ = trainer.pull_params()
    losses, metrics, conf = trainer.eval_class(params, 1)  # VALIDATION
    assert conf is not None
    assert int(numpy.sum(numpy.asarray(conf))) == 120  # n_valid


def test_loader_iter_shards():
    wf = build_wf(max_epochs=1)
    loader = wf.loader
    shards = list(loader.iter_shards(2, 100))  # TRAIN, 720 samples
    assert [len(s) for s in shards] == [100] * 7 + [20]
    seg = numpy.concatenate(shards)
    ends = loader.class_end_offsets
    expect = numpy.asarray(
        loader.shuffled_indices.map_read()[ends[2] - 720:ends[2]])
    numpy.testing.assert_array_equal(seg, expect)


def test_streamed_data_parallel_parity(monkeypatch):
    """Streamed shards land as addressable per-device shards of the
    data-axis NamedSharding; the math still matches in-core DP."""
    from veles_tpu.parallel import DataParallelTrainer, build_mesh

    def build_dp(seed=42):
        prng.get().seed(seed)
        prng.get("loader").seed(seed + 1)
        wf = MnistWorkflow(DummyLauncher(),
                           provider=synthetic_digits(n_train=640,
                                                     n_valid=128),
                           layers=(32,), minibatch_size=64,
                           learning_rate=0.08, max_epochs=2)
        wf.initialize(device=Device(backend="cpu"))
        return wf

    incore = _curve(DataParallelTrainer(
        build_dp(), mesh=build_mesh({"data": 8})).train())
    monkeypatch.setenv("VELES_SHARD_MB", "0.005")
    trainer = DataParallelTrainer(build_dp(),
                                  mesh=build_mesh({"data": 8}),
                                  stream=True)
    assert trainer.streaming
    assert trainer._batches_per_shard < 10  # several shards per sweep
    streamed = _curve(trainer.train())
    numpy.testing.assert_allclose(incore, streamed, atol=1e-6)
    assert not _prefetch_threads()


def test_throttled_overlap_reduces_wait(monkeypatch):
    """The measured overlap win: with a deliberately slow ETL, depth-4
    prefetch with 4 workers must cut the step thread's input wait well
    below the synchronous path (generous margin — CI runners jitter)."""
    from veles_tpu.telemetry.registry import get_registry
    monkeypatch.setenv("VELES_SHARD_MB", "0.005")  # 1 batch per shard
    monkeypatch.setenv("VELES_ETL_THROTTLE_MS", "30")

    def run(depth, workers):
        hist = get_registry().get("veles_step_input_wait_ms")
        if hist is not None:
            hist.reset()
        trainer = FusedTrainer(build_wf(max_epochs=1), stream=True,
                               prefetch_depth=depth,
                               prefetch_workers=workers)
        trainer.train()
        child = get_registry().get("veles_step_input_wait_ms").labels()
        return child.sum, child.count

    sync_ms, n_sync = run(0, 1)
    async_ms, n_async = run(4, 4)
    assert n_sync == n_async > 4
    assert async_ms < sync_ms * 0.6, (sync_ms, async_ms)


# -- the resident data set's interleaved placement (PR 36) ------------------


@pytest.mark.parametrize("on_device", [False, True])
@pytest.mark.parametrize("n_samples", [40, 43, 3])
def test_deal_rows_interleaves_and_pads(n_samples, on_device):
    """Row ``g`` of the source lands as row ``g // 4`` of array
    ``g % 4`` bit for bit, whatever the chunking and wherever the
    source lies; rows past the count are zero; ``interleaved_rows``
    names the same places in the placed whole."""
    import jax.numpy as jnp
    import ml_dtypes
    host = numpy.random.RandomState(n_samples).randn(
        n_samples, 2, 3).astype(ml_dtypes.bfloat16)
    source = jnp.asarray(host) if on_device else host
    rows = -(-n_samples // 4)
    for chunk_bytes in (1, 100, 128 << 20):
        shards = prefetch.deal_rows(source, 4, chunk_bytes)
        assert [s.shape for s in shards] == [(rows, 2, 3)] * 4
        assert all(s.dtype == host.dtype for s in shards)
        whole = numpy.concatenate(shards).view(numpy.uint16)
        at = prefetch.interleaved_rows(
            numpy.arange(-1, n_samples), n_samples, 4)
        assert at[0] == -1
        assert (whole[at[1:]] == host.view(numpy.uint16)).all()
        pads = numpy.setdiff1d(numpy.arange(4 * rows), at[1:])
        assert not whole[pads].any()
