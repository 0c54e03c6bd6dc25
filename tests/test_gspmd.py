"""GSPMD pod-scale training path (ISSUE 15).

The acceptance bars, pinned:

* the GSPMD path's loss curve is BIT-IDENTICAL (CPU, fixed seeds) to
  the coordinator path over >= 3 epochs — the compiler-inserted psum
  gradient merge reproduces the host-mediated exchange's math exactly,
  and the shard-invariant loss reductions make the reported curve
  structural, not lucky;
* a sharded checkpoint written under mesh shape A restores under mesh
  shape B through the measured reshard primitive bit-identically —
  params equal at the restore point AND the continued loss curve
  equals the uninterrupted run's.
"""

import threading

import jax
import numpy
import pytest

from test_mnist_e2e import synthetic_digits

from veles_tpu import prng, snapshotter
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.launcher import Launcher
from veles_tpu.models.mnist import MnistWorkflow
from veles_tpu.parallel import reshard
from veles_tpu.parallel.gspmd import (GSPMDTrainer, gspmd_mesh,
                                      gspmd_param_specs, parse_mesh_spec)
from veles_tpu.parallel.mesh import build_mesh, named_sharding
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train import FusedTrainer


def _make_workflow(launcher, max_epochs=3, mb=64):
    prng.get().seed(42)
    prng.get("loader").seed(43)
    # minibatch 64 divides every mesh batch extent these tests use
    # (8, 4) — the first check an elastic restart at a new world size
    # hits (parallel/dp.py)
    return MnistWorkflow(launcher,
                         provider=synthetic_digits(n_train=320,
                                                   n_valid=64),
                         layers=(32,), minibatch_size=mb,
                         learning_rate=0.08, max_epochs=max_epochs)


def _build_wf(max_epochs=3):
    wf = _make_workflow(DummyLauncher(), max_epochs=max_epochs)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def _weights(wf):
    return {(i, k): numpy.asarray(arr.mem)
            for i, fwd in enumerate(wf.forwards)
            for k, arr in fwd.param_arrays().items()}


def _loss_curve(history):
    """Every float the fused history carries, epoch by epoch."""
    return [(h["epoch"],
             h["validation"]["loss"], h["validation"]["normalized"],
             h["train"]["loss"], h["train"]["normalized"])
            for h in history]


def _assert_reported_curve_matches(got, want):
    """The REPORTED losses of two differently partitioned programs: a
    few float32 ULP (rtol 4e-7), not bit-equal. Each program's compiler
    picks the order in which it sums a batch's per-sample losses, and
    the 8-way program's sum comes out one ULP from the one-device
    program's (2.3427908 against 2.3427906, ROADMAP D8). What training
    depends on, the weights, stays asserted bit-equal beside this."""
    numpy.testing.assert_allclose(
        numpy.asarray(_loss_curve(got), numpy.float64),
        numpy.asarray(_loss_curve(want), numpy.float64),
        rtol=4e-7, atol=0)


# -- mesh spec parsing -------------------------------------------------------


def test_gspmd_mesh_and_spec_parsing():
    mesh = gspmd_mesh()
    assert mesh.shape["batch"] == 8 and mesh.shape["model"] == 1
    mesh = parse_mesh_spec("batch=4,model=2")
    assert mesh.shape["batch"] == 4 and mesh.shape["model"] == 2
    mesh = parse_mesh_spec("4x2")
    assert mesh.shape["batch"] == 4 and mesh.shape["model"] == 2
    mesh = parse_mesh_spec("auto")
    assert mesh.shape["batch"] == 8
    with pytest.raises(ValueError, match="axis"):
        parse_mesh_spec("batch=4,pipe=2")
    with pytest.raises(ValueError, match="BATCHxMODEL"):
        parse_mesh_spec("2x2x2")
    with pytest.raises(ValueError, match="no 'batch' axis"):
        GSPMDTrainer(_build_wf(), mesh=build_mesh({"data": 8}))


def test_gspmd_param_specs_consume_tp_rules():
    wf = _build_wf()
    # model axis of 1: pure DP, replicated params (None = default)
    assert gspmd_param_specs(wf.forwards, gspmd_mesh()) is None
    mesh = gspmd_mesh(batch=4, model=2)
    specs = gspmd_param_specs(wf.forwards, mesh)
    assert specs is not None and len(specs) == len(wf.forwards)
    # the first dense layer is column-sharded over the model axis
    assert specs[0]["weights"].spec == jax.sharding.PartitionSpec(
        None, "model")


# -- the acceptance pin: bit-parity with the coordinator path ----------------


def test_gspmd_loss_curve_bit_identical_to_coordinator():
    """ISSUE 15 acceptance: the GSPMD path (one jit, NamedShardings
    over the 8-way batch axis, psum gradient merge) must produce a
    loss curve BIT-IDENTICAL to the coordinator path (master + slave,
    strict sequential protocol) on the same minibatch sequence over
    >= 3 epochs."""
    # coordinator leg: segment_size=1 + pipeline=False is the strict
    # sequential protocol (one job in flight — the PR 12 parity bar)
    master = Launcher(listen_address="127.0.0.1:0", graphics=False,
                      segment_size=1, heartbeat_timeout=5.0)
    wf_coord = _make_workflow(master)
    master.initialize()
    port = master._server.address[1]
    slave = Launcher(master_address="127.0.0.1:%d" % port,
                     graphics=False, pipeline=False)
    _make_workflow(slave)
    slave.initialize()
    slave_thread = threading.Thread(target=slave.run, daemon=True)
    slave_thread.start()
    master.run()
    slave_thread.join(timeout=120)
    assert not slave_thread.is_alive()
    coord_history = wf_coord.decision.epoch_history
    assert [h["epoch"] for h in coord_history] == [0, 1, 2]

    # GSPMD leg through the SAME production driver (launcher --gspmd)
    gspmd = Launcher(graphics=False, gspmd="batch=8,model=1")
    wf_gspmd = _make_workflow(gspmd)
    gspmd.initialize()
    gspmd.run()
    assert gspmd.run_mode_used == "gspmd"

    # every float in every epoch entry equal — no tolerance
    assert wf_gspmd.decision.epoch_history == coord_history


def test_gspmd_matches_fused_trainer_bit_for_bit():
    """Direct trainer-level parity: the final weights of the GSPMD
    step equal the single-device fused step bit-for-bit (the psum merge
    is bit-transparent), and the reported loss curve agrees to a few
    float32 ULP."""
    wf_one = _build_wf()
    h_one = FusedTrainer(wf_one).train()
    w_one = _weights(wf_one)

    wf_g = _build_wf()
    trainer = GSPMDTrainer(wf_g)  # default mesh: 8-way batch axis
    h_g = trainer.train()
    w_g = _weights(wf_g)

    _assert_reported_curve_matches(h_g, h_one)
    assert set(w_g) == set(w_one)
    for key in w_one:
        assert (w_g[key] == w_one[key]).all(), key

    # telemetry contracts (ISSUE 15 satellites): the sweep histogram
    # observed every epoch, and the collective-bytes estimate was
    # harvested for the PARTITIONED program (and only for it)
    registry = get_registry()
    sweeps = {labels["phase"]: child.count for labels, child in
              registry.get("veles_gspmd_step_ms").series()}
    assert sweeps["train"] >= 3 and sweeps["eval"] >= 3
    coll = {labels["op"]: child.value for labels, child in
            registry.get("veles_op_collective_bytes").series()}
    assert coll.get("gspmd_train_segment", 0) > 0
    assert coll.get("gspmd_eval_segment", 0) > 0


def test_gspmd_streamed_out_of_core_matches_resident():
    """The PR 8 staging ring under the GSPMD step: shards placed
    directly as addressable per-device shards of the global batch
    (prefetch.sharded_placer), loss curve equal to the resident run."""
    wf_res = _build_wf()
    h_res = GSPMDTrainer(wf_res, stream=False).train()
    wf_str = _build_wf()
    trainer = GSPMDTrainer(wf_str, stream=True)
    assert trainer.streaming
    try:
        h_str = trainer.train()
    finally:
        trainer.shutdown()
    assert _loss_curve(h_str) == _loss_curve(h_res)
    # the streamed shards went through the measured reshard primitive
    fam = get_registry().get("veles_reshard_ms")
    placed = [child.count for labels, child in fam.series()
              if labels == {"src": "host", "dst": "P(batch)"}]
    assert placed and placed[0] > 0


# -- reshard: the measured layout-change primitive ---------------------------


def test_reshard_roundtrip_bit_identical_and_labeled():
    mesh = gspmd_mesh()
    host = numpy.arange(64 * 3, dtype=numpy.float32).reshape(64, 3)
    fam = reshard.reshard_histogram()
    sharded = reshard.reshard(host, named_sharding(mesh, "batch"))
    assert reshard.layout_label(sharded) == "P(batch)"
    repl = reshard.reshard(sharded, named_sharding(mesh), block=True)
    assert reshard.layout_label(repl) == "replicated"
    back = reshard.gather_to_host(repl)
    assert (back == host).all()
    series = {tuple(sorted(labels.items())): child.count
              for labels, child in fam.series()}
    for labels in ({"src": "host", "dst": "P(batch)"},
                   {"src": "P(batch)", "dst": "replicated"},
                   {"src": "replicated", "dst": "host"}):
        key = tuple(sorted(labels.items()))
        assert series.get(key, 0) > 0, (labels, series)


def test_layout_labels_bounded_forms():
    mesh = gspmd_mesh(batch=4, model=2)
    assert reshard.layout_label(named_sharding(mesh)) == "replicated"
    assert reshard.layout_label(
        named_sharding(mesh, None, "model")) == "P(_,model)"
    assert reshard.layout_label(
        named_sharding(mesh, ("batch", "model"))) == "P(batch+model)"
    assert reshard.layout_label(numpy.zeros(3)) == "host"
    committed = jax.device_put(numpy.zeros(3), jax.devices()[0])
    assert reshard.layout_label(committed) in ("committed",
                                               "replicated")


def test_reshard_tree_mixed_specs():
    mesh = gspmd_mesh()
    tree = {"a": numpy.ones((16, 2), numpy.float32),
            "b": numpy.full((4,), 7.0, numpy.float32)}
    out = reshard.reshard_tree(tree, named_sharding(mesh), block=True)
    assert (numpy.asarray(out["a"]) == tree["a"]).all()
    assert (numpy.asarray(out["b"]) == tree["b"]).all()


# -- the acceptance pin: checkpoint mesh A -> restore mesh B -----------------


def test_checkpoint_restores_across_mesh_shapes_bit_identical(tmp_path):
    """ISSUE 15 acceptance: a sharded checkpoint written under mesh
    shape A (batch=8) restores under mesh shape B (batch=4, model=2)
    through parallel/reshard.py bit-identically — every re-placed
    param equals the checkpoint moment's exactly, and the first
    continued epoch's loss curve entry equals the uninterrupted run's
    bit for bit (later epochs drift at the ULP level only: a 4-way
    gradient psum sums partials in a different order than the 8-way
    one — float non-associativity, not restore error; curve-level
    bit-parity at a FIXED mesh shape is pinned by the coordinator
    test above)."""
    snapdir = str(tmp_path)
    mesh_a = gspmd_mesh()                     # batch=8, model=1
    checkpoint_epoch = 2

    wf_full = _build_wf(max_epochs=4)
    trainer_a = GSPMDTrainer(wf_full, mesh=mesh_a)
    saved = {}

    def on_epoch(tr, params, states):
        if len(tr.decision.epoch_history) != checkpoint_epoch:
            return
        records = tr.checkpoint_records(params, states)
        gen_dir, _ = snapshotter.save_snapshot_sharded(
            tr.workflow, snapdir, records, tag="_meshA",
            manifest_extra={"mesh_axes": {str(k): int(v) for k, v in
                                          dict(tr.mesh.shape).items()}})
        saved["dir"] = gen_dir
        saved["params"] = {
            (i, k): numpy.asarray(v)
            for i, layer in enumerate(params)
            for k, v in layer.items()}

    trainer_a.epoch_callback = on_epoch
    h_full = trainer_a.train()
    assert "dir" in saved, "checkpoint callback never fired"
    full_curve = _loss_curve(h_full)
    assert len(full_curve) == 4

    # the manifest names the SOURCE layout the restore reshards from
    manifest = snapshotter.generation_manifest(saved["dir"])
    assert manifest["mesh_axes"] == {"batch": 8, "model": 1}

    # restore under mesh B: a different shape on the same devices —
    # the run_elastic_training restore sequence, minus the supervisor
    wf_b = snapshotter.load_workflow(saved["dir"])
    wf_b.initialize(device=Device(backend="cpu"))
    resume_epoch = wf_b.decision.prepare_resume()
    assert resume_epoch == checkpoint_epoch
    wf_b.loader.reset_to_epoch_start(resume_epoch)
    mesh_b = gspmd_mesh(batch=4, model=2)
    # shard_model=False: mesh B re-partitions the BATCH axis only, so
    # the continued math stays bit-comparable to the uninterrupted run
    trainer_b = GSPMDTrainer(wf_b, mesh=mesh_b, shard_model=False)
    params_b, states_b = trainer_b.pull_params()
    replaced = {(i, k): numpy.asarray(v)
                for i, layer in enumerate(params_b)
                for k, v in layer.items()}
    assert set(replaced) == set(saved["params"])
    for key in saved["params"]:
        assert (replaced[key] == saved["params"][key]).all(), key
    # ... and they actually live on mesh B's layout
    leaf = params_b[0]["weights"]
    assert leaf.sharding.is_equivalent_to(
        named_sharding(mesh_b), leaf.ndim)

    h_resumed = trainer_b.train(initial_state=(params_b, states_b))
    resumed_curve = _loss_curve(h_resumed)
    assert len(resumed_curve) >= 2
    # first continued epoch: bit-identical (restored state + loader
    # rewind + PRNG streams all exact, and the shard-invariant loss
    # reductions hold whatever the batch-axis width)
    assert resumed_curve[-2] == full_curve[2]
    # the rest: ULP-level only (different psum partial order at
    # batch=4 vs batch=8)
    numpy.testing.assert_allclose(
        [v for entry in resumed_curve[-2:] for v in entry[1:]],
        [v for entry in full_curve[2:] for v in entry[1:]],
        rtol=1e-6)


# -- elastic integration -----------------------------------------------------


def test_elastic_default_trainer_is_gspmd():
    """The elastic supervisor drives the GSPMD path (ISSUE 15): an
    unsupervised run_elastic_training call trains through GSPMDTrainer
    over the named batch mesh and matches the fused curve."""
    from veles_tpu.parallel import elastic

    wf_ref = _build_wf(max_epochs=2)
    h_ref = FusedTrainer(wf_ref).train()

    history = elastic.run_elastic_training(
        lambda: _build_wf(max_epochs=2))
    _assert_reported_curve_matches(history, h_ref)
    # the sweep went through the GSPMD telemetry (proof of the path)
    fam = get_registry().get("veles_gspmd_step_ms")
    assert fam is not None and any(
        child.count for _, child in fam.series())


def test_gspmd_backward_fence_leaves_the_weights_bit_identical(
        monkeypatch):
    """A partitioned train step fences the entry unit's output
    cotangent on every platform (``dp.fenced``, one
    ``optimization_barrier`` in the backward pass). The fence
    separates and computes nothing: three epochs on the 8-way mesh
    end in the weights of the unfenced program, bit for bit."""
    from veles_tpu.parallel import dp

    def train():
        prng.get().seed(42)
        prng.get("loader").seed(43)
        wf = MnistWorkflow(
            DummyLauncher(),
            provider=synthetic_digits(n_train=320, n_valid=64),
            layers=(32, 24), minibatch_size=64, learning_rate=0.08,
            max_epochs=3)
        wf.initialize(device=Device(backend="cpu"))
        GSPMDTrainer(wf).train()
        return _weights(wf)

    seen = []
    barrier = jax.lax.optimization_barrier
    monkeypatch.setattr(jax.lax, "optimization_barrier",
                        lambda tree: seen.append(tree) or barrier(tree))
    fenced = train()
    assert seen, "no cotangent went through the fence"
    monkeypatch.setattr(dp, "fenced", lambda x: x)
    del seen[:]
    bare = train()
    assert not seen
    assert set(fenced) == set(bare)
    for key in bare:
        assert (fenced[key] == bare[key]).all(), key


# -- the planned minibatch fetch (PR 36) -------------------------------------

#: sweeps of two steps by the order their samples come in
FETCH_ORDERS = ("shuffled", "sequential", "strided", "padded")


def _fetch_trainer(mesh_spec, n_devices):
    """A resident GSPMDTrainer with 64 slots a batch shard (a pair's
    capacity is 40 rows over 4 shards, 24 over 8, a shard's whole
    share over 2) over three minibatches and 7 samples (no mesh here
    divides them: shards end in pad rows), a minibatch of them the
    validation class; and the host's copy of its data set."""
    mesh = parse_mesh_spec(mesh_spec, devices=jax.devices()[:n_devices])
    mb = 64 * mesh.shape["batch"]
    prng.get().seed(42)
    prng.get("loader").seed(43)
    wf = MnistWorkflow(
        DummyLauncher(),
        provider=synthetic_digits(n_train=2 * mb + 7, n_valid=mb),
        layers=(32,), minibatch_size=mb, learning_rate=0.08,
        max_epochs=1)
    wf.initialize(device=Device(backend="cpu"))
    host = (numpy.array(wf.loader.original_data.mem),
            numpy.array(wf.loader.original_labels.mem))
    return GSPMDTrainer(wf, mesh=mesh, stream=False), host


def _sweep_order(order, n_samples, mb, n_shards):
    rng = numpy.random.RandomState(7)
    if order == "sequential":
        idx = numpy.arange(2 * mb)
    elif order == "strided":
        # every sample on shard 0: a pair owes a shard's whole share
        idx = numpy.arange(2 * mb) % (n_samples // n_shards) * n_shards
    else:
        idx = rng.permutation(n_samples)[:2 * mb]
    idx = idx.astype(numpy.int32).reshape(2, mb)
    if order == "padded":
        idx[1, mb // 3:] = -1
    return idx


@pytest.mark.parametrize("order", FETCH_ORDERS)
@pytest.mark.parametrize("mesh_spec,n_devices",
                         [("4x1", 4), ("8x1", 8), ("2x2", 4)])
def test_planned_fetch_is_take_bit_for_bit(mesh_spec, n_devices, order):
    """Slot for slot, what a partitioned segment's scan fetches from
    the interleaved, row-sharded data set is ``jnp.take`` on the
    unsharded one: the same bits in every filled slot, zeros in an
    empty one; the plan's largest pair count is a count over the
    matrix; and what the matrix holds picks the path (a strided order
    overflows the capacity on the 4- and 8-way meshes and takes the
    partitioner's gather, over rows remapped by the same placement)."""
    from veles_tpu.parallel import dp

    trainer, host = _fetch_trainer(mesh_spec, n_devices)
    n_shards = trainer.mesh.shape["batch"]
    mb = trainer.loader.max_minibatch_size
    idx = _sweep_order(order, len(host[0]), mb, n_shards)

    cap = dp.exchange_capacity(mb, n_shards)
    plan, pair_rows = dp.plan_fetch(idx, n_shards, cap)
    counted = numpy.zeros((2, n_shards, n_shards), int)
    for step, slot in zip(*numpy.nonzero(idx >= 0)):
        counted[step, idx[step, slot] % n_shards,
                slot // (mb // n_shards)] += 1
    assert (pair_rows == counted).all()
    assert (plan is None) == (counted.max() > cap)
    if mesh_spec != "2x2":  # there a pair's capacity is a shard's share
        assert (plan is None) == (order == "strided")

    sweeps = get_registry().counter(
        "veles_input_exchange_sweeps_total", labels=("segment", "path"))
    path = "partitioner" if plan is None else "planned"
    before = sweeps.labels(segment="eval", path=path).value
    operand = trainer._index_operand("eval", idx)
    assert sweeps.labels(segment="eval", path=path).value == before + 1
    assert isinstance(operand, dp.FetchPlan) == (plan is not None)

    data, truth, valid = jax.jit(lambda data_args, operand: jax.lax.scan(
        lambda _, step: (None, trainer._fetch(data_args, step)),
        None, operand)[1])(trainer._data_args, operand)
    want_data, want_truth = (numpy.stack(part) for part in zip(*(
        FusedTrainer._gather(host, step) for step in idx)))
    filled = idx >= 0
    assert (numpy.asarray(valid) == filled).all()
    data = numpy.asarray(data)
    assert data.dtype == want_data.dtype
    assert (data.view(numpy.uint32)[filled]
            == want_data.view(numpy.uint32)[filled]).all()
    assert not data[~filled].any()
    assert (numpy.asarray(truth)[filled]
            == numpy.asarray(want_truth)[filled]).all()


def test_exchange_sweeps_are_counted_by_their_path():
    """A trainer's own sweeps: shuffled train sweeps and the
    sequential validation sweep are planned; when the loader's order
    is strided over the shards, the same trainer takes the
    partitioner's gather for that sweep, serves the same losses, and
    goes back."""
    from veles_tpu.loader.base import VALIDATION

    registry = get_registry()
    sweeps = registry.counter("veles_input_exchange_sweeps_total",
                              labels=("segment", "path"))

    def counts():
        return {(segment, path): sweeps.labels(
            segment=segment, path=path).value
            for segment in ("train", "eval")
            for path in ("planned", "partitioner")}

    before = counts()
    trainer, _ = _fetch_trainer("4x1", 4)
    params, states = trainer.pull_params()
    losses = numpy.asarray(trainer.eval_class(params, VALIDATION)[0])
    trainer.train_class(params, states)
    after = counts()
    assert after["eval", "planned"] == before["eval", "planned"] + 1
    assert after["train", "planned"] == before["train", "planned"] + 1
    assert after["eval", "partitioner"] == before["eval", "partitioner"]
    rows = registry.get("veles_input_exchange_rows").labels(
        segment="train").value
    needed = registry.get("veles_input_exchange_needed_rows").labels(
        segment="train").value
    assert rows == 4 * 40 and 32 <= needed <= 64

    # the validation class's 256 samples, every fourth first: each
    # shard's 64 slots ask one shard for all their rows
    order = trainer.loader.shuffled_indices.map_write()
    order[:256] = numpy.arange(256).reshape(64, 4).T.reshape(-1)
    strided = numpy.asarray(trainer.eval_class(params, VALIDATION)[0])
    assert counts()["eval", "partitioner"] == \
        before["eval", "partitioner"] + 1
    # one batch of the same 256 samples in another order
    numpy.testing.assert_allclose(strided, losses, rtol=1e-6)
