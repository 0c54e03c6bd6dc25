"""Multi-host (DCN) loopback: two OS processes joined by
``init_multihost`` into ONE JAX runtime train a shared data-parallel
job and match the single-process result (VERDICT r2 item #7 — the
reference's multi-node story, ``manualrst_veles_distributed_training``,
realized as multi-controller SPMD instead of ZeroMQ masters).

Each process owns 4 virtual CPU devices; the global mesh has 8. Both
processes execute the same program; gradient psums cross the process
boundary through the Gloo collectives the distributed runtime wires up.
"""

import json
import os
import subprocess
import sys

import numpy
import pytest

_WORKER = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %(repo)r)
import jax

from veles_tpu.parallel.mesh import init_multihost
pid = int(sys.argv[1])
assert init_multihost("127.0.0.1:%(port)d", num_processes=2,
                      process_id=pid)
assert len(jax.devices()) == 8, jax.devices()

import numpy
from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.mnist import MnistWorkflow
from veles_tpu.parallel import DataParallelTrainer, build_mesh


class Provider(object):
    def __call__(self):
        rng = numpy.random.RandomState(5)
        mk = lambda n: (rng.rand(n, 8, 8).astype(numpy.float32),
                        rng.randint(0, 10, n).astype(numpy.int32))
        tx, ty = mk(640)
        vx, vy = mk(128)
        return tx, ty, vx, vy


prng.get().seed(42)
prng.get("loader").seed(43)
wf = MnistWorkflow(DummyLauncher(), provider=Provider(), layers=(32,),
                   minibatch_size=64, learning_rate=0.08, max_epochs=3)
wf.initialize(device=Device(backend="cpu"))
mesh = build_mesh({"data": 8})
trainer = DataParallelTrainer(wf, mesh=mesh)
history = trainer.train()
out = [e["validation"]["normalized"] for e in history]
with open(sys.argv[2], "w") as f:
    json.dump(out, f)
print("process", pid, "done:", out, flush=True)
"""


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_loopback_training_matches_single(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "mh_worker.py"
    script.write_text(_WORKER % {"repo": repo, "port": _free_port()})
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = []
    outs = []
    for pid in range(2):
        out = str(tmp_path / ("h%d.json" % pid))
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(pid), out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    for proc in procs:
        stdout, _ = proc.communicate(timeout=600)
        assert proc.returncode == 0, stdout.decode(errors="replace")[-3000:]

    h0 = json.load(open(outs[0]))
    h1 = json.load(open(outs[1]))
    # both controllers ran the same program: identical histories
    assert h0 == h1
    assert len(h0) == 3

    # and the cross-process run matches one process owning all 8 devices
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistWorkflow
    from veles_tpu.parallel import DataParallelTrainer, build_mesh

    class Provider(object):
        def __call__(self):
            rng = numpy.random.RandomState(5)
            mk = lambda n: (rng.rand(n, 8, 8).astype(numpy.float32),  # noqa
                            rng.randint(0, 10, n).astype(numpy.int32))
            tx, ty = mk(640)
            vx, vy = mk(128)
            return tx, ty, vx, vy

    prng.get().seed(42)
    prng.get("loader").seed(43)
    wf = MnistWorkflow(DummyLauncher(), provider=Provider(),
                       layers=(32,), minibatch_size=64,
                       learning_rate=0.08, max_epochs=3)
    wf.initialize(device=Device(backend="cpu"))
    single = [e["validation"]["normalized"]
              for e in DataParallelTrainer(
                  wf, mesh=build_mesh({"data": 8})).train()]
    # Gloo's cross-process allreduce does not promise a reduction
    # order, so the psum'd gradients drift from the single-process
    # result at the ULP level and amplify over epochs into a few
    # flipped validation samples (observed ≤3 of 128, varying run to
    # run). The bitwise check above (h0 == h1) already pins SPMD
    # correctness; against the single-process baseline we assert
    # training-trajectory equivalence instead: every epoch's accuracy
    # within a handful of samples.
    numpy.testing.assert_allclose(h0, single, atol=6.5 / 128)


# -- GSPMD tier, multi-process (ISSUE 15) ------------------------------------
#
# The CI "GSPMD multi-process smoke" step runs this explicitly
# (slow-marked so tier-1 pays for the 2-process XLA bring-up once, in
# its own job step, not inside the suite).

_GSPMD_WORKER = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, %(repo)r)
import jax

from veles_tpu.parallel.mesh import init_multihost
pid = int(sys.argv[1])
assert init_multihost("127.0.0.1:%(port)d", num_processes=2,
                      process_id=pid)
assert len(jax.devices()) == 8, jax.devices()

import numpy
from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.models.mnist import MnistWorkflow
from veles_tpu.parallel import GSPMDTrainer, gspmd_mesh


class Provider(object):
    def __call__(self):
        rng = numpy.random.RandomState(5)
        mk = lambda n: (rng.rand(n, 8, 8).astype(numpy.float32),
                        rng.randint(0, 10, n).astype(numpy.int32))
        tx, ty = mk(640)
        vx, vy = mk(128)
        return tx, ty, vx, vy


prng.get().seed(42)
prng.get("loader").seed(43)
wf = MnistWorkflow(DummyLauncher(), provider=Provider(), layers=(32,),
                   minibatch_size=64, learning_rate=0.08, max_epochs=3)
wf.initialize(device=Device(backend="cpu"))
trainer = GSPMDTrainer(wf, mesh=gspmd_mesh())
history = trainer.train()
out = [(e["validation"]["loss"], e["validation"]["normalized"],
        e["train"]["loss"], e["train"]["normalized"])
       for e in history]
with open(sys.argv[2], "w") as f:
    json.dump(out, f)
print("process", pid, "gspmd done:", out, flush=True)
"""


@pytest.mark.slow
def test_two_process_gspmd_training_is_consistent(tmp_path):
    """ISSUE 15 satellite: the GSPMD tier across a REAL process
    boundary — two jax.distributed processes (gloo collectives, 4
    virtual devices each) drive one GSPMDTrainer over the global
    8-way batch mesh. Both controllers must produce the identical
    loss curve (one SPMD program), pinning the multi-process path the
    CI smoke exists for."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "gspmd_worker.py"
    script.write_text(_GSPMD_WORKER % {"repo": repo,
                                       "port": _free_port()})
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = []
    outs = []
    for pid in range(2):
        out = str(tmp_path / ("g%d.json" % pid))
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(pid), out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    for proc in procs:
        stdout, _ = proc.communicate(timeout=600)
        assert proc.returncode == 0, \
            stdout.decode(errors="replace")[-3000:]

    h0 = json.load(open(outs[0]))
    h1 = json.load(open(outs[1]))
    # both controllers ran the same partitioned program: identical
    # float-level curves, 3 epochs
    assert h0 == h1
    assert len(h0) == 3
    # and training made progress
    assert h0[-1][1] < h0[0][1]
