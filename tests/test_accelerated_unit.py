"""AcceleratedUnit backend dispatch (cf. tests/test_accelerated_unit.py)."""

import numpy

from veles_tpu.accelerated_units import AcceleratedUnit, AcceleratedWorkflow
from veles_tpu.backends import Device, NumpyDevice
from veles_tpu.dummy import DummyLauncher
from veles_tpu.memory import Array


class Doubler(AcceleratedUnit):
    """Doubles its input Array; has both jax and numpy implementations."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(Doubler, self).__init__(workflow, **kwargs)
        self.input = None
        self.output = None
        self.path = None

    def initialize(self, device=None, **kwargs):
        super(Doubler, self).initialize(device=device, **kwargs)
        self.output = Array(numpy.zeros_like(self.input.mem))
        self.init_vectors(self.input, self.output)

    def jax_run(self):
        self.path = "jax"
        self.unmap_vectors(self.input)
        self.output.assign_devmem(self.input.devmem * 2)

    def numpy_run(self):
        self.path = "numpy"
        self.output.map_invalidate()[...] = self.input.mem * 2


def _make(device):
    wf = AcceleratedWorkflow(DummyLauncher())
    u = Doubler(wf, name="doubler")
    u.input = Array(numpy.arange(4, dtype=numpy.float32))
    u.link_from(wf.start_point)
    wf.end_point.link_from(u)
    wf.initialize(device=device)
    wf.run()
    return u


def test_jax_path():
    u = _make(Device(backend="cpu"))
    assert u.path == "jax"
    numpy.testing.assert_allclose(u.output.map_read(), [0, 2, 4, 6])


def test_timed_run_waits_for_the_units_outputs(monkeypatch):
    """Dispatch is asynchronous: a run() that is timed (``timings``)
    must end by blocking on the unit's own device arrays, or it times
    the enqueue."""
    waited = []
    real = Array.block_until_ready
    monkeypatch.setattr(
        Array, "block_until_ready",
        lambda self: waited.append(self) or real(self))
    wf = AcceleratedWorkflow(DummyLauncher())
    unit = Doubler(wf, name="timed", timings=True)
    unit.input = Array(numpy.arange(4, dtype=numpy.float32))
    unit.link_from(wf.start_point)
    wf.end_point.link_from(unit)
    wf.initialize(device=Device(backend="cpu"))
    wf.run()
    assert unit.output in waited
    waited.clear()
    assert _make(Device(backend="cpu")).output not in waited  # untimed


def test_numpy_path():
    u = _make(NumpyDevice())
    assert u.path == "numpy"
    numpy.testing.assert_allclose(u.output.map_read(), [0, 2, 4, 6])


def test_force_numpy_flag():
    wf = AcceleratedWorkflow(DummyLauncher())
    u = Doubler(wf, name="doubler", force_numpy=True)
    u.input = Array(numpy.arange(3, dtype=numpy.float32))
    u.link_from(wf.start_point)
    wf.end_point.link_from(u)
    wf.initialize(device=Device(backend="cpu"))
    wf.run()
    assert u.path == "numpy"


def test_workflow_owns_device():
    wf = AcceleratedWorkflow(DummyLauncher())
    wf.initialize(device=NumpyDevice())
    assert wf.device is not None
