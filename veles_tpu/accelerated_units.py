"""Device-backed compute units.

Re-designs ``veles/accelerated_units.py``. The reference's
AcceleratedUnit assembled OpenCL/CUDA source (defines + Jinja2), built
programs with an on-disk binary cache, and rebound
``ocl_run``/``cuda_run``/``numpy_run`` per device. On TPU the whole
pipeline collapses:

* "kernel source assembly" → a pure JAX function; static shapes/dtypes
  are its closure, so re-`jit` per configuration replaces re-templating;
* "program build + binary cache" → XLA compilation + its persistent
  compilation cache (`jax.config.jax_compilation_cache_dir`);
* backend rebinding survives: units implement ``jax_init``/``jax_run``
  (used by both the tpu and cpu devices) and optionally
  ``numpy_init``/``numpy_run`` (oracle path); :meth:`AcceleratedUnit.
  initialize` binds the right pair exactly like the reference's
  ``assign_backend_methods`` (``veles/backends.py:244-262``).
"""

from veles_tpu.backends import default_device
from veles_tpu.config import root
from veles_tpu.memory import Array
from veles_tpu.units import Unit
from veles_tpu.workflow import Workflow

#: maps Device.BACKEND → method prefix units implement
_METHOD_PREFIX = {"tpu": "jax", "cpu": "jax", "numpy": "numpy"}


class AcceleratedUnit(Unit):
    """Base for units whose run() executes on the device."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        self.force_numpy = kwargs.pop(
            "force_numpy", root.common.engine.get("force_numpy", False))
        self.sync_run = kwargs.pop("sync_run", False)
        super(AcceleratedUnit, self).__init__(workflow, **kwargs)
        self.device = None

    def init_unpickled(self):
        super(AcceleratedUnit, self).init_unpickled()
        self._backend_run_ = None
        self._jit_cache_ = {}

    # -- device binding ----------------------------------------------------

    def initialize(self, device=None, **kwargs):
        self.device = device if device is not None else default_device()
        prefix = self._method_prefix()
        init_fn = getattr(self, prefix + "_init", None)
        self._backend_run_ = getattr(self, prefix + "_run")
        if init_fn is not None:
            init_fn()
        return None

    def _method_prefix(self):
        if self.force_numpy or self.device is None or not self.device.exists:
            return "numpy"
        return _METHOD_PREFIX[self.device.backend_name]

    # -- run dispatch ------------------------------------------------------

    def run(self):
        result = self._backend_run_()
        if (self.sync_run or self.timings) and self.device is not None \
                and self.device.is_jax:
            # dispatch is asynchronous: without this wait a timed run()
            # measures the enqueue, not the unit's work on the device
            for arr in vars(self).values():
                if isinstance(arr, Array):
                    arr.block_until_ready()
        return result

    def numpy_run(self):
        raise NotImplementedError(
            "%s has no numpy fallback" % type(self).__name__)

    def jax_run(self):
        raise NotImplementedError(
            "%s has no jax implementation" % type(self).__name__)

    # -- helpers -----------------------------------------------------------

    def init_vectors(self, *arrays):
        """Attach Arrays to this unit's device (devmem allocation)."""
        for arr in arrays:
            if isinstance(arr, Array):
                arr.initialize(self.device)

    def unmap_vectors(self, *arrays):
        """Flush host writes before launching device compute."""
        for arr in arrays:
            if isinstance(arr, Array):
                arr.unmap()

    def map_vectors_read(self, *arrays):
        for arr in arrays:
            if isinstance(arr, Array):
                arr.map_read()

    def jit(self, fn, **jit_kwargs):
        """jit ``fn`` once per (fn, options); placed on this device."""
        key = (fn, tuple(sorted(jit_kwargs.items())))
        cached = self._jit_cache_.get(key)
        if cached is None:
            import jax
            cached = jax.jit(fn, **jit_kwargs)
            self._jit_cache_[key] = cached
        return cached


class DeviceBenchmark(object):
    """Computing-power estimation (``accelerated_units.py:706-824``)."""

    _cache = {}

    @classmethod
    def estimate(cls, device, size=1000, repeats=3):
        key = (getattr(device, "BACKEND", None),
               getattr(device, "device_index", 0), size, repeats)
        if key not in cls._cache:
            if device is None or not device.exists:
                cls._cache[key] = 1.0
            else:
                from veles_tpu.ops.benchmark import gemm_benchmark
                cls._cache[key] = gemm_benchmark(
                    size=size, repeats=repeats,
                    device=device)["computing_power"]
        return cls._cache[key]


class AcceleratedWorkflow(Workflow):
    """Workflow owning a device; passes it down at initialize.

    (``veles/accelerated_units.py:843-858``)
    """

    hide_from_registry = True

    def __init__(self, workflow=None, **kwargs):
        super(AcceleratedWorkflow, self).__init__(workflow, **kwargs)
        self.device = None

    def initialize(self, device=None, **kwargs):
        self.device = device if device is not None else default_device()
        kwargs["device"] = self.device
        return super(AcceleratedWorkflow, self).initialize(**kwargs)

    @property
    def computing_power(self):
        return DeviceBenchmark.estimate(self.device)

    def __getstate__(self):
        state = super(AcceleratedWorkflow, self).__getstate__()
        state["device"] = None  # re-attached on initialize after restore
        return state
