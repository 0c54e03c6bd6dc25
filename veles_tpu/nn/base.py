"""Base classes for NN forward units.

A forward unit owns parameters (``weights``/``bias`` as
:class:`~veles_tpu.memory.Array`) and a **pure** ``apply(params, x)``.
Eager execution jits ``apply`` per static shape; the step compiler
(:mod:`veles_tpu.train`) reuses the same ``apply`` to build one fused
train step — the unit graph is the model *description*, the compiled
step is the model *execution* (the semantic-gap resolution flagged in
SURVEY.md §7 "hard parts").

Weight initialization follows the reference's filler contract
(``weights_stddev``-style uniform fill from the seeded PRNG registry) so
CPU/TPU runs starting from the same seed produce identical curves.
"""

import numpy

from veles_tpu import prng
from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.memory import Array


class ForwardBase(AcceleratedUnit):
    """Base forward unit: input -> output through pure ``apply``."""

    hide_from_registry = True
    view_group = "WORKER"
    # weight init legitimately advances the global RNG stream — without
    # this, Unit._initialize_wrapped restores the stream and same-shape
    # layers would start bit-identical
    consumes_global_rng_on_init = True
    #: names of parameters that no solver touches: the unit moves them
    #: itself after a train step (``update_state``), as a router's
    #: selection bias. They travel with the other parameters.
    non_gradient = ()

    def __init__(self, workflow, **kwargs):
        self.include_bias = kwargs.pop("include_bias", True)
        self.weights_stddev = kwargs.pop("weights_stddev", None)
        self.bias_stddev = kwargs.pop("bias_stddev", None)
        self.weights_filling = kwargs.pop("weights_filling", "uniform")
        self.bias_filling = kwargs.pop("bias_filling", "uniform")
        self.rand_name = kwargs.pop("rand", "default")
        super(ForwardBase, self).__init__(workflow, **kwargs)
        self.input = None
        self.output = Array()
        self.weights = Array()
        self.bias = Array()
        self.demand("input")

    # -- to override -------------------------------------------------------

    @property
    def has_weights(self):
        return True

    def weights_shape_for(self, input_shape):
        raise NotImplementedError

    def bias_shape_for(self, input_shape):
        raise NotImplementedError

    def output_shape_for(self, input_shape):
        raise NotImplementedError

    def apply(self, params, x):
        """Pure function: params dict + input batch -> output batch."""
        raise NotImplementedError

    def apply_for_grad(self, params, x):
        """The function the paired GD unit differentiates. Defaults to
        :meth:`apply`; softmax heads return logits instead (the
        evaluator seeds the gradient w.r.t. logits)."""
        return self.apply(params, x)

    def publish_stats(self, registry, tag, stats, params):
        """Registry gauges of what this unit handed out of the last
        train sweep's steps (``stats``, a leading axis of steps; the
        trainer calls this after every sweep, as it calls
        ``update_state`` inside every step), labelled ``tag``; ``params``
        are the unit's after the sweep. Nothing by default."""

    def gradient_params(self, params):
        """``params`` without the :attr:`non_gradient` names: what a
        solver updates and keeps state for."""
        if not self.non_gradient:
            return params
        return {k: v for k, v in params.items()
                if k not in self.non_gradient}

    def _placement_mesh(self):
        """Mesh this unit's ``apply`` runs on, or None. Units whose
        forward is a shard_map (ring attention's seq mesh, MoE's expert
        mesh) return the attached mesh; everything that touches the
        compiled step — params, inputs, err_output, optimizer state —
        is then re-placed onto it (replicated), because a committed
        single-device buffer fails the shard_map's device-set check."""
        return None

    def place_for_grad(self, tree):
        """Re-place committed single-device arrays onto the unit's
        mesh, replicated — identity when no mesh is attached;
        uncommitted host arrays pass through untouched. The paired GD
        step routes err_output / optimizer state through here."""
        mesh = self._placement_mesh()
        if mesh is None:
            return tree
        import jax

        from veles_tpu.parallel.mesh import named_sharding
        repl = named_sharding(mesh)

        def place(v):
            return jax.device_put(v, repl) if hasattr(v, "sharding") \
                else v

        return jax.tree_util.tree_map(place, tree)

    # -- parameter handling ------------------------------------------------

    def fill_weights(self):
        rng = prng.get(self.rand_name)
        shape = self.weights.shape
        fan_in = int(numpy.prod(shape[:-1])) if len(shape) > 1 else shape[0]
        stddev = self.weights_stddev or 1.0 / numpy.sqrt(max(fan_in, 1))
        if self.weights_filling == "gaussian":
            rng.fill_normal(self.weights.mem, 0.0, stddev)
        else:
            rng.fill(self.weights.mem, -stddev, stddev)
        if self.include_bias and self.bias.mem is not None:
            bstd = self.bias_stddev or stddev
            if self.bias_filling == "gaussian":
                rng.fill_normal(self.bias.mem, 0.0, bstd)
            elif self.bias_filling == "constant":
                self.bias.mem[...] = bstd
            else:
                rng.fill(self.bias.mem, -bstd, bstd)

    def fill_matrices(self, mem):
        """Fill a matrix, or a stack of them a matrix at a time (so
        that a stack of experts never needs a float64 copy of itself),
        as :meth:`fill_weights` fills ``weights``: ``weights_filling``,
        ``weights_stddev`` or ``1/sqrt(fan_in)``."""
        rng = prng.get(self.rand_name)
        stddev = self.weights_stddev or 1.0 / numpy.sqrt(mem.shape[-2])
        for part in (mem if mem.ndim > 2 else (mem,)):
            if self.weights_filling == "gaussian":
                rng.fill_normal(part, 0.0, stddev)
            else:
                rng.fill(part, -stddev, stddev)

    def param_values(self):
        """Device-side parameter pytree for ``apply`` (re-placed onto
        the unit's mesh when one is attached)."""
        params = {}
        if self.has_weights:
            params["weights"] = self.weights.devmem
            if self.include_bias:
                params["bias"] = self.bias.devmem
        return self.place_for_grad(params)

    def param_arrays(self):
        out = {}
        if self.has_weights:
            out["weights"] = self.weights
            if self.include_bias:
                out["bias"] = self.bias
        return out

    @property
    def input_shape(self):
        mem = self.input.mem if isinstance(self.input, Array) else self.input
        return tuple(mem.shape)

    # -- lifecycle ---------------------------------------------------------

    def initialize(self, device=None, **kwargs):
        super(ForwardBase, self).initialize(device=device, **kwargs)
        in_shape = self.input_shape
        dtype = numpy.float32
        if self.has_weights and self.weights.mem is None:
            self.weights.reset(numpy.zeros(self.weights_shape_for(in_shape),
                                           dtype))
            if self.include_bias:
                self.bias.reset(numpy.zeros(self.bias_shape_for(in_shape),
                                            dtype))
            self.fill_weights()
        out_shape = self.output_shape_for(in_shape)
        if self.output.mem is None or tuple(self.output.shape) != out_shape:
            self.output.reset(numpy.zeros(out_shape, dtype))
        self.init_vectors(self.input, self.output, self.weights, self.bias)

    # -- execution ---------------------------------------------------------

    def _input_devmem(self):
        return self.place_for_grad(
            self.input.devmem if isinstance(self.input, Array)
            else self.input)

    def jax_run(self):
        self.unmap_vectors(self.input, self.weights, self.bias)
        fwd = self.jit(self.apply)
        self.output.assign_devmem(fwd(self.param_values(),
                                      self._input_devmem()))

    def numpy_run(self):
        # the numpy pseudo-device evaluates the same pure function on
        # host buffers (jax-on-CPU under the hood): one math source
        params = {k: v.mem for k, v in self.param_arrays().items()}
        x = self.input.mem if isinstance(self.input, Array) else self.input
        self.output.map_invalidate()[...] = numpy.asarray(
            self.apply(params, x))


class NamedParamsForward(ForwardBase):
    """A forward unit with several parameters, each under its own name.

    ``PARAMS`` names them; ``param_shapes(input_shape)`` gives each
    one's ``(shape, kind)``: a ``"gain"`` starts at one, a ``"zero"``
    at zero, a ``"matrix"`` is filled as the base fills ``weights``
    (:meth:`ForwardBase.fill_matrices`). A name ``weights`` is the
    base class's own array. There is no bias."""

    hide_from_registry = True
    PARAMS = ()

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("include_bias", False)
        super(NamedParamsForward, self).__init__(workflow, **kwargs)
        for name in self.PARAMS:
            if name != "weights":
                setattr(self, name, Array())

    @property
    def has_weights(self):
        return "weights" in self.PARAMS

    def param_shapes(self, input_shape):
        raise NotImplementedError

    def weights_shape_for(self, input_shape):
        return self.param_shapes(input_shape)["weights"][0]

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def fill_weights(self):
        self._fill(self.weights.mem, self.param_shapes(
            self.input_shape)["weights"][1])

    def _fill(self, mem, kind):
        if kind == "matrix":
            self.fill_matrices(mem)
        else:
            mem[...] = 0.0 if kind == "zero" else 1.0

    def initialize(self, device=None, **kwargs):
        super(NamedParamsForward, self).initialize(device=device, **kwargs)
        for name, (shape, kind) in self.param_shapes(
                self.input_shape).items():
            arr = getattr(self, name)
            if arr.mem is None:
                arr.reset(numpy.zeros(shape, numpy.float32))
                self._fill(arr.mem, kind)
        self.init_vectors(*(getattr(self, n) for n in self.PARAMS))

    def param_arrays(self):
        return {name: getattr(self, name) for name in self.PARAMS}

    def param_values(self):
        return self.place_for_grad(
            {name: getattr(self, name).devmem for name in self.PARAMS})
