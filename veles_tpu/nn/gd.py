"""Gradient-descent (backward) units.

The Znicz GD family (GradientDescent, GDTanh, GDRELU, GDSigmoid,
GDSoftmax, GDConv, GDPooling, ...) hand-wrote every backward kernel. Here
ONE implementation serves them all: the paired forward unit's pure
``apply_for_grad`` is differentiated with ``jax.vjp``, the optimizer
rule updates the (shared) parameter Arrays in place, and ``err_input``
propagates to the previous layer's GD unit. The class aliases survive so
reference workflow topologies translate one-to-one.

For the softmax head the evaluator already supplies the gradient w.r.t.
the *logits* (see evaluator.py), so ``All2AllSoftmax.apply_for_grad``
returns logits and GDSoftmax is literally the base class.
"""

import jax
import numpy

from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.memory import Array
from veles_tpu.nn.optim import get_solver


class GradientDescentBase(AcceleratedUnit):
    """Backward unit for any ForwardBase via jax.vjp."""

    hide_from_registry = True
    view_group = "TRAINER"

    def __init__(self, workflow, forward=None, **kwargs):
        self.learning_rate = kwargs.pop("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.pop("learning_rate_bias", None)
        self.weights_decay = kwargs.pop("weights_decay", 0.0)
        self.gradient_moment = kwargs.pop("momentum",
                                          kwargs.pop("gradient_moment",
                                                     0.0))
        self.solver_name = kwargs.pop("solver", "sgd")
        self._solver_hp = dict(kwargs.pop("solver_hp", {}))
        self.need_err_input = kwargs.pop("need_err_input", True)
        super(GradientDescentBase, self).__init__(workflow, **kwargs)
        self.forward = forward
        self.err_output = None      # linked: next GD's err_input / evaluator
        self.err_input = Array()    # produced for the previous layer
        self.opt_state = None
        self.demand("err_output")

    @property
    def hyper(self):
        hp = {"learning_rate": self.learning_rate,
              "weight_decay": self.weights_decay,
              "momentum": self.gradient_moment}
        if self.learning_rate_bias is not None:
            hp["lr_overrides"] = {"bias": self.learning_rate_bias}
        hp.update(getattr(self, "_solver_hp", {}))
        return hp

    def initialize(self, device=None, **kwargs):
        if self.forward is None:
            raise ValueError("%s needs its paired forward unit" % self.name)
        super(GradientDescentBase, self).initialize(device=device, **kwargs)
        if self.need_err_input:
            in_mem = (self.forward.input.mem
                      if isinstance(self.forward.input, Array)
                      else self.forward.input)
            self.err_input.reset(numpy.zeros(in_mem.shape, numpy.float32))
            self.init_vectors(self.err_input)
        solver = get_solver(self.solver_name)
        params = self.forward.gradient_params(
            {k: numpy.asarray(v.mem)
             for k, v in self.forward.param_arrays().items()})
        if self.opt_state is None and params:
            import jax.numpy as jnp
            self.opt_state = jax.tree_util.tree_map(
                jnp.asarray, solver.init(
                    {k: jnp.asarray(v) for k, v in params.items()}))

    def _bwd_fn(self):
        """Builds the jitted (params, x, err_out, state, hp) -> ... fn."""
        fwd = self.forward
        solver = get_solver(self.solver_name)
        has_params = bool(fwd.param_arrays())

        def step(params, x, err_out, state, hp):
            def f(p, xin):
                return fwd.apply_for_grad(p, xin)
            _, vjp = jax.vjp(f, params, x)
            gparams, gx = vjp(err_out)
            if has_params:
                moved, new_state = solver.update(
                    fwd.gradient_params(params),
                    fwd.gradient_params(gparams), state, hp)
                new_params = dict(params, **moved)
            else:
                new_params, new_state = params, state
            return new_params, gx, new_state

        return step

    def jax_run(self):
        fwd = self.forward
        self.unmap_vectors(self.err_output, fwd.weights, fwd.bias)
        params = fwd.param_values()
        # _input_devmem / place_for_grad: mesh-running forwards
        # (ring-attention units) re-place committed single-device
        # buffers so the jitted step sees one consistent device set
        x = fwd._input_devmem()
        err_out = (self.err_output.devmem
                   if isinstance(self.err_output, Array)
                   else self.err_output)
        err_out = fwd.place_for_grad(err_out)
        state = fwd.place_for_grad(self.opt_state or {})
        step = self.jit(self._get_step())
        new_params, gx, new_state = step(params, x, err_out,
                                         state, self.hyper)
        for k, arr in fwd.param_arrays().items():
            arr.assign_devmem(new_params[k])
        self.opt_state = new_state
        if self.need_err_input:
            self.err_input.assign_devmem(gx)

    def _get_step(self):
        if not hasattr(self, "_step_fn_") or self._step_fn_ is None:
            self._step_fn_ = self._bwd_fn()
        return self._step_fn_

    def init_unpickled(self):
        super(GradientDescentBase, self).init_unpickled()
        self._step_fn_ = None

    def numpy_run(self):
        self.jax_run()  # same pure math on host buffers

    # -- distribution (the Znicz GD protocol re-imagined): master sends
    # canonical weights with each job, the slave's local step produces a
    # delta that the master merges additively — a point-to-point
    # parameter-server exchange, exactly the reference's only training
    # parallelism (SURVEY.md §2.4; hooks at ``units.py:157-164``) -------

    def generate_data_for_slave(self, slave=None):
        params = {k: numpy.array(v.map_read())
                  for k, v in self.forward.param_arrays().items()}
        return params or None

    def apply_data_from_master(self, data):
        base = {}
        for k, value in (data or {}).items():
            target = self.forward.param_arrays()[k]
            mem = target.map_invalidate()
            mem[...] = value
            base[k] = value  # freshly unpickled: this frame owns it
        self._job_base_params_ = base

    def generate_data_for_master(self):
        base = getattr(self, "_job_base_params_", None) or {}
        out = {}
        for k, arr in self.forward.param_arrays().items():
            new = numpy.array(arr.map_read())
            out[k] = new - base[k] if k in base else new
        return out or None

    def apply_data_from_slave(self, data, slave=None):
        for k, delta in (data or {}).items():
            target = self.forward.param_arrays()[k]
            mem = target.map_write()
            mem += delta


# -- reference-parity aliases ------------------------------------------------

class GradientDescent(GradientDescentBase):
    """For All2All (linear)."""
    hide_from_registry = False


class GDTanh(GradientDescentBase):
    pass


class GDRELU(GradientDescentBase):
    pass


class GDStrictRELU(GradientDescentBase):
    pass


class GDSigmoid(GradientDescentBase):
    pass


class GDSoftmax(GradientDescentBase):
    """err_output is already d(loss)/d(logits) — see EvaluatorSoftmax."""


class GDConv(GradientDescentBase):
    pass


class GDPooling(GradientDescentBase):
    """No parameters: pure gradient routing through the pooling vjp."""


class GDActivation(GradientDescentBase):
    pass
