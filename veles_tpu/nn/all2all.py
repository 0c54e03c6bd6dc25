"""Fully-connected (All2All) forward units.

The Znicz All2All family: linear, Tanh (LeCun-scaled), RELU (softplus),
Sigmoid, Softmax heads over ``y = act(x @ W + b)``. Input is flattened
to (batch, features); weights are stored (in_features, out_features) so
the matmul lands on the MXU untransposed.
"""

import jax.numpy as jnp
import numpy

from veles_tpu.nn.activation import get_activation
from veles_tpu.nn.base import ForwardBase
from veles_tpu.nn.precision import get_policy


class All2All(ForwardBase):
    """y = activation(flatten(x) @ W + b)."""

    ACTIVATION = "linear"

    def __init__(self, workflow, output_sample_shape=None, **kwargs):
        if output_sample_shape is None:
            output_sample_shape = kwargs.pop("output_shape", None)
        if output_sample_shape is None:
            raise ValueError("All2All needs output_sample_shape")
        if isinstance(output_sample_shape, int):
            output_sample_shape = (output_sample_shape,)
        self.output_sample_shape = tuple(output_sample_shape)
        self.activation_name = kwargs.pop("activation", self.ACTIVATION)
        super(All2All, self).__init__(workflow, **kwargs)

    @property
    def neurons_number(self):
        return int(numpy.prod(self.output_sample_shape))

    def weights_shape_for(self, input_shape):
        in_features = int(numpy.prod(input_shape[1:]))
        return (in_features, self.neurons_number)

    def bias_shape_for(self, input_shape):
        return (self.neurons_number,)

    def output_shape_for(self, input_shape):
        return (input_shape[0],) + self.output_sample_shape

    def apply(self, params, x):
        batch = x.shape[0]
        pol = get_policy()
        xc, wc = pol.cast_in(x.reshape(batch, -1), params["weights"])
        # preferred_element_type keeps the MXU's f32 accumulator all
        # the way to the output (uniform operand dtypes, so the dot vjp
        # accepts it — unlike conv's)
        y = jnp.dot(xc, wc, preferred_element_type=pol.accum_dtype)
        if "bias" in params:
            y = y + params["bias"]
        y = pol.cast_out(get_activation(self.activation_name)(y))
        return y.reshape((batch,) + self.output_sample_shape)


class All2AllTanh(All2All):
    ACTIVATION = "tanh"


class All2AllRELU(All2All):
    ACTIVATION = "relu"


class All2AllStrictRELU(All2All):
    ACTIVATION = "strict_relu"


class All2AllSigmoid(All2All):
    ACTIVATION = "sigmoid"


class All2AllSoftmax(All2All):
    """Softmax head: output is the probability simplex; ``max_idx`` is
    kept for the evaluator (the reference stores it device-side)."""

    ACTIVATION = "linear"

    def _logits(self, params, x):
        """Head logits, always float32 (softmax/CE numerics need it
        regardless of the compute policy)."""
        batch = x.shape[0]
        pol = get_policy()
        xc, wc = pol.cast_in(x.reshape(batch, -1), params["weights"])
        logits = jnp.dot(xc, wc, preferred_element_type=jnp.float32)
        if "bias" in params:
            logits = logits + params["bias"]
        return logits

    def apply(self, params, x):
        batch = x.shape[0]
        logits = self._logits(params, x)
        # max-subtracted for stability, matches reference's softmax kernel
        z = logits - jnp.max(logits, axis=1, keepdims=True)
        e = jnp.exp(z)
        return (e / jnp.sum(e, axis=1, keepdims=True)).reshape(
            (batch,) + self.output_sample_shape)

    def apply_for_grad(self, params, x):
        """Logits only: EvaluatorSoftmax's err_output is already the
        gradient w.r.t. logits (softmax+CE fused), so GDSoftmax must not
        differentiate through the softmax again."""
        batch = x.shape[0]
        return self._logits(params, x).reshape(
            (batch,) + self.output_sample_shape)
