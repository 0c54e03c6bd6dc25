"""Gated feed-forward unit: ``down(silu(gate x) * up x)``.

The feed-forward half of a pre-norm transformer block, with the
block's norm and residual inside the unit (as the attention units
carry theirs): ``x + MLP(rms_norm(x))``. No bias anywhere.
"""

import jax
import jax.numpy as jnp

from veles_tpu.nn.base import NamedParamsForward
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.nn.precision import get_policy


def gated_mlp(pol, x, gate, up, down):
    """``down(silu(x gate) * (x up))`` under policy ``pol``: operands
    in the compute dtype, sums and the gating in the accumulation
    dtype. Returns the accumulation dtype."""
    xc, gate, up, down = pol.cast_in(x, gate, up, down)
    hidden = jax.nn.silu(
        jnp.dot(xc, gate, preferred_element_type=pol.accum_dtype)) \
        * jnp.dot(xc, up, preferred_element_type=pol.accum_dtype)
    return jnp.dot(pol.cast_in(hidden), down,
                   preferred_element_type=pol.accum_dtype)


class GatedMLPForward(NamedParamsForward):
    """``x + down(silu(gate h) * up h)``, ``h = rms_norm(x)``, over
    the last dim of (batch, seq, dim); ``hidden`` is the inner
    width."""

    hide_from_registry = False
    PARAMS = ("norm", "gate", "up", "down")

    def __init__(self, workflow, hidden=None, eps=1e-5, **kwargs):
        super(GatedMLPForward, self).__init__(workflow, **kwargs)
        self.hidden = int(hidden)
        self.eps = float(eps)

    def param_shapes(self, input_shape):
        dim = input_shape[-1]
        return {"norm": ((dim,), "gain"),
                "gate": ((dim, self.hidden), "matrix"),
                "up": ((dim, self.hidden), "matrix"),
                "down": ((self.hidden, dim), "matrix")}

    def apply(self, params, x):
        pol = get_policy()
        y = gated_mlp(pol, rms_norm(x, params["norm"], self.eps),
                      params["gate"], params["up"], params["down"])
        return pol.cast_out(x.astype(pol.accum_dtype) + y)
