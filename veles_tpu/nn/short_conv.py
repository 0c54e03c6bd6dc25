"""Gated short convolution: a token mixer that is not attention.

The mixing half of a pre-norm block, with the block's norm and
residual inside the unit (as the attention units carry theirs):
``x + Wout(C * conv(B * X))``, ``[B, C, X] = rms_norm(x) Win``, the
convolution depthwise, causal and a few taps long over the sequence.
A position reads itself and the ``taps - 1`` positions before it and
no other; what lies before position 0 is zero. No bias by default.
"""

import jax
import jax.numpy as jnp

from veles_tpu.nn.base import NamedParamsForward
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.nn.precision import get_policy
from veles_tpu.telemetry.registry import get_registry

#: what ``veles_short_conv_lowering{unit}`` reads: the mix as shifted
#: multiply-adds (0), the one lowering there is; 1 is kept for a
#: grouped ``lax.conv_general_dilated``, should a chip's trace ever
#: show it nearer the floor
SHIFTED = 0.0


def causal_taps(u, w, bias=None):
    """``v[:, t] = sum_j w[:, j] * u[:, t - (taps - 1) + j]`` (+
    ``bias``) over (batch, seq, dim) ``u`` with one filter ``w[c]`` a
    channel, (dim, taps); ``u`` before position 0 is 0. As many
    shifted multiply-adds as there are taps, in ``u``'s dtype:
    elementwise, so XLA fuses them with what stands around them."""
    taps, seq = w.shape[1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    v = sum(w[:, j] * jax.lax.slice_in_dim(padded, j, j + seq, axis=1)
            for j in range(taps))
    return v if bias is None else v + bias


class ShortConvForward(NamedParamsForward):
    """``x + (C * v) out``, ``[B, C, X] = rms_norm(x) in`` (``dim ->
    3 dim``, split in that order), ``v = causal_taps(B * X, taps)``,
    over (batch, seq, dim): a gated short-convolution operator.
    ``taps`` is the filter's length (a position and the ``taps - 1``
    before it), ``bias`` adds one number a channel to the convolution
    (parameter ``taps_bias``, from zero).

    The two products run in the policy's compute dtype with sums in
    its accumulation dtype and hand their result on in the compute
    dtype; both gates and the taps' sum are float32.
    On the device the norm and the two products run under the
    sub-scope ``proj``, the gates and the convolution under ``mix``.
    Traced, the unit sets ``veles_short_conv_taps{unit}`` and
    ``veles_short_conv_lowering{unit}`` (:data:`SHIFTED`)."""

    hide_from_registry = False
    PARAMS = ("norm", "in", "taps", "out")

    def __init__(self, workflow, taps=3, bias=False, eps=1e-5, **kwargs):
        if bias:
            self.PARAMS = self.PARAMS + ("taps_bias",)
        super(ShortConvForward, self).__init__(workflow, **kwargs)
        #: the filter's length (``taps`` itself is the parameter)
        self.n_taps = int(taps)
        if self.n_taps < 1:
            raise ValueError("a convolution of %r taps is none" % (taps,))
        self.eps = float(eps)

    def param_shapes(self, input_shape):
        dim = input_shape[-1]
        shapes = {"norm": ((dim,), "gain"),
                  "in": ((dim, 3 * dim), "matrix"),
                  "taps": ((dim, self.n_taps), "taps"),
                  "out": ((dim, dim), "matrix"),
                  "taps_bias": ((dim,), "zero")}
        return {name: shapes[name] for name in self.PARAMS}

    def _fill(self, mem, kind):
        # a filter's fan-in is its own taps, not the channels: filled
        # as a (taps, dim) matrix
        super(ShortConvForward, self)._fill(
            mem.T if kind == "taps" else mem,
            "matrix" if kind == "taps" else kind)

    def apply(self, params, x):
        pol = get_policy()
        registry = get_registry()
        registry.gauge(
            "veles_short_conv_taps", "Taps of the unit's causal "
            "depthwise convolution over the sequence", labels=("unit",)
        ).labels(unit=self.name).set(float(self.n_taps))
        registry.gauge(
            "veles_short_conv_lowering", "How the unit's convolution "
            "was traced: 0 shifted multiply-adds, 1 a grouped "
            "convolution", labels=("unit",)
        ).labels(unit=self.name).set(SHIFTED)

        def dot(a, name):
            a, w = pol.cast_in(a, params[name])
            return jnp.dot(a, w, preferred_element_type=pol.accum_dtype)

        with jax.named_scope("proj"):
            # the gates leave the product in the compute dtype and are
            # split there, each third widened where it is used: the
            # v5e compiler then writes them to HBM once, in bfloat16
            # (widened before the split, it wrote them in float32)
            gates = pol.cast_in(
                dot(rms_norm(x, params["norm"], self.eps), "in"))
        with jax.named_scope("mix"):
            b, c, xs = (t.astype(jnp.float32)
                        for t in jnp.split(gates, 3, axis=-1))
            mixed = c * causal_taps(b * xs, params["taps"],
                                    params.get("taps_bias"))
        with jax.named_scope("proj"):
            return pol.cast_out(x.astype(pol.accum_dtype)
                                + dot(mixed, "out"))
