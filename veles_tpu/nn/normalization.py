"""Local response normalization (Znicz normalization.py — the AlexNet
cross-channel LRN) as n shifted slices, which XLA fuses into the
surrounding graph; and RMS normalization. The formulations that were
measured against the slices and lost (a fused Pallas pair, a channel
cumsum, a pow specialization) are in docs/PERF.md.
"""

import jax
import jax.numpy as jnp

from veles_tpu.nn.base import ForwardBase
from veles_tpu.nn.precision import get_policy


def lrn(x, k=2.0, alpha=1e-4, beta=0.75, n=5):
    """Cross-channel LRN, channels last: AlexNet's formula, the
    channel-window sum as n shifted slices (generic-reducer
    reduce_window has no autodiff rule)."""
    sq = jnp.square(x)
    half = n // 2
    padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    channels = x.shape[-1]
    window = sum(
        jax.lax.slice_in_dim(padded, i, i + channels, axis=x.ndim - 1)
        for i in range(n))
    return x / jnp.power(k + alpha * window, beta)


class LRNormalizerForward(ForwardBase):
    def __init__(self, workflow, k=2.0, alpha=1e-4, beta=0.75, n=5,
                 **kwargs):
        kwargs.setdefault("include_bias", False)
        super(LRNormalizerForward, self).__init__(workflow, **kwargs)
        self.k, self.alpha, self.beta, self.n = k, alpha, beta, n

    @property
    def has_weights(self):
        return False

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def apply(self, params, x):
        return lrn(x, self.k, self.alpha, self.beta, self.n)


def rms_norm(x, gain, eps=1e-5):
    """``x * rsqrt(mean(x^2) + eps) * gain`` over the last dim, in
    float32 whatever ``x`` comes in."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps=1e-5):
    """``(x - mean) * rsqrt(var + eps) * gain + bias`` over the last
    dim, computed in float32."""
    x = x.astype(jnp.float32)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * gain + bias


class RMSNormForward(ForwardBase):
    """Root-mean-square normalization over the last dim with a learned
    gain (``weights``, one per feature, starting at one); no bias, no
    mean subtraction."""

    def __init__(self, workflow, eps=1e-5, **kwargs):
        kwargs.setdefault("include_bias", False)
        super(RMSNormForward, self).__init__(workflow, **kwargs)
        self.eps = float(eps)

    def weights_shape_for(self, input_shape):
        return (input_shape[-1],)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def fill_weights(self):
        self.weights.mem[...] = 1.0

    def apply(self, params, x):
        return get_policy().cast_out(
            rms_norm(x, params["weights"], self.eps))
