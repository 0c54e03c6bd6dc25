"""Local response normalization (Znicz normalization.py — the AlexNet
cross-channel LRN).

Two formulations:

* **XLA slices** (the default): n shifted slices — n is tiny, XLA
  fuses them into the surrounding graph, and the generic vjp applies.
* **fused Pallas forward+backward** (:mod:`veles_tpu.ops.lrn`,
  ``VELES_LRN=pallas``): window sums as a banded matmul on the MXU,
  the vjp's only residual is ``x`` (denominator recomputed in VMEM).
  Kept as a measured NEGATIVE result: parity in isolation, −22%
  end-to-end because the opaque kernel blocks fusion (docs/PERF.md).
"""

import jax
import jax.numpy as jnp

from veles_tpu.nn.base import ForwardBase
from veles_tpu.nn.precision import get_policy


def _lrn_slices(x, k=2.0, alpha=1e-4, beta=0.75, n=5):
    """XLA formulation: the channel-window sum as n shifted slices
    (generic-reducer reduce_window has no autodiff rule)."""
    sq = jnp.square(x)
    half = n // 2
    padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    channels = x.shape[-1]
    window = sum(
        jax.lax.slice_in_dim(padded, i, i + channels, axis=x.ndim - 1)
        for i in range(n))
    # plain pow: a beta=0.75 rsqrt(s)*sqrt(rsqrt(s)) specialization was
    # measured r4 at 12.69 vs 12.35 ms/step — the transcendental is NOT
    # the LRN cost (docs/PERF.md: the floor is structural traffic)
    return x / jnp.power(k + alpha * window, beta)


def lrn(x, k=2.0, alpha=1e-4, beta=0.75, n=5):
    """Cross-channel LRN over NHWC: AlexNet formula.

    The default stays on the XLA slices formulation EVERYWHERE — a
    measured decision, not a shortcut: the Pallas custom_vjp pair
    (:mod:`veles_tpu.ops.lrn`) reaches parity on isolated shapes but
    LOSES 22% end-to-end in the AlexNet fused step (9,660 -> 7,526
    samples/s, docs/PERF.md r3 ablation), because an opaque kernel cuts
    the fusion graph XLA otherwise builds around the LRN. Set
    ``VELES_LRN=pallas`` to re-run that ablation — the kernels' row
    blocking is now shape-tuned through the autotune cache
    (``lrn_fwd``/``lrn_bwd`` entries), so re-runs of the ablation pick
    each shape's measured best block instead of the fixed 512."""
    from veles_tpu.envknob import env_knob
    force = env_knob("VELES_LRN", "xla")
    on_tpu = jax.default_backend() == "tpu"
    if x.ndim == 4 and n % 2 == 1 and force == "pallas":
        from veles_tpu.ops.lrn import lrn_fused
        return lrn_fused(x, k, alpha, beta, n, interpret=not on_tpu)
    if force == "cumsum" and n % 2 == 1 and x.shape[-1] > n // 2:
        # same odd-n guard as the Pallas branch (even n is an
        # asymmetric window the symmetric cumsum form cannot express);
        # tiny channel counts fall back too
        return _lrn_cumsum(x, k, alpha, beta, n)
    return _lrn_slices(x, k, alpha, beta, n)


def _lrn_cumsum(x, k=2.0, alpha=1e-4, beta=0.75, n=5):
    """Prefix-sum formulation: window = cs[c+half] - cs[c-half-1] — one
    channel cumsum + a subtract instead of n shifted adds (backward is
    a reverse cumsum). Float rounding differs from the slices form by
    association only (1e-7 measured).

    Kept as the THIRD measured negative result for the LRN floor
    (``VELES_LRN=cumsum`` to re-run): 16.43 vs 12.35 ms/step on the
    staged AlexNet — a cumsum over the minor (lane) axis is a
    sequential scan on TPU, far worse than n fusable shifted adds.
    With Pallas fusion (−22%) and the pow specialization (flat) also
    ruled out, the slices form stands as measured-best (docs/PERF.md).
    """
    sq = jnp.square(x)
    cs = jnp.cumsum(sq, axis=-1)
    half = n // 2
    channels = x.shape[-1]
    if channels <= half:
        raise ValueError(
            "cumsum LRN needs channels (%d) > n//2 (%d) — the "
            "dispatcher falls back to slices below that" %
            (channels, half))
    upper = jnp.concatenate(
        [cs[..., half:],
         jnp.broadcast_to(cs[..., -1:], cs.shape[:-1] + (half,))], -1)
    lower = jnp.concatenate(
        [jnp.zeros_like(cs[..., :half + 1]),
         cs[..., :channels - half - 1]], -1)
    return x / jnp.power(k + alpha * (upper - lower), beta)


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
