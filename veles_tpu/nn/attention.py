"""Trainable multi-head self-attention.

The 2015 reference has no attention anywhere (SURVEY.md §5 records it
absent) — this unit is the beyond-reference long-context building
block the TPU build treats as first-class: single-device it runs the
flash-style streaming softmax (:func:`local_attention`), and with a
``seq`` mesh attached the SAME unit computes exact attention over a
sequence sharded across devices via ring attention
(:mod:`veles_tpu.parallel.sequence`) — K/V blocks rotate on ICI while
each chip accumulates its query block. Both paths are pure ``apply``
functions, so the generic vjp GD unit trains them with no bespoke
backward (the ring's scan + ppermute transpose IS the backward ring).

Parameters pack as one ``weights`` tensor (4, dim, dim) — rows are the
Q/K/V/output projections — so every existing mechanism (filler,
snapshots, param-server deltas, solvers) applies unchanged.
"""

import math

import jax
import jax.numpy as jnp

from veles_tpu.nn.base import ForwardBase, NamedParamsForward
from veles_tpu.nn.gd import GradientDescentBase
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.nn.precision import get_policy
from veles_tpu.parallel.sequence import (causal_attention,
                                         local_attention, ring_attention,
                                         ulysses_attention)


class MultiHeadAttentionForward(ForwardBase):
    """Self-attention over (batch, seq, dim) inputs, residual output."""

    hide_from_registry = False

    def __init__(self, workflow, heads=4, causal=True, residual=True,
                 **kwargs):
        super(MultiHeadAttentionForward, self).__init__(workflow,
                                                        **kwargs)
        self.heads = int(heads)
        self.causal = causal
        #: add x to the attention output (the transformer block wiring;
        #: also keeps deep stacks trainable at plain-SGD rates)
        self.residual = residual
        self._seq_mesh_ = None
        self._seq_axis_ = "seq"

    def use_ring(self, mesh, axis="seq", schedule="ring"):
        """Attach a sequence mesh: apply() switches to the sharded
        plan — ``schedule="ring"`` (ppermute streaming-softmax hops) or
        ``"ulysses"`` (two all_to_alls, exact full-sequence attention
        per head slice; needs heads divisible by the axis).

        Runtime configuration (meshes are process-local device handles,
        so this is transient state — reattach after a snapshot resume).
        """
        if schedule not in ("ring", "ulysses"):
            raise ValueError("unknown sp schedule %r" % (schedule,))
        if schedule == "ulysses" and self.heads % mesh.shape[axis]:
            # both operands are known NOW — reject at the call that
            # causes it, not deep into the first forward trace
            raise ValueError(
                "ulysses needs heads (%d) divisible by the %r axis "
                "(%d)" % (self.heads, axis, mesh.shape[axis]))
        self._seq_mesh_ = mesh
        self._seq_axis_ = axis
        self._seq_schedule_ = schedule
        return self

    def init_unpickled(self):
        super(MultiHeadAttentionForward, self).init_unpickled()
        self._seq_mesh_ = None
        self._seq_axis_ = "seq"
        self._seq_schedule_ = "ring"

    def _placement_mesh(self):
        # base place_for_grad/param_values/_input_devmem re-place every
        # committed buffer onto the seq mesh (the ring's shard_map
        # rejects device-set mismatches otherwise)
        return self._seq_mesh_

    def weights_shape_for(self, input_shape):
        dim = input_shape[-1]
        if dim % self.heads:
            raise ValueError("dim %d not divisible by %d heads"
                             % (dim, self.heads))
        return (4, dim, dim)

    def bias_shape_for(self, input_shape):
        return (4, input_shape[-1])

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def apply(self, params, x):
        w = params["weights"]
        b = params.get("bias")
        batch, seq, dim = x.shape
        heads, head_dim = self.heads, dim // self.heads

        def proj(i, t):
            y = jnp.einsum("bsd,de->bse", t, w[i],
                           preferred_element_type=jnp.float32)
            if b is not None:
                y = y + b[i]
            return y

        def split(t):  # (B, S, D) -> (B, H, S, hd)
            return t.reshape(batch, seq, heads, head_dim).transpose(
                0, 2, 1, 3)

        q, k, v = (split(proj(i, x)) for i in range(3))
        if self._seq_mesh_ is not None:
            if self._seq_schedule_ == "ulysses":
                ctx = ulysses_attention(q, k, v, self._seq_mesh_,
                                        self._seq_axis_,
                                        causal=self.causal)
            else:
                ctx = ring_attention(q, k, v, self._seq_mesh_,
                                     self._seq_axis_,
                                     causal=self.causal)
        else:
            ctx = local_attention(q, k, v, causal=self.causal)
        merged = ctx.transpose(0, 2, 1, 3).reshape(batch, seq, dim)
        out = proj(3, merged)
        if self.residual:
            out = out + x
        return out.astype(x.dtype)


class GDAttention(GradientDescentBase):
    """Backward for the attention block: the generic vjp covers it —
    including THROUGH the ring (scan of ppermutes transposes to the
    reverse ring)."""


def rotary(x, theta):
    """Rotary position embedding of ``x`` (batch, seq, heads, dim)
    over all of ``dim``, positions ``0..seq-1``, in float32. Pairing:
    rotate-half, dim ``i`` with ``i + dim/2``."""
    seq, dim = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    x = x.astype(jnp.float32)
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


class LatentAttentionForward(NamedParamsForward):
    """Multi-head latent attention (DeepSeek-V2's MLA) over (batch,
    seq, dim), pre-norm, residual inside: ``x + MLA(rms_norm(x))``.

    Queries and keys/values are each projected DOWN to a latent
    (``q_rank``, ``kv_rank``), normed there, and projected up to heads
    whose sizes are free of ``dim / heads``: a query/key head is
    ``qk_nope_dim`` plain dims and ``qk_rope_dim`` rotary ones, a
    value head ``v_dim``. The rotary key comes straight from the down
    projection, ONE vector for all heads. Training keeps no latent
    cache, but the low-rank products are computed as written, not
    folded into dense matrices. The core is
    :func:`~veles_tpu.parallel.sequence.causal_attention` (causal,
    float32 softmax, memory linear in ``seq``, ``block`` queries at a
    time): the fused flash kernel on a TPU where the shapes fit its
    tiling, XLA's ``blockwise_attention`` otherwise, and the gauge
    ``veles_attention_core_fused{unit}`` says which; either keeps its
    output and row statistics across the unit's rematerialization
    (:mod:`veles_tpu.remat`), so ``remat`` re-runs the projections
    and not the core; ``block=None`` takes the oracle
    :func:`local_attention`, which holds the whole square.

    On the device the projections and norms run under the sub-scope
    ``proj`` and the core under ``core`` of the unit's scope."""

    hide_from_registry = False
    PARAMS = ("norm", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b",
              "o")

    def __init__(self, workflow, heads=4, q_rank=None, kv_rank=None,
                 qk_nope_dim=None, qk_rope_dim=None, v_dim=None,
                 rope_theta=1e4, eps=1e-5, block=512, **kwargs):
        super(LatentAttentionForward, self).__init__(workflow, **kwargs)
        self.heads = int(heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.qk_nope_dim, self.qk_rope_dim = int(qk_nope_dim), \
            int(qk_rope_dim)
        self.v_dim = int(v_dim)
        self.rope_theta, self.eps = float(rope_theta), float(eps)
        self.block = block

    def param_shapes(self, input_shape):
        dim, h = input_shape[-1], self.heads
        qk = self.qk_nope_dim + self.qk_rope_dim
        return {
            "norm": ((dim,), "gain"),
            "q_a": ((dim, self.q_rank), "matrix"),
            "q_norm": ((self.q_rank,), "gain"),
            "q_b": ((self.q_rank, h * qk), "matrix"),
            "kv_a": ((dim, self.kv_rank + self.qk_rope_dim), "matrix"),
            "kv_norm": ((self.kv_rank,), "gain"),
            "kv_b": ((self.kv_rank,
                      h * (self.qk_nope_dim + self.v_dim)), "matrix"),
            "o": ((h * self.v_dim, dim), "matrix"),
        }

    def apply(self, params, x):
        pol = get_policy()
        batch, seq, _ = x.shape
        h, nope, rope, v_dim = self.heads, self.qk_nope_dim, \
            self.qk_rope_dim, self.v_dim

        def dot(a, name):
            a, w = pol.cast_in(a, params[name])
            return jnp.dot(a, w, preferred_element_type=pol.accum_dtype)

        with jax.named_scope("proj"):
            normed = rms_norm(x, params["norm"], self.eps)
            q = dot(rms_norm(dot(normed, "q_a"), params["q_norm"],
                             self.eps), "q_b").reshape(
                batch, seq, h, nope + rope)
            q = jnp.concatenate(
                [q[..., :nope], rotary(q[..., nope:], self.rope_theta)],
                -1)
            kv = dot(normed, "kv_a")
            k_rope = rotary(kv[:, :, None, self.kv_rank:],
                            self.rope_theta)
            kv = dot(rms_norm(kv[..., :self.kv_rank], params["kv_norm"],
                              self.eps), "kv_b").reshape(
                batch, seq, h, nope + v_dim)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_rope, (batch, seq, h, rope))], -1)
            # (B, S, H, D) -> (B, H, S, D), in the compute dtype
            q, k, v = (pol.cast_in(t).transpose(0, 2, 1, 3)
                       for t in (q, k, kv[..., nope:]))
        scale = 1.0 / math.sqrt(nope + rope)
        with jax.named_scope("core"):
            if self.block:
                ctx = causal_attention(q, k, v, scale, int(self.block),
                                       unit=self.name)
            else:
                ctx = local_attention(q, k, v, causal=True, scale=scale)
        with jax.named_scope("proj"):
            out = dot(ctx.transpose(0, 2, 1, 3).reshape(
                batch, seq, h * v_dim), "o")
            return pol.cast_out(x.astype(pol.accum_dtype) + out)
