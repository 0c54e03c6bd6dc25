"""Trainable self-attention units. The 2015 reference has no attention
anywhere (SURVEY.md §5 records it absent); three units here:

* :class:`MultiHeadAttentionForward` (``attention``): plain multi-head
  attention, the long-context building block of the sequence-parallel
  tier. On one device it runs :func:`local_attention`; with a ``seq``
  mesh attached the SAME unit computes exact attention over a
  sequence sharded across devices, by ring attention (K/V blocks
  rotate on ICI while each chip accumulates its query block) or the
  Ulysses all-to-all schedule (:mod:`veles_tpu.parallel.sequence`).
  Its parameters pack as one ``weights`` tensor (4, dim, dim), rows
  the Q/K/V/output projections, so filler, snapshots, param-server
  deltas and solvers apply unchanged.
* :class:`LatentAttentionForward` (``latent_attention``, PR 27):
  DeepSeek-V2's multi-head latent attention, a token model's block
  half; named parameters, pre-norm, residual inside.
* :class:`GroupedAttentionForward` (``grouped_attention``, PR 31):
  grouped-query attention with a head size free of ``dim / heads``,
  an optional window, rotary embedding over a fraction of a head from
  a plain or a YaRN table (:func:`rotary`, :func:`rotary_frequencies`)
  and a per-head sigmoid gate on the core's output; since PR 33 also
  a per-head RMSNorm on queries and keys (``qk_norm``) and a learned
  selection of the keys a query attends to (``index``: DeepSeek-V3.2's
  sparse-attention indexer and its KL objective).

All are pure ``apply`` functions, so the generic vjp GD unit trains
them with no bespoke backward. The two token units' cores go through
:func:`veles_tpu.parallel.sequence.causal_attention`, which picks the
kernel from the platform and the shapes.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.nn.base import ForwardBase, NamedParamsForward
from veles_tpu.nn.gd import GradientDescentBase
from veles_tpu.nn.normalization import layer_norm, rms_norm
from veles_tpu.nn.precision import get_policy
from veles_tpu.parallel.sequence import (causal_attention,
                                         local_attention, ring_attention,
                                         selection_mask,
                                         ulysses_attention)
from veles_tpu.telemetry.registry import get_registry


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


def rotary_frequencies(dim, theta, yarn=None):
    """``(frequencies, magnitude)`` of a rotary table over ``dim``
    dims: ``dim / 2`` float32 angles a position, and what cos and sin
    are multiplied by. Plain: ``theta ** (-2i / dim)`` and 1. With
    ``yarn`` (arXiv:2309.00071, as the public ``rope_type: yarn``
    initialisation computes it; keys ``factor``,
    ``original_positions``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``): a dim that turns more than ``beta_fast``
    times over the original context keeps its frequency, one that
    turns less than ``beta_slow`` times has it divided by ``factor``,
    a linear ramp over the dims between; the magnitude is
    ``attention_factor``. Constants of the trace: no parameter, no
    step cost."""
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not yarn:
        return plain, 1.0

    def correction(turns):
        return dim * math.log(yarn["original_positions"]
                              / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(correction(yarn["beta_fast"])), 0), dim - 1)
    high = min(max(math.ceil(correction(yarn["beta_slow"])), 0), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp), \
        float(yarn.get("attention_factor", 1.0))


def rotary(x, theta, fraction=1.0, yarn=None):
    """Rotary position embedding of ``x`` (batch, seq, heads, dim),
    positions ``0..seq-1``, in float32, over the first ``fraction *
    dim`` dims of every head (all of them by default; the rest pass
    unrotated), from the table :func:`rotary_frequencies` gives for
    ``theta`` and, where given, YaRN's keys. Pairing: rotate-half, dim
    ``i`` of the rotated part with ``i + half`` of it."""
    seq, dim = x.shape[1], int(round(x.shape[-1] * fraction))
    freqs, magnitude = rotary_frequencies(dim, theta, yarn)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    x = x.astype(jnp.float32)
    x, rest = x[..., :dim], x[..., dim:]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    out = x * cos + rotated * sin
    return jnp.concatenate([out, rest], -1) if rest.shape[-1] else out


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


class GroupedAttentionForward(NamedParamsForward):
    """Grouped-query attention over (batch, seq, dim), pre-norm,
    residual inside: ``x + Wo(gate * Attn(rms_norm(x)))``.

    ``heads`` query heads and ``kv_heads`` key/value heads of
    ``head_dim`` each, free of ``dim / heads``; query head ``j`` reads
    key/value head ``j // (heads / kv_heads)``. Rotary embedding on the
    first ``rotary_fraction`` of every query and key head, from
    ``rope_theta`` or, with ``yarn`` (a dict: :func:`rotary_frequencies`),
    a YaRN table; ``rope_theta=None`` or ``rotary_fraction=0`` rotates
    nothing (a NoPE layer: no position enters but through the causal
    mask). ``window``: query ``i`` sees key ``j`` iff ``0 <= i -
    j < window`` (the key itself included); None is a full causal
    layer. ``gated`` (per head): the core's output of head ``h`` is
    multiplied by ``sigmoid(n w_h)``, one scalar a head and token from
    a (dim, heads) matrix over the same normed input ``n``, before the
    output projection (arXiv:2505.06708's head-wise gate after the
    core). No bias. ``qk_norm``: an RMSNorm over each query and key
    head before the rotary embedding, one gain of ``head_dim`` numbers
    for the queries and one for the keys, shared by the heads.

    ``index`` (a dict ``heads``, ``head_dim``, ``top_k``): the keys a
    query attends to are SELECTED by an index of its own
    (arXiv:2512.02556's lightning indexer, here over grouped heads),
    which reads ``m = stop_gradient(n)``: index
    queries ``a_i = rope(m Wiq)_i`` in ``heads`` heads of ``head_dim``,
    ONE index key head ``b = rope(layer_norm(m Wik))``, weights ``c =
    m Wiw / sqrt(heads * head_dim)``; ``I[t, s] = sum_i c[t, i]
    relu(a[t, i] . b[s])``; query ``t`` attends to the ``min(t + 1,
    top_k)`` keys ``s <= t`` of largest ``I[t, s]``, the lower ``s`` on
    a tie, and to no others. In a train step the unit hands the step
    one more term of the objective (:meth:`apply_step`; the stat that
    :attr:`OBJECTIVE_STAT` names): ``L_I``, the mean over the positions
    of ``KL(p_t || softmax over the selected keys of I[t])``, ``p_t`` the
    mean over the query heads of the core's probabilities, a constant.
    ``L_I`` moves ``index_q``, ``index_k``, ``index_w``,
    ``index_norm_gain`` and ``index_norm_bias`` and nothing else; the
    model's loss moves none of those five. The rotary table of the
    index is the layer's, over the whole index head. The index's
    products run in the policy's compute dtype with float32 sums;
    scores, selection and ``L_I`` are float32.

    The core is :func:`~veles_tpu.parallel.sequence.causal_attention`
    with the window, the grouping and the index's operands as
    shape-like arguments: on a TPU the repo's banded Pallas kernels,
    which hold no repeated key or value and run only the block pairs
    the band touches (where there is an index, the same family's
    kernels, which mask each block pair's scores with the selection in
    VMEM), XLA's ``blockwise_attention`` elsewhere; each
    keeps its output and row statistics across the unit's
    rematerialization. ``block=None`` takes the oracle
    :func:`local_attention` on repeated heads under an explicit mask,
    which holds the whole square (no index there).

    On the device the projections, norm and rotary embedding run under
    the sub-scope ``proj``, the core under ``core``, the gate under
    ``gate`` of the unit's scope; the index's three products, its norm,
    rotary embedding and scores under ``index``, the search for the
    selected keys under ``select``, the head-mean of the probabilities
    and the KL under ``index_loss``."""

    hide_from_registry = False
    PARAMS = ("norm", "q", "k", "v", "gate", "o")
    #: what ``qk_norm`` and ``index`` add
    QK_NORM = ("q_norm", "k_norm")
    INDEX = ("index_q", "index_k", "index_w", "index_norm_gain",
             "index_norm_bias")
    #: the stat of a train step that is a term of its objective
    OBJECTIVE_STAT = "index_loss"

    def __init__(self, workflow, heads=4, kv_heads=None, head_dim=None,
                 window=None, rope_theta=1e4, rotary_fraction=1.0,
                 yarn=None, gated=True, eps=1e-6, block=512,
                 qk_norm=False, index=None, **kwargs):
        self.PARAMS = tuple(p for p in self.PARAMS
                            if gated or p != "gate") \
            + (self.QK_NORM if qk_norm else ()) \
            + (self.INDEX if index else ())
        super(GroupedAttentionForward, self).__init__(workflow, **kwargs)
        self.heads = int(heads)
        self.kv_heads = int(kv_heads or heads)
        if self.heads % self.kv_heads:
            raise ValueError("%d query heads are no whole groups over "
                             "%d key/value heads"
                             % (self.heads, self.kv_heads))
        self.head_dim = int(head_dim)
        self.window = None if window is None else int(window)
        self.rope_theta = None if rope_theta is None else float(rope_theta)
        self.rotary_fraction = float(rotary_fraction)
        self.yarn = dict(yarn) if yarn else None
        self.gated, self.eps, self.block = bool(gated), float(eps), block
        self.qk_norm = bool(qk_norm)
        self.index = dict(index) if index else None
        if self.index and not block:
            raise ValueError("a selection of keys needs a block size: "
                             "the oracle core takes no index")
        if self.index and self.rope_theta is None:
            raise ValueError("the index rotates its heads by the "
                             "layer's table: give a rope_theta")

    @property
    def rotary_dims(self):
        """Dims of a query or key head that the rotary embedding turns."""
        return 0 if self.rope_theta is None else int(
            round(self.head_dim * self.rotary_fraction))

    def param_shapes(self, input_shape):
        dim, d = input_shape[-1], self.head_dim
        shapes = {
            "norm": ((dim,), "gain"),
            "q": ((dim, self.heads * d), "matrix"),
            "k": ((dim, self.kv_heads * d), "matrix"),
            "v": ((dim, self.kv_heads * d), "matrix"),
            "gate": ((dim, self.heads), "matrix"),
            "o": ((self.heads * d, dim), "matrix"),
            "q_norm": ((d,), "gain"),
            "k_norm": ((d,), "gain"),
        }
        if self.index:
            hi, di = self.index["heads"], self.index["head_dim"]
            shapes.update({
                "index_q": ((dim, hi * di), "matrix"),
                "index_k": ((dim, di), "matrix"),
                "index_w": ((dim, hi), "matrix"),
                "index_norm_gain": ((di,), "gain"),
                "index_norm_bias": ((di,), "zero"),
            })
        return {name: shapes[name] for name in self.PARAMS}

    def _index_operands(self, pol, params, normed):
        """``(a, b, c)`` of :func:`~veles_tpu.parallel.sequence.
        index_scores` from the layer's normed input, which the index
        reads as a constant."""
        batch, seq, _ = normed.shape
        hi, di = self.index["heads"], self.index["head_dim"]
        m = jax.lax.stop_gradient(normed)

        def dot(name):
            x, w = pol.cast_in(m, params[name])
            return jnp.dot(x, w, preferred_element_type=pol.accum_dtype)

        a = rotary(dot("index_q").reshape(batch, seq, hi, di),
                   self.rope_theta, 1.0, self.yarn)
        key = layer_norm(dot("index_k"), params["index_norm_gain"],
                         params["index_norm_bias"], self.eps)
        b = rotary(key[:, :, None, :], self.rope_theta, 1.0, self.yarn)
        c = dot("index_w").astype(jnp.float32) / math.sqrt(hi * di)
        return (pol.cast_in(a).transpose(0, 2, 1, 3),
                pol.cast_in(b[:, :, 0, :]), c.transpose(0, 2, 1))

    def _attend(self, params, x, with_loss):
        """``(output, L_I (batch,) or None, (keys selected, the sum of
        their positions), each (batch, seq), or None)``."""
        pol = get_policy()
        batch, seq, _ = x.shape
        d = self.head_dim
        get_registry().gauge(
            "veles_attention_rotary_dims", "Dims of a query or key head "
            "that the unit's rotary embedding turns; 0 in a layer "
            "without position encoding (NoPE)", labels=("unit",)).labels(
            unit=self.name).set(float(self.rotary_dims))

        def dot(a, name):
            a, w = pol.cast_in(a, params[name])
            return jnp.dot(a, w, preferred_element_type=pol.accum_dtype)

        def heads_of(t, n, rotate, gain=None):
            t = t.reshape(batch, seq, n, d)
            if gain is not None:
                t = rms_norm(t, gain, self.eps)
            if rotate and self.rotary_dims:
                t = rotary(t, self.rope_theta, self.rotary_fraction,
                           self.yarn)
            # (B, S, H, D) -> (B, H, S, D), in the compute dtype
            return pol.cast_in(t).transpose(0, 2, 1, 3)

        with jax.named_scope("proj"):
            normed = rms_norm(x, params["norm"], self.eps)
            q = heads_of(dot(normed, "q"), self.heads, True,
                         params.get("q_norm"))
            k = heads_of(dot(normed, "k"), self.kv_heads, True,
                         params.get("k_norm"))
            v = heads_of(dot(normed, "v"), self.kv_heads, False)
        scale = 1.0 / math.sqrt(d)
        loss = chosen = None
        if self.index:
            with jax.named_scope("index"):
                index = self._index_operands(pol, params, normed)
            # the selected core names its own parts: index, select,
            # core, index_loss
            ctx, loss, *chosen = causal_attention(
                q, k, v, scale, int(self.block), unit=self.name,
                index=index, top_k=self.index["top_k"],
                index_loss=with_loss)
            loss = loss / seq if with_loss else None
        else:
            with jax.named_scope("core"):
                if self.block:
                    ctx = causal_attention(
                        q, k, v, scale, int(self.block), unit=self.name,
                        window=self.window)
                else:
                    k, v = (jnp.repeat(t, self.heads // self.kv_heads,
                                       axis=1) for t in (k, v))
                    ctx = local_attention(q, k, v, causal=True,
                                          scale=scale, window=self.window)
        ctx = ctx.transpose(0, 2, 1, 3)
        if self.gated:
            with jax.named_scope("gate"):
                ctx = ctx * jax.nn.sigmoid(dot(normed, "gate"))[..., None]
        with jax.named_scope("proj"):
            out = dot(ctx.reshape(batch, seq, self.heads * d), "o")
            return pol.cast_out(x.astype(pol.accum_dtype) + out), loss, \
                chosen

    def apply(self, params, x):
        return self._attend(params, x, False)[0]

    def selection(self, params, x):
        """The keys every query of ``x`` selects, bool (batch, seq,
        seq), by the unit's own index and search: what a test or a
        reference compares; the core never holds it whole."""
        pol = get_policy()
        return selection_mask(
            *self._index_operands(pol, params, rms_norm(
                x, params["norm"], self.eps)),
            int(self.block), self.index["top_k"])

    def apply_step(self, params, x, ctx):
        """What a fused step calls: ``(y, stats)``. A unit with an
        index reports the keys each query selected and the sum of
        their positions (which keys, as far as a step can say without
        a mask), and one that trains ``index_loss``: ``L_I`` as a mean
        over the positions and over the whole padded batch (the scale
        of the model's loss), which the step adds to its objective."""
        y, loss, chosen = self._attend(params, x,
                                       bool(self.index) and ctx.train)
        if not self.index:
            return y, {}
        stats = {"selected": chosen[0], "selected_places": chosen[1]}
        if loss is not None:
            rows = jnp.ones_like(loss) if ctx.valid is None \
                else ctx.valid.astype(loss.dtype)
            stats[self.OBJECTIVE_STAT] = \
                jnp.sum(loss * rows) / loss.shape[0]
        return y, stats

    def publish_stats(self, registry, tag, stats, params):
        """Of a train sweep's steps: the term ``L_I`` and the
        query-key pairs a sequence really selected, a head (means over
        the sweep)."""
        if "index_loss" in stats:
            registry.gauge(
                "veles_index_loss", "The index's objective L_I of the "
                "unit, mean over the last train sweep", labels=("unit",)
            ).labels(unit=tag).set(float(jnp.mean(stats["index_loss"])))
        if "selected" in stats:
            counts = numpy.asarray(stats["selected"], numpy.float64)
            registry.gauge(
                "veles_attention_selected_per_step", "Query-key pairs "
                "a head and sequence that the unit's index selected, "
                "counted on the device, mean over the last train sweep",
                labels=("unit",)).labels(unit=tag).set(
                float(counts.sum(axis=-1).mean()))
