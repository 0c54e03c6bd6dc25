"""Causal attention over a band, with grouped key/value heads: the
repo's own Pallas kernels for a TPU, two of them (forward, backward).

What jaxlib's flash kernels (``parallel/sequence.py``
``fused_attention``) cannot take: ``k`` and ``v`` of fewer heads than
``q`` (query head ``j`` reads key/value head ``j // group``), and a
WINDOW: query ``i`` sees key ``j`` iff ``0 <= i - j < window`` (the
key itself included; ``window=None`` is the causal triangle). Scores
are made a block of queries against a block of keys at a time and
stay in VMEM with the running max and sum, forward and backward;
only block pairs the band touches are run (:func:`geometry`): the
forward grid's innermost axis counts the band's blocks, not the
sequence's, and a step past the band's edge neither runs nor fetches;
the backward grid's innermost axis is the band's block pairs
themselves. A key/value head is read through the index map ``head //
group`` and never repeated in memory.

The backward makes a block pair's scores, probabilities and their
cotangent ONCE and takes all three gradients from them: ``dq`` sums
over the band's steps of a query block in a block of scratch, ``dk``
and ``dv`` over the group's heads and every query block in float32
scratch that holds the key/value head's whole sequence
(:func:`backward_vmem_bytes`), so they come out with the key/value
heads' shape; nothing partial is written to HBM.

Operands are (batch, heads, seq, head_dim), multiplied in the dtype
they come in; scores, statistics and accumulators are float32, the
scale is applied after the product, probabilities are cast to ``v``'s
dtype for the second product, the reciprocal is exact. The forward
returns the output and each row's log-sum-exp. The backward works on
TRANSPOSED scores (keys by queries), so that a query's statistics are
rows and broadcast along sublanes (the layout ``splash_attention``
uses for the same reason).

Runs on a TPU, or anywhere under ``pltpu.force_tpu_interpret_mode()``.
Which shapes are taken is ``parallel/sequence.py`` ``fused_refusal``'s
to say.
"""

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: a masked score: finite, so that a row whose block holds no visible
#: key gives exp(0) garbage, not NaN; the diagonal block comes last
#: in a row's order and its ``exp(MASKED - max)`` wipes that to 0
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
#: contract the last dim of both operands: ``a @ b.T``
NT = (((1,), (1,)), ((), ()))
#: contract the first dim of both: ``a.T @ b``
TN = (((0,), (0,)), ((), ()))
#: VMEM of a v5e TensorCore
VMEM_BYTES = 128 * 2**20
#: VMEM that a kernel's blocks and its compiler's temporaries were seen
#: to fit into at 512 x 512 blocks and heads of 128: the scoped default
BLOCKS_VMEM_BYTES = 16 * 2**20


def _div(a, b):
    """``a // b`` of a non-negative block index, a Python int or a
    traced one."""
    return a // b if isinstance(a, int) else lax.div(a, jnp.int32(b))


def _clip(a, low, high):
    if isinstance(a, int):
        return min(max(a, low), high)
    return jnp.clip(a, low, high)


# The band by blocks. Query block ``qi`` holds rows ``qi * bq ...``, and
# sees the keys from its first row's oldest (``window - 1`` back) to
# its last row's own.
def _kv_first(qi, bq, bk, window):
    if window is None:
        return 0 * qi
    return _div(_clip(qi * bq - (window - 1), 0, qi * bq), bk)


def _kv_last(qi, bq, bk):
    return _div((qi + 1) * bq - 1, bk)


def _band(seq, bq, bk, window):
    """The block pairs ``(qi, ki)`` the band touches, by query block,
    then key block."""
    return [(qi, ki) for qi in range(seq // bq)
            for ki in range(_kv_first(qi, bq, bk, window),
                            _kv_last(qi, bq, bk) + 1)]


def geometry(seq, bq, bk, window=None):
    """``(kv steps a query block, block pairs run)`` of a sequence of
    ``seq`` in blocks of ``bq`` queries and ``bk`` keys: the forward
    grid's innermost size, and what a head and sequence costs, forward
    or backward (the backward grid's innermost size)."""
    band = _band(seq, bq, bk, window)
    return max(collections.Counter(qi for qi, _ in band).values()), \
        len(band)


def _visible(q0, k0, shape, q_axis, window):
    """The mask of a block whose first query is ``q0`` and first key
    ``k0``; queries run along ``q_axis`` of ``shape``."""
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    seen = k_pos <= q_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return seen


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                    acc_scr, *, scale, window, bq, bk, steps):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = _kv_first(qi, bq, bk, window) + j

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, MASKED, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki <= _kv_last(qi, bq, bk))
    def _():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = lax.dot_general(q, k, NT,
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(_visible(qi * bq, ki * bk, s.shape, 0, window), s,
                      MASKED)
        m_prev, l_prev = m_scr[...], l_scr[...]          # (bq, LANES)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _transposed(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, q0, k0,
                scale, window):
    """``(p, ds)`` of one block pair, keys by queries, float32: the
    probabilities from the kept log-sum-exp, and the scores'
    cotangent ``p * (dp - di) * scale``."""
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    s = lax.dot_general(k, q, NT,
                        preferred_element_type=jnp.float32) * scale
    s = jnp.where(_visible(q0, k0, s.shape, 1, window), s, MASKED)
    p = jnp.exp(s - lse_ref[...])                        # lse: (1, bq)
    dp = lax.dot_general(v, do, NT, preferred_element_type=jnp.float32)
    return p, p * (dp - di_ref[...]) * scale


def _backward_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     di_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                     *, scale, window, bq, bk, group, pairs):
    """A block pair's ``p`` and ``ds`` made once for all three
    gradients: ``dq`` sums over the band's consecutive pairs of a
    query block, ``dk`` and ``dv`` over every query block and head of
    the group, in scratch that holds the key/value head's whole
    sequence."""
    g, t = pl.program_id(2), pl.program_id(3)
    qi, ki = qi_ref[t], ki_ref[t]

    @pl.when((g == 0) & (t == 0))
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(ki == _kv_first(qi, bq, bk, window))
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    p, ds = _transposed(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                        qi * bq, ki * bk, scale, window)
    q, k, do = q_ref[...], k_ref[...], do_ref[...]
    rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)
    dv_scr[rows, :] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
    dk_scr[rows, :] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
    dq_scr[...] += lax.dot_general(
        ds.astype(k.dtype), k, TN, preferred_element_type=jnp.float32)

    @pl.when(ki == _kv_last(qi, bq, bk))
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when((g == group - 1) & (t == pairs - 1))
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


#: the last grid axis walks the band and carries the scratch
_ROWS = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "parallel", "arbitrary"))


def _by_query_block(dim, group, bq, bk, window):
    """Block specs of a grid ``(batch, head, query block, step)``: a
    query block's rows and the key/value block of the step, held at
    the band's last block once the steps pass it (a block that does not
    change is not fetched again)."""
    def kv_index(b, h, qi, j):
        ki = jnp.minimum(_kv_first(qi, bq, bk, window) + j,
                         _kv_last(qi, bq, bk))
        return b, h // group, ki, 0

    return (pl.BlockSpec((None, None, bq, dim),
                         lambda b, h, qi, j: (b, h, qi, 0)),
            pl.BlockSpec((None, None, bk, dim), kv_index))


def forward(q, k, v, scale, bq, bk, window=None):
    """``(o, lse)``: the output, (batch, heads, seq, head_dim) in
    ``q``'s dtype, and each row's log-sum-exp, (batch, heads, seq)
    float32."""
    batch, heads, seq, dim = q.shape
    steps, _ = geometry(seq, bq, bk, window)
    q_spec, kv_spec = _by_query_block(dim, heads // k.shape[1], bq, bk,
                                      window)
    o, lse = pl.pallas_call(
        functools.partial(_forward_kernel, scale=scale, window=window,
                          bq=bq, bk=bk, steps=steps),
        grid=(batch, heads, seq // bq, steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        # the statistic leaves the kernel 128 lanes wide, as it is held
        out_specs=[q_spec, pl.BlockSpec(
            (None, None, bq, LANES), lambda b, h, qi, j: (b, h, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((batch, heads, seq, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, dim), jnp.float32)],
        compiler_params=_ROWS, name="band_attention_forward",
    )(q, k, v)
    return o, lse[..., 0]


def backward_vmem_bytes(seq, dim, dtype):
    """VMEM the backward kernel asks for at a sequence of ``seq`` and
    heads of ``dim`` in ``dtype``: its float32 ``dk`` and ``dv``
    scratch over the whole sequence, the two buffers of each of the
    two outputs, and :data:`BLOCKS_VMEM_BYTES` for the rest."""
    return 2 * seq * dim * (4 + 2 * jnp.dtype(dtype).itemsize) \
        + BLOCKS_VMEM_BYTES


def backward(q, k, v, o, lse, d_out, scale, bq, bk, window=None):
    """``(dq, dk, dv)``; ``dk`` and ``dv`` in the key/value heads'
    shape, summed over each group's query heads. One kernel over the
    grid ``(batch, key/value head, head of the group, block pair)``:
    the last axis runs the band's block pairs and no others, by query
    block, then key block, from two tables the grid reads ahead
    (a step past the band's edge would cost a step's overhead)."""
    batch, heads, seq, dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    # rowsum(dO * O), the softmax backward's correction; a query's
    # statistics as rows: (batch, heads, 1, seq)
    di = jnp.sum(d_out.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    lse = lse[:, :, None, :]

    # the grid's last axis runs the band's pairs and no others
    band = _band(seq, bq, bk, window)
    qi_of, ki_of = (jnp.asarray([pair[i] for pair in band], jnp.int32)
                    for i in (0, 1))
    q_rows = pl.BlockSpec((None, None, bq, dim), lambda b, kvh, g, t, qs, ks: (
        b, kvh * group + g, qs[t], 0))
    q_stats = pl.BlockSpec((None, None, 1, bq), lambda b, kvh, g, t, qs, ks: (
        b, kvh * group + g, 0, qs[t]))
    kv_rows = pl.BlockSpec((None, None, bk, dim),
                           lambda b, kvh, g, t, qs, ks: (b, kvh, ks[t], 0))
    # dk, dv: the key/value head's whole sequence, written once
    kv_seq = pl.BlockSpec((None, None, seq, dim),
                          lambda b, kvh, g, t, qs, ks: (b, kvh, 0, 0))
    return pl.pallas_call(
        functools.partial(_backward_kernel, scale=scale, window=window,
                          bq=bq, bk=bk, group=group, pairs=len(band)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, kv_heads, group, len(band)),
            in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_stats, q_stats],
            out_specs=[q_rows, kv_seq, kv_seq],
            scratch_shapes=[pltpu.VMEM((bq, dim), jnp.float32),
                            pltpu.VMEM((seq, dim), jnp.float32),
                            pltpu.VMEM((seq, dim), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=backward_vmem_bytes(seq, dim, k.dtype)),
        name="band_attention_backward",
    )(qi_of, ki_of, q, k, v, d_out, lse, di)
