"""Causal attention over a band, with grouped key/value heads: the
repo's own Pallas kernels for a TPU (forward, dk/dv, dq).

What jaxlib's flash kernels (``parallel/sequence.py``
``fused_attention``) cannot take: ``k`` and ``v`` of fewer heads than
``q`` (query head ``j`` reads key/value head ``j // group``), and a
WINDOW: query ``i`` sees key ``j`` iff ``0 <= i - j < window`` (the
key itself included; ``window=None`` is the causal triangle). Scores
are made a block of queries against a block of keys at a time and
stay in VMEM with the running max and sum, forward and backward;
only block pairs the band touches are run (:func:`geometry`): the
grid's innermost axis counts the band's blocks, not the sequence's,
and a step past the band's edge neither runs nor fetches. A key/value
head is read through the index map ``head // group`` and never
repeated in memory; the dk/dv kernel sums over the group's heads in
its scratch, so ``dk``, ``dv`` come out with the key/value heads'
shape.

Operands are (batch, heads, seq, head_dim), multiplied in the dtype
they come in; scores, statistics and accumulators are float32, the
scale is applied after the product, probabilities are cast to ``v``'s
dtype for the second product, the reciprocal is exact. The forward
returns the output and each row's log-sum-exp. The backward kernels
work on TRANSPOSED scores (keys by queries), so that a query's
statistics are rows and broadcast along sublanes (the layout
``splash_attention`` uses for the same reason).

Runs on a TPU, or anywhere under ``pltpu.force_tpu_interpret_mode()``.
Which shapes are taken is ``parallel/sequence.py`` ``fused_refusal``'s
to say.
"""

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
# its last row's own; key block ``ki`` is seen by the queries from its
# first key's own row to its last key's youngest (``window - 1`` on).
def _kv_first(qi, bq, bk, window):
    if window is None:
        return 0 * qi
    return _div(_clip(qi * bq - (window - 1), 0, qi * bq), bk)


def _kv_last(qi, bq, bk):
    return _div((qi + 1) * bq - 1, bk)


def _q_first(ki, bq, bk):
    return _div(ki * bk, bq)


def _q_last(ki, bq, bk, window, nq):
    if window is None:
        return 0 * ki + (nq - 1)
    return _clip(_div((ki + 1) * bk - 1 + window - 1, bq), 0, nq - 1)


def geometry(seq, bq, bk, window=None):
    """``(kv steps a query block, query steps a key block, block pairs
    run)`` of a sequence of ``seq`` in blocks of ``bq`` queries and
    ``bk`` keys: the two grids' innermost sizes and what a head and
    sequence costs, forward or in either backward kernel."""
    nq, nk = seq // bq, seq // bk
    per_q = [_kv_last(qi, bq, bk) - _kv_first(qi, bq, bk, window) + 1
             for qi in range(nq)]
    per_k = [_q_last(ki, bq, bk, window, nq) - _q_first(ki, bq, bk) + 1
             for ki in range(nk)]
    return max(per_q), max(per_k), sum(per_q)


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


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, window, bq, bk, steps,
                group, nq):
    ki, g, j = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    qi = _q_first(ki, bq, bk) + j

    @pl.when((g == 0) & (j == 0))
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(qi <= _q_last(ki, bq, bk, window, nq))
    def _():
        p, ds = _transposed(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                            qi * bq, ki * bk, scale, window)
        do, q = do_ref[...], q_ref[...]
        dv_scr[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dk_scr[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    @pl.when((g == group - 1) & (j == steps - 1))
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_scr, *, scale, window, bq, bk, steps):
    qi, j = pl.program_id(2), pl.program_id(3)
    ki = _kv_first(qi, bq, bk, window) + j

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(ki <= _kv_last(qi, bq, bk))
    def _():
        _, ds = _transposed(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                            qi * bq, ki * bk, scale, window)
        k = k_ref[...]
        dq_scr[...] += lax.dot_general(
            ds.astype(k.dtype), k, TN, preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


#: the last grid axis walks the band and carries the scratch
_ROWS = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "parallel", "arbitrary"))


def _by_query_block(dim, group, bq, bk, window):
    """Block specs of a grid ``(batch, head, query block, step)``: a
    query block's rows, its statistics as a row, and the key/value
    block of the step, held at the band's last block once the steps
    pass it (a block that does not change is not fetched again)."""
    def kv_index(b, h, qi, j):
        ki = jnp.minimum(_kv_first(qi, bq, bk, window) + j,
                         _kv_last(qi, bq, bk))
        return b, h // group, ki, 0

    return (pl.BlockSpec((None, None, bq, dim),
                         lambda b, h, qi, j: (b, h, qi, 0)),
            pl.BlockSpec((None, None, 1, bq),
                         lambda b, h, qi, j: (b, h, 0, qi)),
            pl.BlockSpec((None, None, bk, dim), kv_index))


def forward(q, k, v, scale, bq, bk, window=None):
    """``(o, lse)``: the output, (batch, heads, seq, head_dim) in
    ``q``'s dtype, and each row's log-sum-exp, (batch, heads, seq)
    float32."""
    batch, heads, seq, dim = q.shape
    steps, _, _ = geometry(seq, bq, bk, window)
    q_spec, _, kv_spec = _by_query_block(dim, heads // k.shape[1], bq, bk,
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


def backward(q, k, v, o, lse, d_out, scale, bq, bk, window=None):
    """``(dq, dk, dv)``; ``dk`` and ``dv`` in the key/value heads'
    shape, summed over each group's query heads."""
    batch, heads, seq, dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    nq, nk = seq // bq, seq // bk
    k_steps, q_steps, _ = geometry(seq, bq, bk, window)
    # rowsum(dO * O), the softmax backward's correction; a query's
    # statistics as rows: (batch, heads, 1, seq)
    di = jnp.sum(d_out.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    lse = lse[:, :, None, :]

    # grid (batch, key/value head, key block, head of the group, step):
    # the query block of the step, held at the band's last
    def q_block(ki, j):
        return jnp.minimum(_q_first(ki, bq, bk) + j,
                           _q_last(ki, bq, bk, window, nq))

    q_rows = pl.BlockSpec(
        (None, None, bq, dim), lambda b, kvh, ki, g, j: (
            b, kvh * group + g, q_block(ki, j), 0))
    q_stats = pl.BlockSpec(
        (None, None, 1, bq), lambda b, kvh, ki, g, j: (
            b, kvh * group + g, 0, q_block(ki, j)))
    kv_rows = pl.BlockSpec((None, None, bk, dim),
                           lambda b, kvh, ki, g, j: (b, kvh, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, window=window, bq=bq,
                          bk=bk, steps=q_steps, group=group, nq=nq),
        grid=(batch, kv_heads, nk, group, q_steps),
        in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_stats, q_stats],
        out_specs=[kv_rows, kv_rows],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dim), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        name="band_attention_dkv",
    )(q, k, v, d_out, lse, di)

    q_spec, row_spec, kv_spec = _by_query_block(dim, group, bq, bk, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, window=window, bq=bq,
                          bk=bk, steps=k_steps),
        grid=(batch, heads, nq, k_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dim), jnp.float32)],
        compiler_params=_ROWS, name="band_attention_dq",
    )(q, k, v, d_out, lse, di)
    return dq, dk, dv
