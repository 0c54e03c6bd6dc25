"""Causal attention under a learned selection of keys, with grouped
key/value heads: the repo's own Pallas kernels for a TPU, three of
them (forward, probabilities, backward), of :mod:`band_attention`'s
family.

The selection is data: a byte a query-key pair, 1 where the query
attends to the key (``parallel/sequence.py`` ``select_keys``, which
keeps every pair causal). A kernel reads it a block pair at a time,
as a tile beside the pair's keys, and masks the pair's scores with it
in VMEM; every block pair of the causal triangle is run, the mask does
not skip one. Query head ``j`` reads key/value head ``j // group``
through the index maps; a key/value head's ``group`` query heads run
on consecutive grid steps of one block pair, so that its keys, values
and mask tile are fetched once for the group.

* :func:`block_forward`: one block of queries against the keys up to
  its end: the output and each row's log-sum-exp (the running max and
  sum in VMEM, as :func:`band_attention.forward`).
* :func:`block_probabilities`: the same block's probabilities under
  each head's FINAL log-sum-exp, summed over all the heads, float32:
  what the index's objective compares its own distribution with (its
  value, in the forward pass). Every head of the block sits in VMEM;
  the sum of a block pair stays in VMEM over the heads and is written
  once.
* :func:`backward`: ``dq``, ``dk`` and ``dv`` of the whole triangle
  from one recomputation of each block pair's scores, probabilities
  and their cotangent (:func:`band_attention.backward`'s scheme: ``dk``
  and ``dv`` in float32 scratch of the key/value head's whole
  sequence, ``dq`` of a query block's group in scratch); and from the
  same probabilities their sum over the heads, which the objective's
  gradient needs, so that no second pass makes them.

Products run in the operands' dtype; scores, statistics and sums are
float32, the scale applied after the product, probabilities cast to
``v``'s dtype for their products. The probabilities and the backward
work on TRANSPOSED scores (keys by queries), so that a query's
statistics are rows; their mask tiles come transposed too
(:func:`transposed_tiles`).

Runs on a TPU, or anywhere under ``pltpu.force_tpu_interpret_mode()``.
Which shapes are taken is ``parallel/sequence.py``
``selected_refusal``'s to say.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.band_attention import (BLOCKS_VMEM_BYTES, LANES, MASKED,
                                          NT, TN, _band)


def transposed_tiles(selection):
    """A block of queries' selection, bool (B, bq, stop), as the tiles
    the kernels read: int8 (B, stop // bq, bq keys, bq queries), one a
    key block, keys by queries. The tiles of consecutive query blocks,
    concatenated along axis 1, are the causal triangle's, by query
    block, then key block (:func:`band_attention._band`'s order)."""
    batch, rows, stop = selection.shape
    return selection.reshape(batch, rows, stop // rows, rows).transpose(
        0, 2, 3, 1).astype(jnp.int8)


def _forward_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr,
                    l_scr, acc_scr, *, scale, steps):
    j, g = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[g] = jnp.full(m_scr.shape[1:], MASKED, jnp.float32)
        l_scr[g] = jnp.zeros(l_scr.shape[1:], jnp.float32)
        acc_scr[g] = jnp.zeros(acc_scr.shape[1:], jnp.float32)

    q, k, v = q_ref[g], k_ref[...], v_ref[...]
    s = lax.dot_general(q, k, NT, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_ref[...] != 0, s, MASKED)
    # a row none of whose keys so far is selected holds exp(0) garbage,
    # which its first selected key's exp(MASKED - max) wipes to 0; every
    # row selects a key
    m_prev, l_prev = m_scr[g], l_scr[g]                  # (bq, LANES)
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])
    l_scr[g] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[g] = m_next
    acc_scr[g] = alpha[:, :1] * acc_scr[g] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _():
        l = l_scr[g]
        o_ref[g] = (acc_scr[g] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[g] = m_scr[g] + jnp.log(l)


def forward_vmem_bytes(group, bq, dim, dtype):
    """VMEM :func:`block_forward` asks for: a group's float32 running
    max, sum (128 lanes each) and accumulator, the two buffers of its
    queries, outputs and statistics, and :data:`BLOCKS_VMEM_BYTES` for
    the key blocks and the rest."""
    size = jnp.dtype(dtype).itemsize
    return group * bq * ((2 * LANES + dim) * 4 + 2 * (2 * dim * size
                                                      + LANES * 4)) \
        + BLOCKS_VMEM_BYTES


def block_forward(q, k, v, mask, qi, scale):
    """``(o (B, H, bq, D) in q's dtype, lse (B, H, bq, LANES)
    float32)`` of query block ``qi`` (``bq``: ``mask``'s rows) against
    the keys up to its end under ``mask``, int8 (B, bq, (qi + 1) *
    bq): 1 where the query attends to the key. ``q``: (B, H, S, D),
    ``k``, ``v``: (B, KV, S, D), whole. Grid ``(batch, key/value head,
    key block, head of the group)``."""
    batch, heads, _, dim = q.shape
    kv_heads = k.shape[1]
    group, bq = heads // kv_heads, mask.shape[1]
    steps = qi + 1
    q_spec = pl.BlockSpec((None, group, bq, dim),
                          lambda b, h, j, g: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((None, None, bq, dim),
                           lambda b, h, j, g: (b, h, j, 0))
    o_spec = pl.BlockSpec((None, group, bq, dim),
                          lambda b, h, j, g: (b, h, 0, 0))
    # the statistic leaves the kernel 128 lanes wide, as it is held
    lse_spec = pl.BlockSpec((None, group, bq, LANES),
                            lambda b, h, j, g: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=scale, steps=steps),
        grid=(batch, kv_heads, steps, group),
        in_specs=[q_spec, kv_spec, kv_spec, pl.BlockSpec(
            (None, bq, bq), lambda b, h, j, g: (b, 0, j))],
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((batch, heads, bq, dim), q.dtype),
                   jax.ShapeDtypeStruct((batch, heads, bq, LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group, bq, LANES), jnp.float32),
                        pltpu.VMEM((group, bq, LANES), jnp.float32),
                        pltpu.VMEM((group, bq, dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=forward_vmem_bytes(group, bq, dim, q.dtype)),
        name="select_attention_forward",
    )(q, k, v, mask)


def _probabilities_kernel(q_ref, k_ref, lse_ref, mask_ref, total_ref,
                          total_scr, *, scale, heads):
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _():
        total_scr[...] = jnp.zeros(total_scr.shape, jnp.float32)

    s = lax.dot_general(k_ref[...], q_ref[h], NT,
                        preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_ref[...] != 0, s, MASKED)
    total_scr[...] += jnp.exp(s - lse_ref[h])            # lse: (1, bq)

    @pl.when(h == heads - 1)
    def _():
        total_ref[...] = total_scr[...].T


def probabilities_vmem_bytes(heads, bq, dim, dtype):
    """VMEM :func:`block_probabilities` asks for: the two buffers of
    every head's block of queries and statistics (a row of 8
    sublanes), the float32 sum of a block pair and its two output
    buffers, and :data:`BLOCKS_VMEM_BYTES` for the rest."""
    size = jnp.dtype(dtype).itemsize
    return 2 * heads * bq * (dim * size + 8 * 4) + 3 * bq * bq * 4 \
        + BLOCKS_VMEM_BYTES


def block_probabilities(q, k, lse, tiles, qi, scale):
    """The summed probabilities of query block ``qi``: float32 (B, bq,
    (qi + 1) * bq), ``sum_h exp(s_h - lse_h)`` over the pairs ``tiles``
    selects and 0 elsewhere. ``lse``: (B, H, 1, bq) float32, the
    block's rows' final log-sum-exp; ``tiles``: the block's, int8 (B,
    qi + 1, bq, bq) as :func:`transposed_tiles` makes them. Grid
    ``(batch, key block, head)``: every head's queries are held; a
    pair's sum stays in VMEM over the heads."""
    batch, heads, _, dim = q.shape
    group = heads // k.shape[1]
    bq = tiles.shape[2]
    steps = qi + 1
    return pl.pallas_call(
        functools.partial(_probabilities_kernel, scale=scale, heads=heads),
        grid=(batch, steps, heads),
        in_specs=[
            pl.BlockSpec((None, heads, bq, dim),
                         lambda b, j, h: (b, 0, qi, 0)),
            pl.BlockSpec((None, None, bq, dim),
                         lambda b, j, h: (b, h // group, j, 0)),
            pl.BlockSpec((None, heads, 1, bq), lambda b, j, h: (b, 0, 0, 0)),
            pl.BlockSpec((None, None, bq, bq),
                         lambda b, j, h: (b, j, 0, 0))],
        out_specs=pl.BlockSpec((None, bq, bq), lambda b, j, h: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((batch, bq, steps * bq), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=probabilities_vmem_bytes(heads, bq, dim,
                                                      q.dtype)),
        name="select_attention_probabilities",
    )(q, k, lse, tiles)


def _backward_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     di_ref, mask_ref, *refs, scale, bq, group, kv_heads,
                     pairs):
    """A block pair's ``p`` and ``ds`` of one head made once for all
    three gradients: ``dq`` sums over a query block's pairs in the
    head's block of scratch, ``dk`` and ``dv`` over every pair and
    head of the group in scratch of the key/value head's whole
    sequence. With a fourth output, ``p`` summed over the heads: over
    the group in a slot of scratch, onto what the key/value heads
    before left of the pair's tile in memory (:func:`_total`)."""
    with_total = len(refs) == 10
    if with_total:
        dq_ref, dk_ref, dv_ref, total_hbm, dq_scr, dk_scr, dv_scr, \
            total_scr, before_scr, sems = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs
    t, g = pl.program_id(2), pl.program_id(3)
    qi, ki = qi_ref[t], ki_ref[t]

    @pl.when((t == 0) & (g == 0))
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(ki == 0)
    def _():
        dq_scr[g] = jnp.zeros(dq_scr.shape[1:], jnp.float32)

    q, k, v, do = q_ref[g], k_ref[...], v_ref[...], do_ref[g]
    s = lax.dot_general(k, q, NT, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_ref[...] != 0, s, MASKED)
    p = jnp.exp(s - lse_ref[g])                          # lse: (1, bq)
    if with_total:
        _total(total_hbm, total_scr, before_scr, sems, p, t, g,
               group=group, kv_heads=kv_heads, pairs=pairs)
    dp = lax.dot_general(v, do, NT, preferred_element_type=jnp.float32)
    ds = p * (dp - di_ref[g]) * scale
    rows = pl.ds(pl.multiple_of(ki * bq, bq), bq)
    dv_scr[rows, :] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
    dk_scr[rows, :] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
    dq_scr[g] += lax.dot_general(ds.astype(k.dtype), k, TN,
                                 preferred_element_type=jnp.float32)

    @pl.when(ki == qi)
    def _():
        dq_ref[g] = dq_scr[g].astype(dq_ref.dtype)

    @pl.when((t == pairs - 1) & (g == group - 1))
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _total(total_hbm, total_scr, before_scr, sems, p, t, g, *, group,
           kv_heads, pairs):
    """Adds a head's ``p`` to the total of block pair ``t``. A
    key/value head's group sums in one of two slots of scratch, by the
    parity of the pair's place in the batch's sweep; at the group's
    last head the tile the key/value heads before wrote (read at the
    first) is added and the slot is written to memory, a copy waited
    for one grid step into the next pair, or before that pair's read
    where the sweep is one pair long, and at the sweep's end. So a
    tile is read only after the previous key/value head's write of it
    is done, and a slot refilled only after its write is."""
    b, h = pl.program_id(0), pl.program_id(1)
    slot = (h * pairs + t) % 2

    def write(slot, tile):
        return pltpu.make_async_copy(total_scr.at[slot],
                                     total_hbm.at[b, tile], sems.at[0])

    def read():
        return pltpu.make_async_copy(total_hbm.at[b, t], before_scr,
                                     sems.at[1])

    @pl.when((g == (0 if pairs == 1 else min(1, group - 1)))
             & ((h > 0) | (t > 0)))
    def _():
        write(0, 0).wait()               # the previous pair's: one tile

    @pl.when(g == 0)
    def _():
        total_scr[slot] = jnp.zeros(total_scr.shape[1:], jnp.float32)

        @pl.when(h > 0)
        def _():
            read().start()

    total_scr[slot] += p

    @pl.when(g == group - 1)
    def _():
        @pl.when(h > 0)
        def _():
            read().wait()
            total_scr[slot] += before_scr[...]

        write(slot, t).start()

        @pl.when((h == kv_heads - 1) & (t == pairs - 1))
        def _():
            write(slot, t).wait()


def backward_vmem_bytes(seq, group, bq, dim, dtype):
    """VMEM :func:`backward` asks for: its float32 ``dk`` and ``dv``
    scratch over the whole sequence with the two buffers of each of
    the two outputs (:func:`band_attention.backward_vmem_bytes`'s), a
    group's float32 ``dq`` scratch with the two buffers of its
    queries, their cotangent and ``dq``, the total's three float32
    tiles of scratch, and :data:`BLOCKS_VMEM_BYTES` for the rest."""
    size = jnp.dtype(dtype).itemsize
    return 2 * seq * dim * (4 + 2 * size) \
        + group * bq * dim * (4 + 3 * 2 * size) + 3 * bq * bq * 4 \
        + BLOCKS_VMEM_BYTES


def backward(q, k, v, o, lse, d_out, tiles, scale, with_total=False):
    """``(dq, dk, dv)`` of the selected core over the whole causal
    triangle; ``dk`` and ``dv`` in the key/value heads' shape, summed
    over each group's query heads. ``lse``: (B, H, S) float32;
    ``tiles``: int8 (B, T, bq, bq), the triangle's
    :func:`transposed_tiles`, by query block, then key block. One kernel
    over the grid ``(batch, key/value head, block pair, head of the
    group)``, the pairs from two tables the grid reads ahead. With
    ``with_total``, a fourth result: the probabilities summed over
    every head, float32 in ``tiles``' shape (keys by queries); each
    key/value head reads and writes a pair's tile once, and the first
    writes it without reading."""
    batch, heads, seq, dim = q.shape
    kv_heads = k.shape[1]
    group, bq = heads // kv_heads, tiles.shape[2]
    # rowsum(dO * O), the softmax backward's correction; a query's
    # statistics as rows: (batch, heads, 1, seq)
    di = jnp.sum(d_out.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    lse = lse[:, :, None, :]
    pairs = _band(seq, bq, bq, None)
    qi_of, ki_of = (jnp.asarray([pair[i] for pair in pairs], jnp.int32)
                    for i in (0, 1))
    q_rows = pl.BlockSpec((None, group, bq, dim),
                          lambda b, h, t, g, qs, ks: (b, h, qs[t], 0))
    q_stats = pl.BlockSpec((None, group, 1, bq),
                           lambda b, h, t, g, qs, ks: (b, h, 0, qs[t]))
    kv_rows = pl.BlockSpec((None, None, bq, dim),
                           lambda b, h, t, g, qs, ks: (b, h, ks[t], 0))
    mask = pl.BlockSpec((None, None, bq, bq),
                        lambda b, h, t, g, qs, ks: (b, t, 0, 0))
    # dk, dv: the key/value head's whole sequence, written once
    kv_seq = pl.BlockSpec((None, None, seq, dim),
                          lambda b, h, t, g, qs, ks: (b, h, 0, 0))
    out_specs = [q_rows, kv_seq, kv_seq]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = [pltpu.VMEM((group, bq, dim), jnp.float32),
               pltpu.VMEM((seq, dim), jnp.float32),
               pltpu.VMEM((seq, dim), jnp.float32)]
    if with_total:
        # the total: in memory, copied tile by tile by the kernel
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(jax.ShapeDtypeStruct(tiles.shape, jnp.float32))
        scratch += [pltpu.VMEM((2, bq, bq), jnp.float32),
                    pltpu.VMEM((bq, bq), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        functools.partial(_backward_kernel, scale=scale, bq=bq, group=group,
                          kv_heads=kv_heads, pairs=len(pairs)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, kv_heads, len(pairs), group),
            in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_stats, q_stats,
                      mask],
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the total passes from one key/value head to the next
            dimension_semantics=(
                "parallel", "arbitrary" if with_total else "parallel",
                "arbitrary", "arbitrary"),
            vmem_limit_bytes=backward_vmem_bytes(seq, group, bq, dim,
                                                 k.dtype)),
        name="select_attention_backward",
    )(qi_of, ki_of, q, k, v, d_out, lse, di, tiles)
