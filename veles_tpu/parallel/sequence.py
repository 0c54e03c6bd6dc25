"""Sequence/context parallelism: ring attention; and the causal
attention core of one chip.

Long sequences are sharded over the mesh's ``seq`` axis; each device
holds a Q/K/V block. K/V blocks rotate around the ring via
``lax.ppermute`` while each device accumulates its Q block's attention
with the streaming-softmax (flash) recurrence — max ``m``, denominator
``l`` and weighted sum carried across hops — so the full sequence is
never materialized on any chip and compute overlaps the ICI transfer.

This is the veles_tpu long-context primitive (the 2015 reference has no
attention at all — SURVEY.md §5 records it as absent; here it is a
first-class capability, designed per the task brief).

On one chip :func:`causal_attention` is the memory-linear causal core
(PR 28). It is one algorithm with two lowerings, chosen from the
backend's platform and the operands' shapes and from nothing else: on
a TPU, where the shapes fit the tiling (:func:`fused_refusal`), the
flash kernels jaxlib ships (:func:`fused_attention`: scores, running
max and sum, probabilities, ``dp`` and ``ds`` stay in VMEM, forward
and backward, and blocks above the diagonal are skipped); anywhere
else :func:`blockwise_attention`, the same recurrence in plain XLA,
which writes each block's float32 scores to memory between the two
products. The gauge ``veles_attention_core_fused{unit}`` says which
one a unit was traced into. :func:`local_attention` is the oracle of
both.

Both lowerings are ``custom_vjp``s of this module (PR 30; the fused
one around jaxlib's three kernels, not jaxlib's own wrapper), because
their forward rules say what a rematerialized unit should not make
twice: the core's output and each row's statistics go through
:func:`veles_tpu.remat.keep` into the residuals, so the step's
checkpoint holds them (85 MB a unit at the token cell's shape) and the
backward pass re-runs the projections but not the core's forward.

GROUPED key/value heads and a WINDOW (PR 31) are shape-like arguments
of the same entry: ``k`` and ``v`` may have fewer heads than ``q``
(query head ``j`` reads key/value head ``j // group``; ``dk``, ``dv``
come back summed over the group, and no repeated ``k`` or ``v`` is
ever held), and ``window=w`` lets query ``i`` see key ``j`` iff ``0 <=
i - j < w``. Either takes a third lowering on a TPU,
:func:`banded_attention`: the repo's own Pallas kernels
(``ops/band_attention.py``, a forward and ONE backward kernel), which
run the block pairs the band touches and no others, forward and
backward; jaxlib's kernels take
neither a group nor a window. Off the TPU :func:`blockwise_attention`
computes the same band. Operands of one shape without a window lower
as they did before.

A LEARNED SELECTION of keys (PR 33) is the fourth case, chosen like
the others by the operands alone: given the operands of an index
(``index=(a, b, c)``: index queries, ONE index key head, per-head
weights), :func:`causal_attention` takes :func:`selected_attention`,
in which every query attends to the ``top_k`` keys before it that the
index scores highest and to no others, and which also gives the
index's own objective (the KL divergence from the head-mean of the
core's probabilities to the index's distribution over the same keys).
On a TPU, where :func:`selected_refusal` has no objection, its core
runs in the repo's own Pallas kernels of the banded family
(``ops/select_attention.py``: a forward, the probabilities the index's
objective compares with, ONE backward), which read the selection as a
byte a pair beside each block pair's keys and mask the scores in VMEM;
anywhere else in XLA's blocks.
"""

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from veles_tpu import remat
from veles_tpu.telemetry.registry import get_registry


def _block_attention(q, k, v, q_off, k_off, scale, causal, m, l, acc):
    """One streaming-softmax update of (m, l, acc) with a new K/V block.

    q: (B, H, Sq, D); k/v: (B, H, Sk, D); offsets are the blocks' global
    sequence positions (for causal masking).
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 2)
        k_pos = k_off + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 3)
        scores = jnp.where(q_pos >= k_pos, scores, -jnp.inf)
    blk_max = jnp.max(scores, axis=-1)               # (B,H,Sq)
    new_m = jnp.maximum(m, blk_max)
    # guard -inf rows (fully masked block): exp(-inf - -inf) -> use safe m
    safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    correction = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf,
                                   m - safe_m))
    correction = jnp.where(jnp.isneginf(m), 0.0, correction)
    new_l = l * correction + jnp.sum(p, axis=-1)
    new_acc = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return new_m, new_l, new_acc


def ring_attention(q, k, v, mesh, axis="seq", causal=False, scale=None):
    """Attention over a sequence sharded on ``axis`` (dim 2 of BHSD).

    Returns the attention output with the same sharding as ``q``.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n_shards = mesh.shape[axis]
    spec = P(None, None, axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def inner(q_blk, k_blk, v_blk):
        seq_shard = q_blk.shape[2]
        my_idx = jax.lax.axis_index(axis)
        q_off = my_idx * seq_shard
        m = jnp.full(q_blk.shape[:3], -jnp.inf, jnp.float32)
        l = jnp.zeros(q_blk.shape[:3], jnp.float32)
        acc = jnp.zeros(q_blk.shape[:3] + (q_blk.shape[3],), jnp.float32)
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        def hop(carry, h):
            k_cur, v_cur, m, l, acc = carry
            src_idx = (my_idx - h) % n_shards
            k_off = src_idx * seq_shard
            m, l, acc = _block_attention(q_blk, k_cur, v_cur, q_off,
                                         k_off, scale, causal, m, l, acc)
            # rotate K/V to the next device while nothing depends on it
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (k_nxt, v_nxt, m, l, acc), None

        # lax.scan (not fori_loop): reverse-mode AD flows through the
        # ring — ppermute's transpose is the inverse rotation, so
        # training THROUGH ring attention needs nothing special
        carry = (k_blk, v_blk, m, l, acc)
        carry, _ = jax.lax.scan(hop, carry, jnp.arange(n_shards))
        _, _, m, l, acc = carry
        l = jnp.maximum(l, 1e-30)
        return (acc / l[..., None]).astype(q_blk.dtype)

    return inner(q, k, v)


def local_attention(q, k, v, causal=False, scale=None, window=None):
    """Single-device oracle with identical math (for parity tests):
    the whole square of scores under an explicit mask. ``window``
    (with ``causal``): query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 3)
        seen = q_pos >= k_pos
        if window is not None:
            seen = seen & (q_pos - k_pos < window)
        scores = jnp.where(seen, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def ulysses_attention(q, k, v, mesh, axis="seq", causal=False,
                      scale=None):
    """All-to-all sequence parallelism (the DeepSpeed-Ulysses
    schedule): the complement to :func:`ring_attention`.

    Q/K/V arrive sequence-sharded (dim 2 of BHSD). One
    ``lax.all_to_all`` per tensor swaps the sequence sharding for a
    HEAD sharding, so each device computes exact full-sequence
    attention for ``H / n_shards`` of the heads with a single dense
    kernel (no streaming recurrence, better MXU shapes); the inverse
    all_to_all restores sequence sharding on the output. Costs two
    all_to_alls of the activations vs the ring's n_shards ppermute
    hops — the better trade when heads divide evenly and the ICI
    bisection is wide; ring wins when H < n_shards or memory for the
    full-sequence scores is tight. Requires H % n_shards == 0.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    n_shards = mesh.shape[axis]
    if q.shape[1] % n_shards:
        raise ValueError(
            "ulysses needs heads (%d) divisible by the %r axis (%d) — "
            "use ring_attention for head counts below the mesh" %
            (q.shape[1], axis, n_shards))
    spec = P(None, None, axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def inner(q_blk, k_blk, v_blk):
        # (B, H, S/n, D) -> (B, H/n, S, D): split heads, gather seq
        def to_heads(t):
            return jax.lax.all_to_all(t, axis, split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = to_heads(q_blk), to_heads(k_blk), to_heads(v_blk)
        out = local_attention(qh, kh, vh, causal=causal, scale=scale)
        # (B, H/n, S, D) -> (B, H, S/n, D)
        return jax.lax.all_to_all(out, axis, split_axis=2,
                                  concat_axis=1, tiled=True)

    return inner(q, k, v)


def _causal_block_scores(q, k, start, scale, first=0, window=None,
                         rows=None):
    """Scores of a block of queries that starts at position ``start``
    against the keys from position ``first`` up to the block's end,
    masked causally and to the ``window``, float32: (B, H, bq, stop -
    first). With grouped heads the group's query blocks lie one under
    the other along the query dim, ``rows`` positions each."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    if rows is not None:
        q_pos = q_pos % rows
    q_pos = start + q_pos
    k_pos = first + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 3)
    seen = q_pos >= k_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return jnp.where(seen, scores, -jnp.inf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def blockwise_attention(q, k, v, scale, block, window=None):
    """Causal softmax attention of (B, H, S, D) operands that never
    holds the ``S x S`` scores: a block of ``block`` queries at a time
    against the keys up to the block's end, so the peak is ``block x
    S`` scores, linear in ``S``, and the half of the square above the
    diagonal is not computed but for the diagonal blocks' corners. The
    backward pass keeps ``q, k, v``, the output and each row's
    log-sum-exp (the last two also across a unit's rematerialization:
    :func:`veles_tpu.remat.keep`) and recomputes a block's
    probabilities from them (the flash recurrence's backward).
    ``q``/``k`` and ``v`` may differ in
    their last dim. Operands are multiplied in the dtype they come in,
    sums are float32. :func:`local_attention` is the oracle.

    ``window``: query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``; a block of queries then runs against the keys from
    ``window - 1`` before its first row on, and no others. ``k`` and
    ``v`` may have fewer heads than ``q``, a whole number of query
    heads to each (query head ``j`` reads key/value head ``j //
    group``): a group's query blocks are laid one under the other
    against their one key/value head, nothing is repeated, and ``dk``,
    ``dv`` come back in ``k``'s shape, summed over the group."""
    return _blockwise_forward(q, k, v, scale, block, window)[0]


def _blocks(seq, block, window=None):
    """``(first key, start, stop)`` of each block of queries."""
    return [(0 if window is None else max(0, start - window + 1),
             start, min(start + block, seq))
            for start in range(0, seq, block)]


def _fold(x, kv_heads):
    """(B, H, bq, D) of grouped query heads as (B, KV, group * bq,
    D): a group's blocks one under the other; the identity where every
    query head has its own key/value head."""
    batch, heads, rows, dim = x.shape
    if heads == kv_heads:
        return x
    return x.reshape(batch, kv_heads, heads // kv_heads * rows, dim)


def _blockwise_forward(q, k, v, scale, block, window=None):
    outs, lses = [], []
    heads, kv_heads = q.shape[1], k.shape[1]
    rows = None if heads == kv_heads else block
    for first, start, stop in _blocks(q.shape[2], block, window):
        scores = _causal_block_scores(
            _fold(q[:, :, start:stop], kv_heads), k[:, :, first:stop],
            start, scale, first, window, rows and stop - start)
        lse = jax.nn.logsumexp(scores, axis=-1)
        p = jnp.exp(scores - lse[..., None]).astype(v.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v[:, :, first:stop],
                         preferred_element_type=jnp.float32)
        outs.append(out.reshape(out.shape[0], heads, stop - start, -1))
        lses.append(lse.reshape(lse.shape[0], heads, stop - start))
    out = jnp.concatenate(outs, axis=2).astype(q.dtype)
    return out, jnp.concatenate(lses, axis=2)


def _blockwise_fwd(q, k, v, scale, block, window=None):
    out, lse = remat.keep(*_blockwise_forward(q, k, v, scale, block,
                                              window))
    return out, (q, k, v, out, lse)


def _blockwise_bwd(scale, block, window, residuals, d_out):
    q, k, v, out, lse = residuals
    heads, kv_heads = q.shape[1], k.shape[1]
    rows = None if heads == kv_heads else block
    # rowsum(dO * O): the softmax backward's correction term
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dqs = []
    for first, start, stop in _blocks(q.shape[2], block, window):
        q_blk = _fold(q[:, :, start:stop], kv_heads)
        do_blk = _fold(d_out[:, :, start:stop], kv_heads)
        k_blk, v_blk = k[:, :, first:stop], v[:, :, first:stop]
        scores = _causal_block_scores(q_blk, k_blk, start, scale, first,
                                      window, rows and stop - start)
        stats = [_fold(t[:, :, start:stop, None], kv_heads)
                 for t in (lse, delta)]
        p = jnp.exp(scores - stats[0])
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk, v_blk,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - stats[1]) * scale).astype(q.dtype)
        dv = dv.at[:, :, first:stop].add(jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(do_blk.dtype), do_blk,
            preferred_element_type=jnp.float32))
        dk = dk.at[:, :, first:stop].add(jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q_blk,
            preferred_element_type=jnp.float32))
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk,
                        preferred_element_type=jnp.float32)
        dqs.append(dq.reshape(dq.shape[0], heads, stop - start, -1))
    dq = jnp.concatenate(dqs, axis=2).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


blockwise_attention.defvjp(_blockwise_fwd, _blockwise_bwd)


def index_scores(a, b, c):
    """Index scores of a block of queries against keys, float32:
    ``I[t, s] = sum_i c[t, i] * relu(a[t, i] . b[s])``. ``a``: (B, Hi,
    bq, Di) index queries; ``b``: (B, Sk, Di), the ONE index key head;
    ``c``: (B, Hi, bq) float32 weights, the index's scale folded in.
    The products run in the dtype the operands come in, sums are
    float32: (B, bq, Sk). One index head at a time (a scan), so that
    the peak is one head's products and not all of them."""
    def head(total, operands):
        a_head, c_head = operands
        dots = jnp.einsum("bqd,bkd->bqk", a_head, b,
                          preferred_element_type=jnp.float32)
        return total + c_head[..., None].astype(jnp.float32) \
            * jax.nn.relu(dots), None

    total, _ = jax.lax.scan(
        head, jnp.zeros((a.shape[0], a.shape[2], b.shape[1]), jnp.float32),
        (jnp.moveaxis(a, 1, 0), jnp.moveaxis(c, 1, 0)))
    return total


def _index_scores_backward(a, b, c, d_scores):
    """:func:`index_scores`' gradient to its operands for the
    cotangent ``d_scores`` (B, bq, Sk), a head at a time, each head's
    products made again: ``(da, db float32, dc)``."""
    def head(db, operands):
        a_head, c_head = operands
        dots = jnp.einsum("bqd,bkd->bqk", a_head, b,
                          preferred_element_type=jnp.float32)
        dc = jnp.sum(d_scores * jax.nn.relu(dots), axis=-1)
        d_dots = jnp.where(
            dots > 0, d_scores * c_head[..., None].astype(jnp.float32),
            0.0).astype(a.dtype)
        da = jnp.einsum("bqk,bkd->bqd", d_dots, b,
                        preferred_element_type=jnp.float32)
        return db + jnp.einsum("bqk,bqd->bkd", d_dots, a_head,
                               preferred_element_type=jnp.float32), \
            (da, dc)

    db, (da, dc) = jax.lax.scan(
        head, jnp.zeros(b.shape, jnp.float32),
        (jnp.moveaxis(a, 1, 0), jnp.moveaxis(c, 1, 0)))
    return jnp.moveaxis(da, 0, 1), db, jnp.moveaxis(dc, 0, 1)


def _causal_rows(start, rows, keys):
    """bool (rows, keys): key ``s`` is no later than the query at
    position ``start + row``."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1) <= \
        start + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)


def select_keys(scores, start, top_k):
    """Which keys each query of a block attends to: bool (B, bq, Sk),
    for the query at position ``t = start + row`` exactly ``min(t + 1,
    top_k)`` of the keys ``s <= t``: those of largest ``scores[t, s]``,
    the lower ``s`` on a tie, which is ``lax.top_k``'s rule (and +0 ties
    with -0). No sort: the k-th largest score of a row is found exactly
    by a search over its float32 bit pattern, one bit a pass (32 passes
    of a compare and a row sum over the block's scores), and the ties
    at that value are taken in the order of their positions. A block
    none of whose queries has more than ``top_k`` keys before it takes
    them all, and searches nothing."""
    rows, keys = scores.shape[-2:]
    causal = _causal_rows(start, rows, keys)
    if start + rows <= top_k:
        return jnp.broadcast_to(causal, scores.shape)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32),
        jnp.uint32)
    # floats in their order as unsigned integers in theirs: a negative
    # number's bits inverted, a positive one's sign bit set; a key the
    # query cannot see ranks under every number
    sign = jnp.uint32(1 << 31)
    ordered = jnp.where(causal, jnp.where(bits >= sign, ~bits,
                                          bits | sign), jnp.uint32(0))
    want = jnp.minimum(start + jnp.arange(rows, dtype=jnp.int32) + 1,
                       top_k)

    def refine(i, kth):
        candidate = kth | jax.lax.shift_right_logical(
            sign, i.astype(jnp.uint32))
        enough = jnp.sum(ordered >= candidate[..., None], axis=-1,
                         dtype=jnp.int32) >= want
        return jnp.where(enough, candidate, kth)

    # the largest value that at least ``want`` of the row reach
    kth = jax.lax.fori_loop(0, 32, refine,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = ordered > kth[..., None]
    ties = (ordered == kth[..., None]) & causal
    left = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                            <= left[..., None]))


def _by_group(x, kv_heads):
    """(B, H, rows, D) of grouped query heads as (KV, B, G, rows, D):
    what a scan over the key/value heads takes a group at a time."""
    batch, heads, rows, dim = x.shape
    return jnp.moveaxis(x.reshape(batch, kv_heads, heads // kv_heads,
                                  rows, dim), 1, 0)


def _group_probabilities(q_grp, k_head, sel, scale, lse=None):
    """One key/value head's group of query heads against that head's
    keys under the block's selection: ``(probabilities (B, G, bq, Sk)
    float32, log-sum-exp (B, G, bq))``; with ``lse`` given, from it."""
    scores = jnp.einsum("bgqd,bkd->bgqk", q_grp, k_head,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(sel[:, None], scores, -jnp.inf)
    if lse is None:
        lse = jax.nn.logsumexp(scores, axis=-1)
    return jnp.exp(scores - lse[..., None]), lse


def _selected_block(q_blk, k_blk, v_blk, sel, scale):
    """A block of queries' core under its selection, ONE key/value
    head's group at a time (a scan: the peak is a group's float32
    scores, not every head's): ``(out (B, H, bq, Dv) float32, lse (B,
    H, bq), the heads' summed probabilities (B, bq, Sk))``."""
    batch, heads, rows, _ = q_blk.shape
    kv_heads = k_blk.shape[1]

    def group(total, operands):
        q_grp, k_head, v_head = operands
        p, lse = _group_probabilities(q_grp, k_head, sel, scale)
        out = jnp.einsum("bgqk,bkd->bgqd", p.astype(v_head.dtype), v_head,
                         preferred_element_type=jnp.float32)
        return total + jnp.sum(p, axis=1), (out, lse)

    total, (out, lse) = jax.lax.scan(
        group, jnp.zeros(sel.shape, jnp.float32),
        (_by_group(q_blk, kv_heads), jnp.moveaxis(k_blk, 1, 0),
         jnp.moveaxis(v_blk, 1, 0)))
    return (jnp.moveaxis(out, 0, 1).reshape(batch, heads, rows, -1),
            jnp.moveaxis(lse, 0, 1).reshape(batch, heads, rows), total)


def _selected_block_backward(q_blk, k_blk, v_blk, do_blk, lse_blk,
                             delta_blk, sel, scale):
    """The flash recurrence's backward of :func:`_selected_block`, a
    key/value head's group at a time: ``(dq (B, H, bq, D), dk, dv (B,
    KV, Sk, D) float32, the heads' summed probabilities)``."""
    batch, heads, rows, _ = q_blk.shape
    kv_heads = k_blk.shape[1]

    def group(total, operands):
        q_grp, k_head, v_head, do_grp, lse_grp, delta_grp = operands
        p, _ = _group_probabilities(q_grp, k_head, sel, scale,
                                    lse_grp[..., 0])
        dp = jnp.einsum("bgqd,bkd->bgqk", do_grp, v_head,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_grp) * scale).astype(q_grp.dtype)
        dv = jnp.einsum("bgqk,bgqd->bkd", p.astype(do_grp.dtype), do_grp,
                        preferred_element_type=jnp.float32)
        dk = jnp.einsum("bgqk,bgqd->bkd", ds, q_grp,
                        preferred_element_type=jnp.float32)
        dq = jnp.einsum("bgqk,bkd->bgqd", ds, k_head,
                        preferred_element_type=jnp.float32)
        return total + jnp.sum(p, axis=1), (dq, dk, dv)

    total, (dq, dk, dv) = jax.lax.scan(
        group, jnp.zeros(sel.shape, jnp.float32),
        (_by_group(q_blk, kv_heads), jnp.moveaxis(k_blk, 1, 0),
         jnp.moveaxis(v_blk, 1, 0), _by_group(do_blk, kv_heads),
         _by_group(lse_blk[..., None], kv_heads),
         _by_group(delta_blk[..., None], kv_heads)))
    return (jnp.moveaxis(dq, 0, 1).reshape(batch, heads, rows, -1),
            jnp.moveaxis(dk, 0, 1), jnp.moveaxis(dv, 0, 1), total)


def _index_log_share(scores, sel):
    """log softmax of the index scores over the selected keys."""
    scores = jnp.where(sel, scores, -jnp.inf)
    return scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)


def _selected_forward(q, k, v, a, b, c, scale, block, top_k, with_loss,
                      kernels):
    """``(out, lse, loss, counts, places, selections)``: ``loss`` (B,)
    is the index's objective summed over the queries (0 without
    ``with_loss``), ``counts`` (B, S) the keys each query selected and
    ``places`` (B, S) the sum of their positions, ``selections`` the
    blocks' masks that a search made (the others are the causal
    triangle's). ``kernels``: the core in
    ``ops/select_attention.py``'s kernels, else in XLA's blocks."""
    batch, heads, seq, _ = q.shape
    outs, lses, counts, places, selections = [], [], [], [], []
    loss = jnp.zeros((batch,), jnp.float32)
    for _, start, stop in _blocks(seq, block):
        span = slice(start, stop)
        with jax.named_scope("index"):
            index = index_scores(a[:, :, span], b[:, :stop], c[:, :, span])
        with jax.named_scope("select"):
            sel = select_keys(jax.lax.stop_gradient(index), start, top_k)
            counts.append(jnp.sum(sel, axis=-1, dtype=jnp.int32))
            places.append(jnp.sum(
                jnp.where(sel, jnp.arange(stop, dtype=jnp.int32), 0),
                axis=-1, dtype=jnp.int32))
        if stop > top_k:
            selections.append(sel)
        with jax.named_scope("core"):
            # a forward that hands no objective (validation) stays in
            # XLA's blocks: there the forward kernel measured slower
            if kernels and with_loss:
                out, lse, total = _selected_block_kernels(
                    q, k, v, sel, start // block, scale)
            else:
                out, lse, total = _selected_block(
                    q[:, :, span], k[:, :, :stop], v[:, :, :stop], sel,
                    scale)
        outs.append(out)
        lses.append(lse)
        if with_loss:
            with jax.named_scope("index_loss"):
                share = total / heads
                loss = loss + jnp.sum(jnp.where(
                    sel & (share > 0), share * (
                        jnp.log(jnp.where(share > 0, share, 1.0))
                        - _index_log_share(index, sel)), 0.0), axis=(1, 2))
    return (jnp.concatenate(outs, axis=2).astype(q.dtype),
            jnp.concatenate(lses, axis=2), loss,
            jnp.concatenate(counts, axis=1),
            jnp.concatenate(places, axis=1), selections)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _selected_block_kernels(q, k, v, sel, qi, scale):
    """:func:`_selected_block`'s ``(out, lse, total)`` of query block
    ``qi`` in the selected kernels: the forward, then the
    probabilities summed over the heads; ``q``, ``k``, ``v`` whole.
    Jitted, so that the units of a chain, all of one shape, trace and
    lower each block's kernels once."""
    from veles_tpu.ops import select_attention
    out, lse = select_attention.block_forward(
        q, k, v, sel.astype(jnp.int8), qi, float(scale))
    total = select_attention.block_probabilities(
        q, k, lse[..., :1].swapaxes(2, 3),
        select_attention.transposed_tiles(sel), qi, float(scale))
    return out, lse[..., 0], total


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def selected_attention(q, k, v, a, b, c, scale, block, top_k,
                       with_loss=True, kernels=False):
    """Causal softmax attention in which every query attends to the
    ``top_k`` keys before it, itself included, that an INDEX scores
    highest (DeepSeek-V3.2's sparse attention, over grouped heads):
    ``(out, loss, counts, places)``.

    ``q``: (B, H, S, D); ``k``, ``v``: (B, KV, S, D), ``H / KV`` query
    heads to each; the index's operands as :func:`index_scores` takes
    them: ``a`` (B, Hi, S, Di), ``b`` (B, S, Di), ``c`` (B, Hi, S).
    Query ``t`` selects ``S_t``, the ``min(t + 1, top_k)`` keys ``s <=
    t`` of largest index score (:func:`select_keys`; every head uses
    the same), and ``out`` is the softmax attention over ``S_t``
    alone. ``loss`` (B,), with ``with_loss``: the sum over the queries
    of ``KL(p_t || softmax over S_t of the index scores)``, ``p_t`` the
    mean over the heads of the core's probabilities, taken as a
    constant. ``counts`` (B, S): the keys each query selected;
    ``places`` (B, S): the sum of those keys' positions, int32 (under
    2**31 for ``top_k * S``): what a step can show of WHICH keys it
    selected without holding a mask.

    The gradient: ``out``'s reaches ``q, k, v`` and ``loss``'s reaches
    ``a, b, c``, and neither the other's operands; nothing passes
    through the selection. Blocks of ``block`` queries against the
    keys up to the block's end; the causal square is computed under
    the selection's mask, not skipped by it, and no ``(H, S, S)``
    array exists. The core runs in XLA, as
    :func:`blockwise_attention`, within a block one key/value head's
    group of query heads at a time (that group's float32 scores are
    the peak); or with ``kernels`` in the Pallas kernels of
    ``ops/select_attention.py``, in which scores and probabilities
    stay in VMEM, forward and backward: the index's objective takes
    its summed probabilities from a kernel of their own in the forward
    pass and from the backward kernel in the backward. A forward
    without ``with_loss`` (validation) stays in XLA's blocks with or
    without ``kernels``: on a v5e the forward kernel made the Keye
    cell's eval step 9.3 ms slower than XLA's blocks. Traced under
    sub-scopes of the caller's: ``index`` (the index scores),
    ``select``, ``core``, ``index_loss``. The backward pass keeps the
    operands, the output, each row's log-sum-exp and the searched
    blocks' masks (a byte a pair; the last three also across a unit's
    rematerialization: :func:`veles_tpu.remat.keep`) and recomputes a
    block's scores once for the three gradients of the core and the
    summed probabilities."""
    out, _, loss, counts, places, _ = _selected_forward(
        q, k, v, a, b, c, scale, block, top_k, with_loss, kernels)
    return out, loss, counts, places


def _selected_fwd(q, k, v, a, b, c, scale, block, top_k, with_loss,
                  kernels):
    out, lse, loss, counts, places, selections = _selected_forward(
        q, k, v, a, b, c, scale, block, top_k, with_loss, kernels)
    out, lse, *selections = remat.keep(out, lse, *selections)
    return (out, loss, counts, places), \
        (q, k, v, a, b, c, out, lse, selections)


def _selected_bwd(scale, block, top_k, with_loss, kernels, residuals,
                  cotangents):
    q, k, v, a, b, c, out, lse, selections = residuals
    d_out, d_loss = cotangents[:2]
    batch, heads, seq, _ = q.shape
    selections = iter(selections)
    # a block that ends within top_k keys selected them all
    sels = [next(selections) if stop > top_k else jnp.broadcast_to(
        _causal_rows(start, stop - start, stop),
        (batch, stop - start, stop))
        for _, start, stop in _blocks(seq, block)]
    if kernels:
        from veles_tpu.ops import select_attention
        with jax.named_scope("core"):
            tiles = jnp.concatenate(
                [select_attention.transposed_tiles(sel) for sel in sels],
                axis=1)
            dq, dk, dv, *totals = _selected_backward_kernel(
                q, k, v, out, lse, d_out, tiles, scale, with_loss)
    else:
        with jax.named_scope("core"):
            delta = jnp.sum(d_out.astype(jnp.float32)
                            * out.astype(jnp.float32), axis=-1)
        dk = jnp.zeros(k.shape, jnp.float32)
        dv = jnp.zeros(v.shape, jnp.float32)
        dqs = []
    db = jnp.zeros(b.shape, jnp.float32)
    das, dcs = [], []
    for (_, start, stop), sel in zip(_blocks(seq, block), sels):
        span = slice(start, stop)
        with jax.named_scope("core"):
            if kernels:
                if with_loss:
                    # the block's tiles of the total, keys by queries,
                    # as (B, bq, stop)
                    first = start // block * (start // block + 1) // 2
                    total = totals[0][:, first:first + stop // block]
                    total = total.transpose(0, 3, 1, 2).reshape(
                        batch, stop - start, stop)
            else:
                dq, dk_blk, dv_blk, total = _selected_block_backward(
                    q[:, :, span], k[:, :, :stop], v[:, :, :stop],
                    d_out[:, :, span], lse[:, :, span], delta[:, :, span],
                    sel, scale)
                dk = dk.at[:, :, :stop].add(dk_blk)
                dv = dv.at[:, :, :stop].add(dv_blk)
                dqs.append(dq)
        if with_loss:
            operands = a[:, :, span], b[:, :stop], c[:, :, span]
            with jax.named_scope("index"):
                index = index_scores(*operands)
            with jax.named_scope("index_loss"):
                # d KL(p || softmax(I)) / d I = softmax(I) - p on S_t
                d_index = jnp.where(
                    sel, jnp.exp(_index_log_share(index, sel))
                    - total / heads, 0.0) * d_loss[:, None, None]
            with jax.named_scope("index"):
                da, db_blk, dc = _index_scores_backward(*operands, d_index)
                db = db.at[:, :stop].add(db_blk)
            das.append(da)
            dcs.append(dc)
    if with_loss:
        da, dc = jnp.concatenate(das, axis=2), jnp.concatenate(dcs, axis=2)
    else:
        da, dc = jnp.zeros_like(a), jnp.zeros_like(c)
    if not kernels:
        dq = jnp.concatenate(dqs, axis=2)
    return (dq.astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype), da.astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype))


@functools.partial(jax.jit, static_argnums=(7, 8))
def _selected_backward_kernel(q, k, v, out, lse, d_out, tiles, scale,
                              with_total):
    from veles_tpu.ops import select_attention
    return select_attention.backward(q, k, v, out, lse, d_out, tiles,
                                     float(scale), with_total)


selected_attention.defvjp(_selected_fwd, _selected_bwd)


def selection_mask(a, b, c, block, top_k):
    """The selection :func:`selected_attention` makes from these
    index operands, whole: bool (B, S, S), made the same way, a block
    of queries at a time. For tests and for a comparison with a
    reference; the core itself never holds it."""
    seq = a.shape[2]
    rows = []
    for _, start, stop in _blocks(seq, block):
        sel = select_keys(index_scores(
            a[:, :, start:stop], b[:, :stop], c[:, :, start:stop]),
            start, top_k)
        rows.append(jnp.pad(sel, ((0, 0), (0, 0), (0, seq - stop))))
    return jnp.concatenate(rows, axis=1)


def selected_pairs(seq, top_k):
    """Query-key pairs a head and sequence that a selection of
    ``top_k`` keys a query leaves of the causal triangle."""
    full = min(seq, top_k)
    return full * (full + 1) // 2 + (seq - full) * top_k


#: the fused kernel's key/value block, forward and backward: chosen on
#: a v5e at the token cell's (2, 20, 4096, 256) bf16 with 512 queries a
#: block (PR 28, scripts/attention_core_bench.py; ms forward / forward
#: + backward): 512 3.19 / 14.43; 256 3.36 / 15.41; 1024 in one step
#: 3.11, as a major block of two 512s 3.25 / 14.58; 2048 3.58; 4096
#: does not fit VMEM. XLA's blocks take 7.38 / 20.98.
FUSED_KV_BLOCK = 512
#: the vector lanes of a TPU register: every block and head size of the
#: fused kernel is a whole number of them
LANES = 128
#: most bytes of a block of queries (block x head size) that the three
#: kernels were seen to fit into a v5e's scoped VMEM with, at every
#: head size and dtype tried (the v5e compiler, no chip, PR 28); twice
#: that fits at some shapes and not at others
FUSED_QUERY_TILE_BYTES = 512 * 1024

_refusals_logged = set()


def fused_refusal(q, k, v, block, window=None):
    """Why no fused kernel can take these (B, H, S, D) operands a
    ``block`` of queries at a time, in words; None where one can.
    Shapes only: the platform is :func:`causal_attention`'s to ask.
    Operands of one shape without a window are jaxlib's kernels' to
    take; grouped key/value heads or a window the repo's own
    (:func:`banded_attention`), whose backward holds ``dk`` and ``dv``
    of a key/value head's whole sequence in VMEM: a sequence too long
    for that is refused."""
    seq, head = q.shape[2], q.shape[3]
    banded = window is not None or q.shape[1] != k.shape[1]
    if banded:
        if k.shape != v.shape or q.shape[1] % k.shape[1] or \
                q.shape[:1] + q.shape[2:] != k.shape[:1] + k.shape[2:]:
            return "the grouped, banded kernels want k and v of one " \
                "shape, which is q's but for a head count that divides " \
                "q's, got %s, %s, %s" % (q.shape, k.shape, v.shape)
        if window is not None and window < 1:
            return "a window of %r keys is none" % (window,)
    elif not q.shape == k.shape == v.shape:
        return "the kernel wants q, k and v of one shape (or k and v " \
            "of one shape with fewer heads: the grouped kernels), " \
            "got %s, %s, %s" % (q.shape, k.shape, v.shape)
    if head % LANES:
        return "head size %d is not a multiple of %d" % (head, LANES)
    if block % LANES or seq % block:
        return "sequence %d is not whole blocks of %d queries, " \
            "themselves a multiple of %d" % (seq, block, LANES)
    tile = block * head * q.dtype.itemsize
    if tile > FUSED_QUERY_TILE_BYTES:
        return "a block of %d queries of %d %s is %d bytes, over the " \
            "%d the kernels' VMEM is known to hold" % (
                block, head, q.dtype, tile, FUSED_QUERY_TILE_BYTES)
    if banded:
        from veles_tpu.ops import band_attention
        need = band_attention.backward_vmem_bytes(seq, head, k.dtype)
        if need > band_attention.VMEM_BYTES:
            return "the banded backward's dk/dv scratch over %d positions " \
                "of %d %s, with its blocks, is %d bytes, over the %d of " \
                "VMEM a core has" % (seq, head, k.dtype, need,
                                     band_attention.VMEM_BYTES)
    return None


def selected_refusal(q, k, v, block):
    """Why the selected kernels (``ops/select_attention.py``) cannot
    take these (B, H, S, D) operands a ``block`` of queries at a time,
    in words; None where they can. Shapes only, as
    :func:`fused_refusal`, whose rules of shape and tiling hold here
    too; then the VMEM each of the three kernels asks for (the
    backward's ``dk``/``dv`` scratch over the whole sequence, every
    head's block of queries in the probabilities') against a core's."""
    refusal = fused_refusal(q, k, v, block)
    if refusal:
        return refusal
    from veles_tpu.ops import band_attention, select_attention
    heads, seq, dim = q.shape[1:]
    group = heads // k.shape[1]
    for kernel, need in (
            ("forward", select_attention.forward_vmem_bytes(
                group, block, dim, q.dtype)),
            ("probabilities", select_attention.probabilities_vmem_bytes(
                heads, block, dim, q.dtype)),
            ("backward", select_attention.backward_vmem_bytes(
                seq, group, block, dim, k.dtype))):
        if need > band_attention.VMEM_BYTES:
            return "the selected %s kernel at %d positions of %d heads " \
                "of %d %s asks for %d bytes of VMEM, over the %d a core " \
                "has" % (kernel, seq, heads, dim, q.dtype, need,
                         band_attention.VMEM_BYTES)
    return None


def _kv_block(seq):
    """The fused kernels' key block: :data:`FUSED_KV_BLOCK`, or what
    of it divides the sequence (whole lanes, since whole blocks of
    queries are)."""
    return math.gcd(seq, FUSED_KV_BLOCK)


def _flash(seq, block):
    """jaxlib's flash-attention module and its block sizes for ``block``
    queries of a sequence of ``seq``; the key block is
    :func:`_kv_block`'s."""
    from jax.experimental.pallas.ops.tpu import flash_attention as flash
    kv = _kv_block(seq)
    return flash, flash.BlockSizes(
        block_q=block, block_k_major=kv, block_k=kv, block_b=1,
        block_q_major_dkv=block, block_q_dkv=block,
        block_k_major_dkv=kv, block_k_dkv=kv,
        block_q_dq=block, block_k_major_dq=kv, block_k_dq=kv)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _fused_forward(q, k, v, scale, block, save_residuals):
    """The forward kernel: ``o``, or ``(o, l, m)`` with each row's sum
    and max. Jitted, like :func:`_fused_backward`, so that the units
    of a chain, all of one shape, trace and lower the kernels once."""
    flash, sizes = _flash(q.shape[2], block)
    return flash._flash_attention_impl(
        q, k, v, None, None, save_residuals, True, float(scale),
        sizes.block_b, sizes.block_q, sizes.block_k_major, sizes.block_k,
        False)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _fused_backward(q, k, v, o, l, m, d_out, scale, block):
    """jaxlib's backward rule whole: ``di``, the dk/dv kernel, the dq
    kernel."""
    flash, sizes = _flash(q.shape[2], block)
    dq, dk, dv, _, _ = flash._flash_attention_bwd(
        False, True, float(scale), sizes, False,
        (q, k, v, None, None, o, l, m), d_out)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_attention(q, k, v, scale, block):
    """:func:`blockwise_attention`'s mathematics as the three Mosaic
    kernels of ``jax.experimental.pallas.ops.tpu.flash_attention``
    (forward, dk/dv, dq) under this repo's own ``custom_vjp``: jaxlib's
    kernels, block sizes and backward rule (``di = rowsum(dO * O)``
    included), called as jaxlib's wrapper calls them, but a forward
    rule that names what it keeps. Operands are multiplied in the dtype
    they come in, scores are float32 and scaled after the product, the
    probabilities are cast to ``v``'s dtype for the second product,
    the reciprocal is exact; a block wholly above the diagonal is not
    run. The backward pass keeps ``q, k, v``, the output and each
    row's max and sum; the last three go through
    :func:`veles_tpu.remat.keep`, so a rematerialized unit runs the
    forward kernel once a step, not twice. ``block`` is the query
    block of all three kernels, the key block :func:`_flash`'s. Runs
    on a TPU, or anywhere under ``pltpu.force_tpu_interpret_mode()``;
    the shapes are :func:`fused_refusal`'s to admit."""
    return _fused_forward(q, k, v, scale, block, False)


def _fused_fwd(q, k, v, scale, block):
    o, l, m = remat.keep(*_fused_forward(q, k, v, scale, block, True))
    return o, (q, k, v, o, l, m)


def _fused_bwd(scale, block, residuals, d_out):
    return _fused_backward(*residuals, d_out, scale, block)


fused_attention.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _banded_forward(q, k, v, scale, block, window):
    from veles_tpu.ops import band_attention
    return band_attention.forward(q, k, v, float(scale), block,
                                  _kv_block(q.shape[2]), window)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _banded_backward(q, k, v, o, lse, d_out, scale, block, window):
    from veles_tpu.ops import band_attention
    return band_attention.backward(q, k, v, o, lse, d_out, float(scale),
                                   block, _kv_block(q.shape[2]), window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def banded_attention(q, k, v, scale, block, window=None):
    """:func:`blockwise_attention`'s mathematics, grouped heads and
    window included, as the two Pallas kernels of
    ``veles_tpu/ops/band_attention.py`` (forward, backward): scores
    never leave VMEM; a block pair outside the band is neither run
    nor fetched, in either; the backward makes a pair's scores once
    for ``dq``, ``dk`` and ``dv``; ``k`` and ``v`` are read through
    ``head // group`` and never repeated; ``dk``, ``dv`` are summed
    over the group inside the kernel. The forward rule keeps the
    output and each row's log-sum-exp through
    :func:`veles_tpu.remat.keep`, as :func:`fused_attention` keeps
    ``o, l, m``. ``block`` is the query block, the key block
    :func:`_kv_block`'s. Runs on a TPU, or anywhere under
    ``pltpu.force_tpu_interpret_mode()``; the shapes are
    :func:`fused_refusal`'s to admit."""
    return _banded_forward(q, k, v, scale, block, window)[0]


def _banded_fwd(q, k, v, scale, block, window=None):
    o, lse = remat.keep(*_banded_forward(q, k, v, scale, block, window))
    return o, (q, k, v, o, lse)


def _banded_bwd(scale, block, window, residuals, d_out):
    return _banded_backward(*residuals, d_out, scale, block, window)


banded_attention.defvjp(_banded_fwd, _banded_bwd)


def core_blocks(seq, block, window=None, fused=True):
    """Block pairs (a block of queries against a block of keys) a head
    and sequence that the core runs in one pass over the scores: the
    fused kernels' by their grids, XLA's blockwise path's by the keys
    each block of queries is given, in blocks, rounded up."""
    if fused and not seq % block:
        from veles_tpu.ops import band_attention
        return band_attention.geometry(seq, block, _kv_block(seq),
                                       window)[1]
    return sum(-(-(stop - first) // block)
               for first, _, stop in _blocks(seq, block, window))


def causal_attention(q, k, v, scale, block, unit="", window=None,
                     index=None, top_k=None, index_loss=True):
    """The memory-linear causal core of (B, H, S, D) operands, a
    ``block`` of queries at a time. ``k`` and ``v`` may have fewer
    heads than ``q`` (grouped), and ``window`` keeps a query to the
    ``window`` newest keys, itself included; both are shape-like: they
    choose the lowering with the platform and the shapes, and nothing
    else does. Operands of one shape without a window take
    :func:`fused_attention` (jaxlib's kernels) where the default
    backend is a TPU and :func:`fused_refusal` has no objection;
    grouped or windowed ones :func:`banded_attention` (the repo's
    own) on the same two conditions; everything else
    :func:`blockwise_attention`. A head that is no whole lanes (64 of
    128) goes to the same kernels behind zeros up to whole lanes
    (``veles_attention_head_padding{unit}``): the scores are what they
    were, the padded part of the output is cut off again, and the
    kernels run the products of the padded size. Called while a
    program is traced; sets the gauges
    ``veles_attention_core_fused{unit}`` (1 or 0),
    ``veles_attention_window{unit}`` (0 for none),
    ``veles_attention_kv_group{unit}`` (query heads to a key/value
    head), ``veles_attention_core_blocks{unit,pass}``
    (:func:`core_blocks`, forward and backward: the backward runs as
    many as the forward) and ``veles_attention_backward_kernels{unit}``
    (the Pallas kernels the backward runs: 1 banded, 2 jaxlib's, 0 in
    XLA's blocks) accordingly, and on a TPU logs, once a reason, why a
    core fell back.

    With ``index=(a, b, c)``, the operands of an index
    (:func:`index_scores`), and ``top_k``, every query attends to the
    ``top_k`` keys the index scores highest:
    :func:`selected_attention`, in its kernels where the default
    backend is a TPU and :func:`selected_refusal` has no objection (the
    gauges then read 1 and 1; a forward without ``index_loss`` stays
    XLA's blocks), else in XLA's blocks, and the result is
    its ``(out, loss, counts, places)`` (``loss`` 0 without
    ``index_loss``). Two more gauges then:
    ``veles_attention_index_topk{unit}`` and
    ``veles_attention_selected_pairs{unit}`` (:func:`selected_pairs`:
    what the selection leaves of the causal triangle, a head and
    sequence; the blocks gauge still counts what the lowering runs,
    the whole triangle)."""
    on_tpu = jax.default_backend() == "tpu"
    if index is not None and window is not None:
        raise ValueError("a selection of keys inside a window is not "
                         "implemented")
    lanes = 0
    if not on_tpu:
        refusal = "no TPU"
    elif index is not None:
        refusal = selected_refusal(q, k, v, block)
    else:
        refusal = fused_refusal(q, k, v, block, window)
        if refusal and q.shape[-1] % LANES:
            # a head of part of a lane (64): the kernels take it
            # behind zeros up to whole lanes, which add nothing to a
            # score and come out of the values' product as zeros
            padded = [jax.ShapeDtypeStruct(
                t.shape[:-1] + (t.shape[-1] + -t.shape[-1] % LANES,),
                t.dtype) for t in (q, k, v)]
            if fused_refusal(*padded, block, window) is None:
                refusal, lanes = None, -q.shape[-1] % LANES
    plain = window is None and q.shape[1] == k.shape[1]
    registry = get_registry()
    registry.gauge(
        "veles_attention_core_fused", "1 where the unit's causal "
        "attention core was traced into the fused TPU kernel, 0 where "
        "into XLA's blockwise path", labels=("unit",)).labels(
        unit=unit).set(0.0 if refusal else 1.0)
    registry.gauge(
        "veles_attention_head_padding", "Zeros behind every head of "
        "the unit's attention core, up to whole lanes, so that a fused "
        "kernel takes it; 0 where the head is whole lanes or no kernel "
        "runs", labels=("unit",)).labels(unit=unit).set(float(lanes))
    registry.gauge(
        "veles_attention_window", "Keys a query of the unit's "
        "attention core sees, itself included; 0 where all before it",
        labels=("unit",)).labels(unit=unit).set(float(window or 0))
    registry.gauge(
        "veles_attention_kv_group", "Query heads that read one "
        "key/value head in the unit's attention core",
        labels=("unit",)).labels(unit=unit).set(
        q.shape[1] / k.shape[1])
    blocks = registry.gauge(
        "veles_attention_core_blocks", "Block pairs a head and "
        "sequence that the unit's attention core runs in a pass over "
        "the scores", labels=("unit", "pass"))
    # the selected kernels run XLA's blocks' triangle, a key block as
    # wide as a query block
    pairs = core_blocks(q.shape[2], block, window,
                        fused=not refusal and index is None)
    for which in ("forward", "backward"):
        blocks.labels(**{"unit": unit, "pass": which}).set(float(pairs))
    registry.gauge(
        "veles_attention_backward_kernels", "Pallas kernels the "
        "backward pass of the unit's attention core runs: 1 where the "
        "repo's banded kernels, 2 where jaxlib's, 0 where XLA's blocks",
        labels=("unit",)).labels(unit=unit).set(
        0.0 if refusal else 2.0 if plain and index is None else 1.0)
    if refusal and on_tpu and refusal not in _refusals_logged:
        _refusals_logged.add(refusal)
        logging.getLogger("sequence").warning(
            "attention core of %r takes XLA's blockwise path, not the "
            "fused kernel: %s", unit, refusal)
    if index is not None:
        registry.gauge(
            "veles_attention_index_topk", "Keys a query of the unit's "
            "attention core selects by its index, itself included",
            labels=("unit",)).labels(unit=unit).set(float(top_k))
        registry.gauge(
            "veles_attention_selected_pairs", "Query-key pairs a head "
            "and sequence that the unit's selection leaves of the "
            "causal triangle", labels=("unit",)).labels(unit=unit).set(
            float(selected_pairs(q.shape[2], top_k)))
        return selected_attention(q, k, v, *index, scale, block,
                                  int(top_k), bool(index_loss),
                                  refusal is None)
    if refusal is None:
        head = v.shape[-1]
        if lanes:
            q, k, v = (jnp.pad(t, ((0, 0),) * 3
                               + ((0, -t.shape[-1] % LANES),))
                       for t in (q, k, v))
        out = fused_attention(q, k, v, scale, block) if plain \
            else banded_attention(q, k, v, scale, block, window)
        return out[..., :head] if lanes else out
    return blockwise_attention(q, k, v, scale, block, window)
