"""GEMM with selectable accumulation discipline.

Re-provides the reference's matrix-multiplication kernel family
(``ocl/matrix_multiplication_begin.cl`` / ``_subsum.cl`` / ``_end.cl`` /
``_precise.cl``; CUBLAS on the CUDA backend) the TPU way, as three
accumulation disciplines:

* ``precision_level=0`` — plain MXU matmul with fp32 accumulation
  (``preferred_element_type``): XLA's dot. On TPU this is already
  stronger than the reference's level 0 (fp32 multiply-add chain)
  because the MXU accumulates in fp32 regardless of bf16 inputs.
* ``precision_level=1`` — Kahan-compensated accumulation over K-chunks
  (the reference's ``PRECISION_LEVEL 1`` summation, ``_subsum.cl``).
* ``precision_level=2`` — multi-partial pairwise summation: K is split
  into partials that are reduced pairwise (``PRECISION_LEVEL 2``).

One Pallas kernel, the carrier of level 1: :func:`pallas_kahan_gemm`
keeps the accumulator and its compensation in VMEM. Which carrier runs
is a function of the platform and the operands' shapes, written where
it is called (:func:`kahan_matmul`): the kernel on a TPU when the
shapes divide by its tiles, the ``fori_loop`` of chunked dots anywhere
else. Level 0 has no kernel of its own: a fixed 256x256x512 Pallas
tiling read 18.4 against XLA's 40.3 TF/s on a compute-bound square
and within 20% of it on the fc shapes, each product alone, and an
opaque call would cut the fusions XLA builds around a dense layer
(docs/PERF.md "GEMM disciplines").
"""

import functools

import jax
import jax.numpy as jnp


def _on_tpu():
    return jax.default_backend() == "tpu"


# -- public gemm -------------------------------------------------------------

def gemm(a, b, transpose_a=False, transpose_b=False, alpha=1.0, beta=0.0,
         c=None, precision_level=0, out_dtype=None):
    """cuBLAS-like gemm: ``alpha * op(a) @ op(b) + beta * c``."""
    if transpose_a:
        a = a.T
    if transpose_b:
        b = b.T
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    if precision_level <= 0:
        out = jnp.dot(a, b, preferred_element_type=jnp.float32)
    elif precision_level == 1:
        out = kahan_matmul(a, b)
    else:
        out = pairwise_matmul(a, b)
    out = alpha * out
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out.astype(out_dtype)


def pairwise_matmul(a, b, parts=None):
    """PRECISION_LEVEL 2: split-K partial sums reduced pairwise."""
    k = a.shape[-1]
    if parts is None:
        parts = 1
        while parts * parts < k:
            parts *= 2
        parts = min(parts, k)
    while k % parts:
        parts //= 2
    kc = k // parts
    ap = a.reshape(a.shape[:-1] + (parts, kc))
    bp = b.reshape((parts, kc) + b.shape[1:])
    # partials[p] = a[:, p-chunk] @ b[p-chunk, :] with fp32 accumulation
    partials = jnp.einsum("mpk,pkn->pmn", ap, bp,
                          preferred_element_type=jnp.float32)
    # pairwise tree reduction of the partials
    while partials.shape[0] > 1:
        n = partials.shape[0]
        if n % 2:
            partials = jnp.concatenate(
                [partials[:-2], (partials[-2] + partials[-1])[None]], axis=0)
        else:
            partials = partials[0::2] + partials[1::2]
    return partials[0]


def kahan_matmul(a, b, chunk=None):
    """PRECISION_LEVEL 1: Kahan-compensated accumulation over K chunks.

    On a TPU, when the shapes divide by the kernel's tiles and no
    ``chunk`` is asked for, the Pallas kernel (the compensation lives
    in VMEM next to the accumulator); else an XLA ``fori_loop`` of
    chunked dots carrying the compensation (CPU, ragged shapes)."""
    if chunk is None and _on_tpu() and _tileable(a, b):
        return pallas_kahan_gemm(a, b)
    return _kahan_matmul_loop(a, b, chunk)


def _kahan_matmul_loop(a, b, chunk=None):
    m, k = a.shape
    n = b.shape[1]
    if chunk is None:
        chunk = max(1, min(512, k))
    if k % chunk:
        # zero-pad K to a multiple: zeros add nothing to the sums and
        # keep the loop count at ceil(k/chunk) even for prime K
        pad = chunk - k % chunk
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
        k += pad
    steps = k // chunk
    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)

    def body(i, carry):
        acc, comp = carry
        ak = jax.lax.dynamic_slice(a32, (0, i * chunk), (m, chunk))
        bk = jax.lax.dynamic_slice(b32, (i * chunk, 0), (chunk, n))
        term = jnp.dot(ak, bk, preferred_element_type=jnp.float32)
        # Kahan: y = term - comp; t = acc + y; comp = (t - acc) - y
        y = term - comp
        t = acc + y
        comp = (t - acc) - y
        return t, comp

    acc = jnp.zeros((m, n), jnp.float32)
    comp = jnp.zeros((m, n), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, steps, body, (acc, comp))
    return acc


# ---------------------------------------------------------------------------
# Pallas tiled Kahan GEMM (TPU): fp32 accumulator and compensation in VMEM.
# ---------------------------------------------------------------------------

#: tile sizes of the Pallas kernel
_BM, _BN, _BK = 256, 256, 512


def _tileable(a, b, bm=_BM, bn=_BN, bk=_BK):
    m, k = a.shape
    n = b.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    return m % bm == 0 and n % bn == 0 and k % bk == 0


def _kahan_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, comp_ref, *,
                       k_steps):
    """Tiled GEMM whose K-accumulation is Kahan-compensated IN VMEM —
    the fused realization of the reference's ``PRECISION_LEVEL 1``
    summation (``ocl/matrix_multiplication_subsum.cl``): each K-step's
    partial product joins the accumulator through the compensated
    add, and neither the accumulator nor the compensation ever round-
    trips to HBM."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    term = jnp.dot(a_ref[...], b_ref[...],
                   preferred_element_type=jnp.float32)
    y = term - comp_ref[...]
    t = acc_ref[...] + y
    comp_ref[...] = (t - acc_ref[...]) - y
    acc_ref[...] = t

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def pallas_kahan_gemm(a, b, bm=_BM, bn=_BN, bk=_BK, interpret=False):
    """Kahan-compensated tiled MXU matmul (precision_level=1 carrier)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk or not (_on_tpu() or interpret):
        return _kahan_matmul_loop(a, b)
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_kahan_gemm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(m * k + k * n + m * n) * a.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(a, b)
