"""GEMM with selectable accumulation discipline.

Re-provides the reference's matrix-multiplication kernel family
(``ocl/matrix_multiplication_begin.cl`` / ``_subsum.cl`` / ``_end.cl`` /
``_precise.cl``; CUBLAS on the CUDA backend) the TPU way:

* ``precision_level=0`` — plain MXU matmul with fp32 accumulation
  (``preferred_element_type``): the fast path. On TPU this is already
  stronger than the reference's level 0 (fp32 multiply-add chain)
  because the MXU accumulates in fp32 regardless of bf16 inputs.
* ``precision_level=1`` — Kahan-compensated accumulation over K-chunks
  (the reference's ``PRECISION_LEVEL 1`` summation, ``_subsum.cl``).
* ``precision_level=2`` — multi-partial pairwise summation: K is split
  into partials that are reduced pairwise (``PRECISION_LEVEL 2``).

Dispatch between XLA dot and the Pallas kernels is SHAPE-AWARE via
:mod:`veles_tpu.ops.autotune`: the old static rule (level 0 always on
XLA dot, because the one fixed 256x256x512 tiling lost ~2x on large
compute-bound squares while beating XLA on bandwidth-bound shapes —
fc6 wgrad 2.5 vs 1.5 TF/s, 4096^3 18 vs 40 TF/s, docs/PERF.md) is now
the ``VELES_AUTOTUNE=off`` fallback; with the tuner on, each
``(M, N, K, dtype)`` picks whatever the per-device measurement cache
says wins, block config included. The Pallas kernels themselves are
parameterized over block sizes, ``dimension_semantics`` and an
optional fused bias+activation epilogue (:func:`fused_linear`) so the
All2All forward absorbs its elementwise tail into the GEMM's output
step instead of a separate HBM pass.
"""

import functools

import jax
import jax.numpy as jnp


def _on_tpu():
    return jax.default_backend() == "tpu"


# -- fused epilogues ---------------------------------------------------------
# Bit-for-bit twins of veles_tpu.nn.activation's family, duplicated
# here (a) to keep ops/ free of an nn/ dependency and (b) because the
# backward pass needs the FROM-Y derivative forms below. The parity is
# pinned by tests/test_autotune.py.

def _act_linear(x):
    return x


def _act_tanh(x):
    return 1.7159 * jnp.tanh(0.6666 * x)


def _act_sigmoid(x):
    return jax.nn.sigmoid(x)


def _act_relu_soft(x):
    return jnp.where(x > 15.0, x, jnp.log1p(jnp.exp(jnp.minimum(x, 15.0))))


def _act_relu_strict(x):
    return jnp.maximum(x, 0.0)


_EPILOGUES = {
    "linear": _act_linear,
    "tanh": _act_tanh,
    "sigmoid": _act_sigmoid,
    "relu": _act_relu_soft,
    "strict_relu": _act_relu_strict,
}

#: activation derivative AS A FUNCTION OF THE OUTPUT y — the property
#: that lets :func:`fused_linear`'s backward keep only (x, w, y) as
#: residuals (no pre-activation round-trips to HBM)
_EPILOGUE_GRADS = {
    "linear": lambda y: jnp.ones_like(y),
    "tanh": lambda y: 1.7159 * 0.6666 * (1.0 - jnp.square(y / 1.7159)),
    "sigmoid": lambda y: y * (1.0 - y),
    # y = log1p(e^x) => dy/dx = sigmoid(x) = 1 - e^-y (clamped region
    # y = x > 15 gives 1 - e^-y ~ 1, exact to f32)
    "relu": lambda y: 1.0 - jnp.exp(-y),
    "strict_relu": lambda y: (y > 0.0).astype(y.dtype),
}


def epilogue_fn(name):
    """The epilogue activation by name (fusable subset only)."""
    try:
        return _EPILOGUES[name]
    except KeyError:
        raise ValueError("no fused epilogue for activation %r (have %s)"
                         % (name, sorted(_EPILOGUES)))


def fusable_activation(name):
    return name in _EPILOGUES


# -- public gemm -------------------------------------------------------------

def gemm(a, b, transpose_a=False, transpose_b=False, alpha=1.0, beta=0.0,
         c=None, precision_level=0, out_dtype=None):
    """cuBLAS-like gemm: ``alpha * op(a) @ op(b) + beta * c``."""
    ta, tb = transpose_a, transpose_b
    if transpose_a:
        a = a.T
    if transpose_b:
        b = b.T
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    if precision_level <= 0:
        out = _planned_dot(a, b, ta=ta, tb=tb)
    elif precision_level == 1:
        # on TPU with tileable shapes the Kahan carrier is the Pallas
        # kernel (compensation lives in VMEM next to the accumulator);
        # the fori_loop fallback covers CPU and ragged shapes
        out = kahan_matmul(a, b, ta=ta, tb=tb)
    else:
        out = pairwise_matmul(a, b, ta=ta, tb=tb)
    out = alpha * out
    if c is not None and beta != 0.0:
        out = out + beta * c
    return out.astype(out_dtype)


def _dtype_key(a, b):
    return str(jnp.result_type(a.dtype, b.dtype))


def _planned_dot(a, b, ta=False, tb=False):
    """Level-0 dispatch seam: the autotuner's winner for this shape,
    XLA dot otherwise (= today's static behavior)."""
    from veles_tpu.ops import autotune
    impl, cfg = autotune.gemm_plan(
        a.shape[0], b.shape[1], a.shape[1], _dtype_key(a, b),
        ta=ta, tb=tb, level=0)
    if impl == "pallas" and cfg:
        return pallas_gemm(
            a, b, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
            out_dtype=jnp.float32,
            dimension_semantics=autotune.ds_tuple(cfg),
            interpret=autotune.kernel_interpret())
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def pairwise_matmul(a, b, parts=None, ta=False, tb=False):
    """PRECISION_LEVEL 2: split-K partial sums reduced pairwise."""
    k = a.shape[-1]
    if parts is None:
        from veles_tpu.ops import autotune
        impl, cfg = autotune.gemm_plan(
            a.shape[0], b.shape[1], k, _dtype_key(a, b),
            ta=ta, tb=tb, level=2)
        if impl == "pairwise" and cfg:
            parts = cfg.get("parts")
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


def kahan_matmul(a, b, chunk=None, ta=False, tb=False):
    """PRECISION_LEVEL 1: Kahan-compensated accumulation over K chunks.

    Dispatch order: the autotuner's per-shape winner (Pallas config or
    loop chunk size); untuned, the legacy static rule — Pallas on TPU
    when the shapes tile, else an XLA ``fori_loop`` of chunked dots
    carrying the compensation."""
    if chunk is None:
        from veles_tpu.ops import autotune
        impl, cfg = autotune.gemm_plan(
            a.shape[0], b.shape[1], a.shape[1], _dtype_key(a, b),
            ta=ta, tb=tb, level=1)
        if impl == "pallas" and cfg:
            return pallas_kahan_gemm(
                a, b, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
                dimension_semantics=autotune.ds_tuple(cfg),
                interpret=autotune.kernel_interpret())
        if impl == "loop" and cfg:
            return _kahan_matmul_loop(a, b, cfg.get("chunk"))
        if _on_tpu() and _tileable(a, b):
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
# Pallas tiled GEMM (TPU): MXU-tiled with fp32 VMEM accumulator.
# ---------------------------------------------------------------------------

#: default tile sizes for the Pallas kernels (the untuned fallback —
#: the autotuner's candidate grid supersedes them per shape)
_BM, _BN, _BK = 256, 256, 512
_DS = ("parallel", "parallel", "arbitrary")


def _tileable(a, b, bm=_BM, bn=_BN, bk=_BK):
    m, k = a.shape
    n = b.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    return m % bm == 0 and n % bn == 0 and k % bk == 0


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps,
                 activation="linear"):
    @jax.named_scope("init")
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        init()

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _():
        o_ref[...] = _EPILOGUES[activation](acc_ref[...]).astype(
            o_ref.dtype)


def _gemm_bias_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
                      k_steps, activation="linear"):
    """Tiled GEMM whose output step applies bias + activation while
    the block is still in VMEM — the All2All forward epilogue the
    profile wanted fused (the separate XLA add/act pass re-reads the
    whole (M, N) product from HBM)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _():
        pre = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        o_ref[...] = _EPILOGUES[activation](pre).astype(o_ref.dtype)


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


@functools.partial(jax.jit, static_argnames=(
    "bm", "bn", "bk", "out_dtype", "dimension_semantics", "interpret"))
def pallas_kahan_gemm(a, b, bm=_BM, bn=_BN, bk=_BK, out_dtype=None,
                      dimension_semantics=_DS, interpret=False):
    """Kahan-compensated tiled MXU matmul (precision_level=1 carrier)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk or not (_on_tpu() or interpret):
        return _kahan_matmul_loop(a, b)
    k_steps = k // bk
    out_dtype = out_dtype or jnp.float32
    return pl.pallas_call(
        functools.partial(_kahan_gemm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(m * k + k * n + m * n) * a.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=(
    "bm", "bn", "bk", "out_dtype", "activation", "dimension_semantics",
    "interpret"))
def pallas_gemm(a, b, bm=_BM, bn=_BN, bk=_BK, out_dtype=None, *,
                bias=None, activation="linear", dimension_semantics=_DS,
                interpret=False):
    """Hand-tiled MXU matmul with an optional fused bias+activation
    epilogue; shapes must divide by the tile sizes (the non-tiling
    and non-TPU fallback is the equivalent XLA chain).

    Block sizes and ``dimension_semantics`` are the autotuner's
    search axes (:mod:`veles_tpu.ops.autotune`); the module-level
    defaults are only the untuned fallback."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    out_dtype = out_dtype or a.dtype
    if m % bm or n % bn or k % bk or not (_on_tpu() or interpret):
        out = jnp.dot(a, b, preferred_element_type=jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return _EPILOGUES[activation](out).astype(out_dtype)
    k_steps = k // bk
    common = dict(
        grid=(m // bm, n // bn, k_steps),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(m * k + k * n + m * n) * a.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )
    ab_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
    ]
    if bias is None:
        return pl.pallas_call(
            functools.partial(_gemm_kernel, k_steps=k_steps,
                              activation=activation),
            in_specs=ab_specs, **common)(a, b)
    return pl.pallas_call(
        functools.partial(_gemm_bias_kernel, k_steps=k_steps,
                          activation=activation),
        in_specs=ab_specs + [
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j))],
        **common)(a, b, bias.reshape(1, n))


# ---------------------------------------------------------------------------
# Fused linear layer: act(x @ w + b) with a VJP whose backward dots go
# back through the autotuned dispatch (the fc wgrad shapes are where
# the Pallas kernels historically won).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_linear(x, w, b, activation, out_dtype, cfg):
    """``act(x @ w + b)`` through the fused-epilogue Pallas kernel.

    ``cfg`` is the hashable tuple ``(bm, bn, bk, dimension_semantics,
    interpret)`` the autotuner picked (see :func:`fused_linear_cfg`).
    Differentiable: the custom VJP keeps only (x, w, y) as residuals —
    every supported epilogue's derivative is a function of the OUTPUT
    (``_EPILOGUE_GRADS``), so the pre-activation never materializes.
    """
    return _fused_linear_fwd(x, w, b, activation, out_dtype, cfg)[0]


def fused_linear_cfg(config):
    """Autotune config dict -> the hashable cfg tuple."""
    from veles_tpu.ops import autotune
    return (config["bm"], config["bn"], config["bk"],
            autotune.ds_tuple(config), autotune.kernel_interpret())


def _fused_linear_fwd(x, w, b, activation, out_dtype, cfg):
    bm, bn, bk, ds, interpret = cfg
    y = pallas_gemm(x, w, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                    bias=b, activation=activation,
                    dimension_semantics=ds, interpret=interpret)
    return y, (x, w, y)


def _fused_linear_bwd(activation, out_dtype, cfg, res, g):
    x, w, y = res
    dpre = (g.astype(jnp.float32) *
            _EPILOGUE_GRADS[activation](y.astype(jnp.float32)))
    db = jnp.sum(dpre, axis=0).astype(jnp.float32)
    # backward dots in the forward's compute dtype (the policy's MXU
    # path), routed through the same shape-aware dispatch — dgrad is
    # (M, K) x (K=N) and wgrad the thin (K, M) x (M, N) shape
    dpre_c = dpre.astype(w.dtype)
    dx = _planned_dot(dpre_c, w.T, tb=True).astype(x.dtype)
    dw = _planned_dot(x.T, dpre_c, ta=True).astype(w.dtype)
    return dx, dw, db


fused_linear.defvjp(_fused_linear_fwd, _fused_linear_bwd)
