"""Matrix reductions (``ocl/matrix_reduce.cl``, ``cuda/matrix_reduce.cu``).

The reference runs a two-stage tree reduction over matrix columns on the
GPU. On TPU, XLA lowers ``jnp.sum``/``jnp.max`` onto the VPU with its own
tree schedule, so the *public contract* (reduce a matrix along an axis
with a selectable op) is all that must survive.
"""

import functools

import jax
import jax.numpy as jnp

_OPS = {
    "sum": jnp.sum,
    "max": jnp.max,
    "min": jnp.min,
    "mean": jnp.mean,
    "argmax": jnp.argmax,
    "l2": lambda x, axis: jnp.sqrt(jnp.sum(jnp.square(x), axis=axis)),
}


@functools.partial(jax.jit, static_argnames=("op", "axis"))
def matrix_reduce(x, op="sum", axis=0):
    """Reduce a matrix along ``axis`` with ``op`` (fp32 accumulation)."""
    fn = _OPS[op]
    if op in ("argmax",):
        return fn(x, axis=axis)
    return fn(x.astype(jnp.float32), axis=axis)
