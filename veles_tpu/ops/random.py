"""Device random number generation.

The reference fills buffers with xorshift1024* on the GPU
(``ocl/random.cl:1-125``, ``cuda/random.cu:46-73``) seeded from the host
RandomGenerator. Here:

* :func:`xorshift128plus` — exact host implementation of the xorshift128+
  step the reference exposes (``veles/prng/random_generator.py:273``),
  used for state-evolution parity tests;
* :func:`uniform` — counter-based ``jax.random`` fill (the idiomatic TPU
  path: stateless, splittable, reproducible across meshes).
"""

import functools

import jax
import jax.numpy as jnp
import numpy

_U64 = (1 << 64) - 1


def xorshift128plus(state):
    """One xorshift128+ step on a 2-element uint64 state (host-side).

    Returns (new_state, output). Bit-exact with the reference's
    generator so stream parity can be asserted in tests.
    """
    s0, s1 = int(state[0]), int(state[1])
    x = s0
    y = s1
    x ^= (x << 23) & _U64
    x ^= x >> 17
    x ^= y ^ (y >> 26)
    new = numpy.array([y, x], dtype=numpy.uint64)
    return new, (x + y) & _U64


def fill_xorshift(state, count):
    """Fill ``count`` uint64s, evolving the 2-word state (host loop)."""
    out = numpy.empty(count, dtype=numpy.uint64)
    for i in range(count):
        state, value = xorshift128plus(state)
        out[i] = value
    return state, out


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def uniform(key, shape, vmin=0.0, vmax=1.0, dtype=jnp.float32):
    """Uniform fill via JAX's counter-based PRNG."""
    return jax.random.uniform(key, shape, dtype=dtype, minval=vmin,
                              maxval=vmax)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def normal(key, shape, mean=0.0, stddev=1.0, dtype=jnp.float32):
    return mean + stddev * jax.random.normal(key, shape, dtype=dtype)
