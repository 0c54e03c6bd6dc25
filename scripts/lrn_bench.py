#!/usr/bin/env python3
"""Fused-Pallas-LRN vs XLA benchmark (VERDICT r2 item #1).

Times forward and forward+backward at the AlexNet LRN shapes, f32 and
bf16, chained in-jit and ended by one scalar read that waits for the
chain. Appended to docs/PERF.md by hand.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_fwd(fn, x, iters=50):
    """The inputs are jit ARGUMENTS, never closure captures — captured
    arrays bake into the HLO as 150 MB literals."""
    import jax
    import jax.numpy as jnp

    def chain_fn(x):
        def body(c, _):
            y = fn(x + c.astype(x.dtype))
            # consume EVERY element: a [0]-slice carry lets XLA
            # dead-code-eliminate the bulk of a transparent formulation
            # while an opaque Pallas kernel still does the real work —
            # the sum costs one fused pass, identically for everyone
            return jnp.sum(y.astype(jnp.float32)) * 1e-30, None
        return jax.lax.scan(body, jnp.float32(0), None, length=iters)[0]

    chain = jax.jit(chain_fn)
    float(chain(x))
    t = time.time()
    float(chain(x))
    return (time.time() - t) / iters * 1000


def bench_fwdbwd(fn, x, g, iters=50):
    import jax
    import jax.numpy as jnp

    def chain_fn(x, g):
        def body(c, _):
            y, vjp = jax.vjp(fn, x + c.astype(x.dtype))
            dx, = vjp(g)
            return (jnp.sum(y.astype(jnp.float32)) +
                    jnp.sum(dx.astype(jnp.float32))) * 1e-30, None
        return jax.lax.scan(body, jnp.float32(0), None, length=iters)[0]

    chain = jax.jit(chain_fn)
    float(chain(x, g))
    t = time.time()
    float(chain(x, g))
    return (time.time() - t) / iters * 1000


def main():
    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu.nn.normalization import _lrn_slices
    from veles_tpu.ops.lrn import lrn_fused

    print("platform:", jax.devices()[0].platform, file=sys.stderr)
    rng = numpy.random.RandomState(0)
    shapes = [("conv1 (128,55,55,96)", (128, 55, 55, 96)),
              ("conv2 (128,27,27,256)", (128, 27, 27, 256))]
    print("| shape dtype | XLA fwd | Pallas fwd | XLA fwd+bwd | "
          "Pallas fwd+bwd |\n|---|---|---|---|---|", flush=True)
    for name, shape in shapes:
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jnp.asarray(rng.randn(*shape), dtype=dtype)
            g = jnp.asarray(rng.randn(*shape), dtype=dtype)
            xla = lambda v: _lrn_slices(v)
            pallas = lambda v: lrn_fused(v)
            cells = []
            for label, t in (
                    ("xla fwd", lambda: bench_fwd(xla, x)),
                    ("pallas fwd", lambda: bench_fwd(pallas, x)),
                    ("xla fb", lambda: bench_fwdbwd(xla, x, g)),
                    ("pallas fb", lambda: bench_fwdbwd(pallas, x, g))):
                print("  %s %s %s..." % (name, jnp.dtype(dtype).name,
                                         label),
                      file=sys.stderr, flush=True)
                try:
                    cells.append("%.2f ms" % t())
                except Exception as e:
                    cells.append("error: %s" % type(e).__name__)
            print("| %s %s | %s |" % (
                name, jnp.dtype(dtype).name, " | ".join(cells)),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
