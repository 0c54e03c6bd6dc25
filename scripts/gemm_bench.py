#!/usr/bin/env python3
"""GEMM discipline benchmark (VERDICT r1 item #10).

Times XLA dot, pallas_gemm, pallas_kahan_gemm and the fori-loop Kahan
at the reference's 1500^2 computing-power shape
(``veles/accelerated_units.py:713-778``) and the AlexNet fc shapes,
printing a Markdown table (appended to docs/PERF.md by hand).

``--autotune`` instead runs the :mod:`veles_tpu.ops.autotune` search
across the flagship model's ACTUAL GEMM shapes (fc6/fc7/fc8 forward,
wgrad and dgrad at the bench batch, plus the fused bias+activation
forward variants) and prints the per-shape XLA-vs-best-Pallas table
from the resulting cache entries — the winners persist to the
per-device cache file, so a subsequent ``bench.py`` run picks them up
with zero measurements. ``--dtype`` selects the compute dtype
(default bfloat16, the flagship policy's MXU dtype).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the flagship AlexNet fc GEMMs at the bench batch (B=128):
#: (layer, pass, activation-or-None, (M, K, N), (ta, tb)). fc6:
#: 9216->4096 relu, fc7: 4096->4096 relu, fc8: 4096->1000 linear
#: (softmax head). wgrad is x.T @ dpre (M=fan_in, K=batch, ta=1) and
#: dgrad is dpre @ w.T (tb=1) — the flags must match the keys the
#: fused-linear backward consults at runtime, or the pre-tuned
#: winners never hit. Shared with scripts/profile_step.py --tune.
def flagship_gemm_shapes(batch=128):
    fcs = [("fc6", 9216, 4096, "relu"),
           ("fc7", 4096, 4096, "relu"),
           ("fc8", 4096, 1000, "linear")]
    out = []
    for name, fin, fout, act in fcs:
        out.append((name + " fwd", "gemm", None, (batch, fin, fout),
                    (0, 0)))
        out.append((name + " fwd+epilogue", "linear", act,
                    (batch, fin, fout), (0, 0)))
        out.append((name + " wgrad", "gemm", None, (fin, batch, fout),
                    (1, 0)))
        out.append((name + " dgrad", "gemm", None, (batch, fout, fin),
                    (0, 1)))
    return out


def bench(fn, a, b, iters=30):
    """Chained in-jit iterations, ended by one scalar read that waits
    for the chain: per-call timing would count one dispatch per
    kernel. The scalar carry serializes steps and defeats CSE."""
    import jax
    import jax.numpy as jnp

    def body(c, _):
        out = fn(a + c, b)
        return out[0, 0] * 1e-30, None

    chain = jax.jit(lambda: jax.lax.scan(
        body, jnp.float32(0), None, length=iters)[0])
    float(chain())  # compile + force
    t = time.time()
    float(chain())
    dt = time.time() - t
    flops = 2 * a.shape[0] * a.shape[1] * b.shape[1] * iters
    return flops / dt / 1e12, dt / iters * 1000


def autotune_main(dtype="bfloat16", batch=128, out_dtype=None):
    """Search the flagship shapes, then print the per-shape table.

    ``dtype`` is the compute (operand) dtype and ``out_dtype`` the
    layer-output dtype — they must match the active precision policy's
    (compute, keep-or-accum) pair or the persisted ``linear`` keys
    will never be consulted at runtime (profile_step.py --tune derives
    both from the policy). Default: out_dtype = dtype, which is right
    for the uniform float32 and bfloat16 policies."""
    os.environ.setdefault("VELES_AUTOTUNE", "search")
    from veles_tpu.ops import autotune

    out_dtype = out_dtype or dtype

    print("autotune: mode=%s device=%s cache=%s"
          % (autotune.mode(), autotune.device_kind(),
             autotune.cache_path()), file=sys.stderr, flush=True)
    if not autotune.tunable():
        print("NOT TUNABLE here (no TPU and no VELES_AUTOTUNE_FORCE): "
              "plans will fall back without measuring", file=sys.stderr)

    rows = ["| shape | M x K x N | XLA | best Pallas | winner |",
            "|---|---|---|---|---|"]
    for label, op, act, (m, k, n), (ta, tb) in \
            flagship_gemm_shapes(batch):
        t0 = time.time()
        if op == "linear":
            impl, cfg = autotune.linear_plan(m, n, k, dtype, act,
                                             out_dtype)
        else:
            impl, cfg = autotune.gemm_plan(m, n, k, dtype, ta=ta,
                                           tb=tb, level=0)
        key_fields = dict(m=m, n=n, k=k, dtype=dtype)
        if op == "linear":
            key_fields.update(act=str(act), out=out_dtype)
        else:
            key_fields.update(ta=ta, tb=tb)
        entry = autotune.get_cache().get(
            autotune._key(op if op == "linear" else "gemm",
                          **key_fields)) or {}
        impl_ms = entry.get("impl_ms", {})
        flops = 2.0 * m * n * k

        def tfs(ms):
            return "%.1f TF/s" % (flops / (ms * 1e-3) / 1e12) if ms \
                else "-"
        win = impl if not cfg else "%s %s" % (impl, {
            k2: v for k2, v in cfg.items() if v is not None} or "")
        rows.append("| %s | %dx%dx%d | %s | %s | %s |" % (
            label, m, k, n, tfs(impl_ms.get("xla")),
            tfs(min((v for k2, v in impl_ms.items() if k2 != "xla"),
                    default=None) if impl_ms else None), win))
        print("%s  (%.1fs)" % (rows[-1], time.time() - t0),
              file=sys.stderr, flush=True)
    print("\n".join(rows))
    # the LRN/col-reduce plans are only CONSULTED from inside a jit
    # trace at runtime (where _plan defers searching), so this eager
    # sweep is what creates their cache entries: the flagship LRN
    # row-views (conv1 55x55x96, conv2 27x27x256 at the bench batch,
    # exercised by the VELES_LRN=pallas ablation) and the fc-width
    # column reduces
    for rows_, c in ((batch * 55 * 55, 96), (batch * 27 * 27, 256)):
        for which in ("fwd", "bwd"):
            t0 = time.time()
            impl, cfg = autotune.lrn_plan(rows_, c, dtype, which)
            print("lrn_%s %dx%d -> %s %s  (%.1fs)"
                  % (which, rows_, c, impl, cfg or "",
                     time.time() - t0), file=sys.stderr, flush=True)
    for n in (1000, 4096):
        t0 = time.time()
        impl, cfg = autotune.reduce_plan(batch, n, dtype)
        print("col_reduce %dx%d -> %s %s  (%.1fs)"
              % (batch, n, impl, cfg or "", time.time() - t0),
              file=sys.stderr, flush=True)
    s = autotune.summary()
    print("\nsearches=%d hits=%d misses=%d -> %s"
          % (s["searches"], s["hits"], s["misses"], s["path"]),
          file=sys.stderr)
    return 0


def main():
    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu.ops.gemm import (_kahan_matmul_loop, pallas_gemm,
                                    pallas_kahan_gemm)

    rng = numpy.random.RandomState(0)
    shapes = [
        ("1500^2 (reference computing_power)", (1500, 1500, 1500)),
        ("AlexNet fc6 fwd (128x9216 @ 9216x4096)", (128, 9216, 4096)),
        ("AlexNet fc7 fwd (128x4096 @ 4096x4096)", (128, 4096, 4096)),
        ("AlexNet fc6 wgrad (9216x128 @ 128x4096)", (9216, 128, 4096)),
        ("4096^3 (tileable square)", (4096, 4096, 4096)),
    ]
    xla = jax.jit(lambda a, b: jnp.dot(
        a, b, preferred_element_type=jnp.float32))
    kloop = jax.jit(_kahan_matmul_loop)
    rows = ["| shape | XLA dot | pallas_gemm | pallas Kahan | "
            "fori Kahan |", "|---|---|---|---|---|"]
    for name, (m, k, n) in shapes:
        a = jnp.asarray(rng.rand(m, k).astype("f") - 0.5)
        b = jnp.asarray(rng.rand(k, n).astype("f") - 0.5)
        cells = []
        for fname, fn in (("xla", xla), ("pallas", pallas_gemm),
                          ("pallas_kahan", pallas_kahan_gemm),
                          ("kahan_loop", kloop)):
            print("  %s %s..." % (name, fname), file=sys.stderr,
                  flush=True)
            try:
                tf, ms = bench(fn, a, b)
                cells.append("%.1f TF/s (%.2f ms)" % (tf, ms))
            except Exception as e:
                cells.append("error: %s" % type(e).__name__)
        rows.append("| %s | %s |" % (name, " | ".join(cells)))
        print(rows[-1], flush=True)
    print("\n".join(rows[:2] + rows[2:]))
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--autotune", action="store_true",
                        help="run the shape search over the flagship "
                             "GEMMs and persist winners to the "
                             "per-device autotune cache")
    parser.add_argument("--dtype", default="bfloat16",
                        help="compute dtype for --autotune")
    parser.add_argument("--out-dtype", default=None,
                        help="layer-output dtype for the fused-"
                             "epilogue search (default: --dtype)")
    parser.add_argument("--batch", type=int, default=128)
    cli = parser.parse_args()
    sys.exit(autotune_main(cli.dtype, cli.batch, cli.out_dtype)
             if cli.autotune else main())
