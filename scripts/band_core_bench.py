#!/usr/bin/env python3
"""The grouped, banded attention core alone on the chip, at each
attention shape of the three cells that run it (the window and global
layers of the pre-routed cell, the window and full layers of the
Laguna cell, the LFM2 cell's heads of 64 behind zeros), bfloat16: ms a
call of the core's forward and of its gradient, and each Pallas
kernel's device time a gradient call from a trace, with its share of
the chip's peak against the products it executes (2 a block pair
forward; the backward 5 in one kernel, or 4 + 3 where dk/dv and dq are
two). A cell costs minutes of set-up; this costs one, and says how a
change to ``ops/band_attention.py`` moves the cores before any cell
runs.

    python scripts/band_core_bench.py [--root CHECKOUT]   # on a TPU host

``--root``: import ``veles_tpu`` and read ``benchmark/configs/`` from
another checkout (a parent commit unpacked under ``.checkouts/``); one
process a root, one after the other: a chip belongs to one process.
Writes ``chiprun_out/band_core_bench.txt``. Needs a TPU.
"""

import argparse
import collections
import math
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join("chiprun_out", "band_core_bench.txt")
#: the cells whose ``grouped_attention`` layers are timed, one call a
#: distinct shape, as their configuration's file describes them
CELLS = ("smallthinker-21ba3b-ep8share.pretrain16k-1seq",
         "lfm2-8b-a1b-ep4share.pretrain8k-1seq",
         "laguna-s21-ep32share.pretrain-1seq")
#: bf16 peak of a v5e, FLOP/s
PEAK = 197e12
#: products a block pair, by kernel name
PRODUCTS = {"band_attention_forward": 2, "band_attention_backward": 5,
            "band_attention_dkv": 4, "band_attention_dq": 3}
LANES = 128


def say(*parts):
    line = " ".join(str(p) for p in parts)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=HERE)
    parser.add_argument("--loops", type=int, default=10)
    args = parser.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp

    from benchmark import harness, trace_reduce
    from veles_tpu.parallel import sequence

    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU, found %s" % jax.default_backend())

    def timed(fn, *operands):
        """ms a call: best of three means over ``--loops`` calls."""
        jax.block_until_ready(fn(*operands))
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(args.loops):
                out = fn(*operands)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t) / args.loops)
        return best * 1e3

    def kernel_ms(fn, operands, name):
        """Device self time a call by kernel (the events' names, to the
        first dot), everything else as ``xla``, over ``--loops`` traced
        calls."""
        trace_dir = os.path.join(root, ".veles_cache", "band_core_bench",
                                 name)
        jax.block_until_ready(fn(*operands))
        with jax.profiler.trace(trace_dir):
            for _ in range(args.loops):
                out = fn(*operands)
            jax.block_until_ready(out)
        reduced = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir))
        times = collections.Counter()
        for op in reduced.devices[0].ops:
            kernel = re.match(r"%?(band_attention_[a-z]+)", op.name)
            times[kernel.group(1) if kernel else "xla"] += \
                op.self_ns / 1e6 / args.loops
        return times

    say("root %s (%s) on %s" % (args.root, os.path.dirname(sequence.__file__),
                                jax.devices()[0].device_kind))
    bench = harness.Benchmark(root)
    for cell in CELLS:
        config = bench.config(bench.cell(cell))
        batch, seq = config["batch"], config["layers"][0]["positions"]
        shapes = sorted({(d["heads"], d["kv_heads"], d["head_dim"],
                          d["window"], d["block"])
                         for d in config["layers"]
                         if d["type"] == "grouped_attention"},
                        key=lambda s: (s[3] is None, s))
        for heads, kv_heads, head_dim, window, block in shapes:
            keys = jax.random.split(jax.random.PRNGKey(40), 4)
            q, do = (jax.random.normal(key, (batch, heads, seq, head_dim),
                                       jnp.bfloat16) for key in keys[:2])
            k, v = (jax.random.normal(key, (batch, kv_heads, seq, head_dim),
                                      jnp.bfloat16) for key in keys[2:])
            scale = 1.0 / head_dim ** 0.5
            unit = "core_%d_%s" % (heads, window)

            def core(q, k, v):
                return sequence.causal_attention(q, k, v, scale, block,
                                                 unit=unit, window=window)

            def loss(q, k, v, do):
                return jnp.sum(core(q, k, v).astype(jnp.float32)
                               * do.astype(jnp.float32))

            forward = jax.jit(core)
            grad = jax.jit(jax.grad(loss, (0, 1, 2)))
            pairs = sequence.core_blocks(seq, block, window)
            kv = math.gcd(seq, sequence.FUSED_KV_BLOCK)
            dim = head_dim + -head_dim % LANES
            # FLOPs of one product over every block pair the kernels run
            product = 2.0 * block * kv * dim * pairs * heads * batch
            fwd_ms, grad_ms = timed(forward, q, k, v), timed(grad, q, k, v,
                                                            do)
            times = kernel_ms(grad, (q, k, v, do), unit)
            say("%s: %d x %d on %d of %d, window %s, %d block pairs a "
                "head: forward %.3f ms, forward + gradient %.3f ms (host)"
                % (cell, seq, heads, kv_heads, head_dim, window, pairs,
                   fwd_ms, grad_ms))
            for kernel, ms in sorted(times.items()):
                share = "" if kernel == "xla" else \
                    ", %d products a pair, %.1f%% of peak" % (
                        PRODUCTS[kernel],
                        100 * PRODUCTS[kernel] * product / (ms * 1e-3)
                        / PEAK)
                say("    %-24s %8.3f ms a gradient call%s" % (
                    kernel, ms, share))
            say("    %-24s %8.3f ms" % ("device, all", sum(times.values())))


if __name__ == "__main__":
    sys.exit(main())
