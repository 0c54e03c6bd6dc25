#!/usr/bin/env python3
"""The grouped attention cores alone on the chip, at each attention
shape of the cells that run the repo's kernels (the window and global
layers of the pre-routed cell, the window and full layers of the
Laguna cell, the LFM2 cell's heads of 64 behind zeros, the Keye
cell's core under its index's selection), bfloat16: ms a call of the
core's forward and of its gradient, and each Pallas kernel's device
time a gradient call from a trace, with its share of the chip's peak
against the products it executes (2 a block pair forward; the
backward 5 in one kernel, or 4 + 3 where dk/dv and dq are two; the
selected core's probabilities 1). The selected core is timed whole,
as a train step runs it: the forward with the index's objective and
the gradient of both, the index, the search and the objective in XLA
(``xla``). A cell costs minutes of set-up; this costs one, and says
how a change to ``ops/band_attention.py`` or
``ops/select_attention.py`` moves the cores before any cell runs.

    python scripts/band_core_bench.py [--root CHECKOUT] [--cell NAME ...]
        [--numerics]                                   # on a TPU host

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
         "laguna-s21-ep32share.pretrain-1seq",
         "keye-vl2-ep8share.pretrain8k-1seq")
#: bf16 peak of a v5e, FLOP/s
PEAK = 197e12
#: products a block pair, by kernel name
PRODUCTS = {"band_attention_forward": 2, "band_attention_backward": 5,
            "band_attention_dkv": 4, "band_attention_dq": 3,
            "select_attention_forward": 2,
            "select_attention_probabilities": 1,
            "select_attention_backward": 5}
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
    parser.add_argument("--cell", action="append", choices=CELLS)
    parser.add_argument("--numerics", action="store_true")
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
            kernel = re.match(r"%?((?:band|select)_attention_[a-z]+)",
                              op.name)
            times[kernel.group(1) if kernel else "xla"] += \
                op.self_ns / 1e6 / args.loops
        return times

    say("root %s (%s) on %s" % (args.root, os.path.dirname(sequence.__file__),
                                jax.devices()[0].device_kind))
    bench = harness.Benchmark(root)
    for cell in args.cell or CELLS:
        config = bench.config(bench.cell(cell))
        batch, seq = config["batch"], config["layers"][0]["positions"]
        shapes = sorted({(d["heads"], d["kv_heads"], d["head_dim"],
                          d["window"], d["block"],
                          tuple(sorted((d.get("index") or {}).items())))
                         for d in config["layers"]
                         if d["type"] == "grouped_attention"},
                        key=lambda s: (s[3] is None, s))
        for heads, kv_heads, head_dim, window, block, index in shapes:
            index = dict(index)
            keys = jax.random.split(jax.random.PRNGKey(40), 7)
            q, do = (jax.random.normal(key, (batch, heads, seq, head_dim),
                                       jnp.bfloat16) for key in keys[:2])
            k, v = (jax.random.normal(key, (batch, kv_heads, seq, head_dim),
                                      jnp.bfloat16) for key in keys[2:4])
            scale = 1.0 / head_dim ** 0.5
            unit = "core_%d_%s" % (heads, window)
            operands = (q, k, v)
            if index:
                # the index's queries, its one key head, its weights
                operands += (
                    jax.random.normal(keys[4], (batch, index["heads"], seq,
                                                index["head_dim"]),
                                      jnp.bfloat16),
                    jax.random.normal(keys[5], (batch, seq,
                                                index["head_dim"]),
                                      jnp.bfloat16),
                    jax.random.normal(keys[6], (batch, index["heads"], seq),
                                      jnp.float32) / index["heads"])

            def core(q, k, v, *index_operands):
                if not index:
                    return sequence.causal_attention(
                        q, k, v, scale, block, unit=unit, window=window)
                return sequence.causal_attention(
                    q, k, v, scale, block, unit=unit, index=index_operands,
                    top_k=index["top_k"])

            def loss(do, *operands):
                out = core(*operands)
                term = 0.0
                if index:
                    out, term = out[0], jnp.sum(out[1])
                return jnp.sum(out.astype(jnp.float32)
                               * do.astype(jnp.float32)) + term

            if index and args.numerics:
                numerics(sequence, do, operands, scale, block, index)
            forward = jax.jit(core)
            # the selected core's forward gives the objective's value,
            # as a train step's does
            grad = jax.jit((jax.value_and_grad if index else jax.grad)(
                loss, tuple(range(1, len(operands) + 1))))
            pairs = sequence.core_blocks(seq, block, window,
                                         fused=not index)
            kv = block if index else math.gcd(seq, sequence.FUSED_KV_BLOCK)
            dim = head_dim + -head_dim % LANES
            # FLOPs of one product over every block pair the kernels run
            product = 2.0 * block * kv * dim * pairs * heads * batch
            fwd_ms = timed(forward, *operands)
            grad_ms = timed(grad, do, *operands)
            times = kernel_ms(grad, (do,) + operands, unit)
            say("%s: %d x %d on %d of %d, window %s, index %s, %d block "
                "pairs a head: forward %.3f ms, forward + gradient %.3f ms "
                "(host)" % (cell, seq, heads, kv_heads, head_dim, window,
                            index or None, pairs, fwd_ms, grad_ms))
            for kernel, ms in sorted(times.items()):
                share = "" if kernel == "xla" else \
                    ", %d products a pair, %.1f%% of peak" % (
                        PRODUCTS[kernel],
                        100 * PRODUCTS[kernel] * product / (ms * 1e-3)
                        / PEAK)
                say("    %-24s %8.3f ms a gradient call%s" % (
                    kernel, ms, share))
            say("    %-24s %8.3f ms" % ("device, all", sum(times.values())))


def numerics(sequence, do, operands, scale, block, index):
    """Norm of the difference over the norm of XLA's blocks' result, of
    the selected core's output, objective and six gradients, its
    kernels against XLA's blocks, both on the chip."""
    import jax
    import jax.numpy as jnp

    def run(kernels):
        def objective(*operands):
            out, loss, _, _ = sequence.selected_attention(
                *operands, scale, block, index["top_k"], True, kernels)
            return jnp.sum(out.astype(jnp.float32)
                           * do.astype(jnp.float32)) + jnp.sum(loss), \
                (out, loss)
        (_, values), grads = jax.jit(jax.value_and_grad(
            objective, tuple(range(6)), has_aux=True))(*operands)
        return values + grads

    for name, got, want in zip(("out", "loss") + tuple(
            "d" + c for c in "qkvabc"), run(True), run(False)):
        got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
        say("    numerics %-4s |kernels - xla| / |xla| %.3e" % (
            name, float(jnp.linalg.norm(got - want)
                        / jnp.linalg.norm(want))))


if __name__ == "__main__":
    sys.exit(main())
