#!/usr/bin/env python3
"""The selected attention core's search for a block's keys against
``lax.top_k`` on the chip: ms a call of
``veles_tpu.parallel.sequence.select_keys`` (the exact k-th largest of
a row by 32 passes over its float32 bits, no sort) and of the plain
form the benchmark's reference uses (``lax.top_k`` of the row, its
indices scattered into a mask), on one block of 512 queries against
8,192 keys, 2,048 selected: the last block of a sequence of 8,192,
float32 scores. The two masks are compared, so the number is of two
ways to the same selection. PR 33's review asked for the reading.

    chiprun -- python scripts/select_keys_bench.py

Writes ``chiprun_out/select_keys_bench.txt``. Needs a TPU.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join("chiprun_out", "select_keys_bench.txt")


def say(*parts):
    line = " ".join(str(p) for p in parts)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rows", type=int, default=512)
    parser.add_argument("--keys", type=int, default=8192)
    parser.add_argument("--top-k", type=int, default=2048)
    parser.add_argument("--loops", type=int, default=20)
    parser.add_argument("--backend", default="tpu")
    args = parser.parse_args()
    sys.path.insert(0, HERE)

    import jax
    import jax.numpy as jnp
    import numpy

    from benchmark.reference.indexed_moe_lm import select as by_top_k
    from veles_tpu.parallel.sequence import select_keys

    if jax.default_backend() != args.backend:
        raise SystemExit("needs a %s, found %s" % (
            args.backend, jax.default_backend()))
    start = args.keys - args.rows
    scores = jax.random.normal(jax.random.PRNGKey(33),
                               (1, args.rows, args.keys), jnp.float32)
    # a sum of relus has exact zeros and ties: a row in eight all ties
    scores = jnp.where(jnp.arange(args.rows)[None, :, None] % 8 == 0,
                       0.0, jnp.round(scores * 64) / 64)
    ways = {
        "search over the bits (select_keys)": jax.jit(
            lambda s: select_keys(s, start, args.top_k)),
        "lax.top_k scattered into a mask": jax.jit(
            lambda s: by_top_k(s, start, args.top_k)),
    }
    say("select_keys_bench: (%d, %d) float32 scores from position %d, "
        "top %d, %s" % (args.rows, args.keys, start, args.top_k,
                        jax.devices()[0].device_kind))
    masks = []
    for name, fn in ways.items():
        t0 = time.perf_counter()
        masks.append(numpy.asarray(fn(scores)))
        compiled = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.loops):
            out = fn(scores)
        out.block_until_ready()
        say("  %-36s %.3f ms a call (first call %.1f s)" % (
            name, (time.perf_counter() - t0) / args.loops * 1e3,
            compiled))
    say("  the two masks are equal: %s; keys a query: %s" % (
        bool((masks[0] == masks[1]).all()),
        sorted(set(masks[0].sum(-1).ravel().tolist()))))


if __name__ == "__main__":
    main()
