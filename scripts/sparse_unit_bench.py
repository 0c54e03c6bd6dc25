#!/usr/bin/env python3
"""ONE dropless sparse unit alone on the chip, at each token cell's
published shape under the bfloat16 policy: ms a call of its forward
(what a validation step runs) and of the gradient of the rematerialized
unit (what a train step runs), with the routing that parameters at
std 0.02 give (about ``tokens * top_k * held / n_experts`` rows, under
the cell's bound). A whole cell costs eight minutes of set-up; this
costs one, and says which way a change to ``nn/moe.py`` moves before a
cell is run. PR 32 chose the combine's form with it.

    chiprun -- python scripts/sparse_unit_bench.py [--root CHECKOUT]

``--root``: import ``veles_tpu`` and read ``benchmark/configs/`` from
another checkout (a parent commit unpacked under ``.checkouts/``: the
unit's nine shapes are spelled here, so any commit with the dropless
layer will do); one process a root, one after the other: a chip
belongs to one process. Writes
``chiprun_out/sparse_unit_bench.txt``. Needs a TPU.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join("chiprun_out", "sparse_unit_bench.txt")
#: the token cells whose first ``moe`` layer is timed, as its
#: configuration's file under ``benchmark/configs/`` describes it
CELLS = ("glm47flash-ep8share.pretrain4k",
         "laguna-s21-ep32share.pretrain-1seq")


def say(*parts):
    line = " ".join(str(p) for p in parts)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=HERE)
    parser.add_argument("--loops", type=int, default=20)
    args = parser.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from veles_tpu import remat
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.nn import precision
    from veles_tpu.nn.moe import MoEForward
    from veles_tpu.train.step import device_scope, unit_tag

    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU, found %s" % jax.default_backend())
    precision.set_policy("bfloat16")

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

    say("root %s (%s) on %s" % (args.root, os.path.dirname(remat.__file__),
                                jax.devices()[0].device_kind))
    bench = harness.Benchmark(os.path.abspath(args.root))
    for cell in CELLS:
        config = bench.config(bench.cell(cell))
        descr = next(d for d in config["layers"] if d["type"] == "moe")
        batch, seq = config["batch"], config["layers"][0]["positions"]
        dim, hidden = config["hidden_size"], descr["hidden"]
        held, shared = descr["experts_held"][1], descr["shared_experts"]
        fwd = MoEForward(DummyLauncher(), name="moe4", **{
            k: v for k, v in descr.items() if k not in ("type", "remat")})
        matrices = {"weights": (dim, fwd.n_experts),
                    "gate": (held, dim, hidden), "up": (held, dim, hidden),
                    "down": (held, hidden, dim),
                    "shared_gate": (shared, dim, hidden),
                    "shared_up": (shared, dim, hidden),
                    "shared_down": (shared, hidden, dim)}
        keys = jax.random.split(jax.random.PRNGKey(32), len(matrices) + 1)
        params = {k: 0.02 * jax.random.normal(key, dims, jnp.float32)
                  for key, (k, dims) in zip(keys, sorted(matrices.items()))}
        params.update(norm=jnp.ones((dim,)),
                      select_bias=jnp.zeros((fwd.n_experts,)))
        x = jax.random.normal(keys[-1], (batch, seq, dim), jnp.bfloat16)
        tag = unit_tag(4, fwd)

        def forward(p, x):
            with device_scope(tag):
                return fwd.apply_step(p, x, None)

        def loss(p, x):
            with device_scope(tag):
                (y, _), _ = remat.checkpoint(
                    lambda p, x: fwd.apply_step(p, x, None))(p, x)
            # a cotangent that differs by element, as a block's is
            return jnp.sum(y.astype(jnp.float32) * x.astype(jnp.float32))

        forward, step = jax.jit(forward), jax.jit(
            jax.value_and_grad(loss, (0, 1)))
        _, stats = forward(params, x)
        say("%s: %d tokens, top-%d of %d, %d held, bound %d; %d rows "
            "routed here: forward %.3f ms, forward + gradient %.3f ms" % (
                cell, batch * seq, fwd.top_k, fwd.n_experts, held,
                fwd.dispatch_rows, int(stats["expert_counts"][:held].sum()),
                timed(forward, params, x), timed(step, params, x)))


if __name__ == "__main__":
    sys.exit(main())
