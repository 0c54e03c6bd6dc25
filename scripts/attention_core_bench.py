"""The causal attention core alone on the chip: candidates against
``blockwise_attention`` at the token cell's shape, forward and
``jax.grad``, block sizes swept; numerics against the float32 oracle;
and whether the kernels' device events keep the scope they were traced
under (``tf_op``, what ``benchmark/readers/trace_lm.py`` joins by).

    chiprun -- python scripts/attention_core_bench.py [--only NAME]

``NAME``: ``blockwise``, ``merged`` (``fused_attention`` as the program
calls it), ``flash-fwd`` / ``flash-grad`` (the kernel's block sizes
swept), ``numerics``, ``trace``. PR 28 chose ``FUSED_KV_BLOCK`` with it.

Writes ``chiprun_out/attention_core_bench.txt``. Needs a TPU."""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as fa

from veles_tpu.parallel import sequence
from veles_tpu.train.step import device_scope

B, H, S, D = 2, 20, 4096, 256
SCALE = 1.0 / D ** 0.5
OUT = os.path.join("chiprun_out", "attention_core_bench.txt")


def say(*parts):
    line = " ".join(str(p) for p in parts)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(fn, *args, loops=10, repeats=3):
    """ms a call: best of ``repeats`` means over ``loops`` calls."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t = time.perf_counter()
        for _ in range(loops):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / loops)
    return best * 1e3


def scoped(core):
    """``core`` under the scope a unit's core carries in a fused step."""
    def fn(q, k, v):
        with device_scope("u03.latent_attention3"), \
                jax.named_scope("core"):
            return core(q, k, v)
    return fn


def grad_of(core):
    return jax.jit(jax.grad(
        lambda q, k, v, do: jnp.sum(
            core(q, k, v).astype(jnp.float32) * do.astype(jnp.float32)),
        argnums=(0, 1, 2)))


def flash(bq, bkm, bk, dkv=None, dq=None):
    """dkv: (q_major, q, k_major, k); dq: (q, k_major, k)."""
    dkv = dkv or (bq, bq, bk, bk)
    dq = dq or (bq, bk, bk)
    sizes = fa.BlockSizes(
        block_q=bq, block_k_major=bkm, block_k=bk, block_b=1,
        block_q_major_dkv=dkv[0], block_q_dkv=dkv[1],
        block_k_major_dkv=dkv[2], block_k_dkv=dkv[3],
        block_q_dq=dq[0], block_k_major_dq=dq[1], block_k_dq=dq[2])
    return scoped(functools.partial(
        fa.flash_attention, causal=True, sm_scale=SCALE,
        block_sizes=sizes))


def run(name, fn, *args):
    try:
        say("%-64s %8.3f ms" % (name, timed(fn, *args)))
    except Exception as e:  # a refused tiling, VMEM: report, go on
        say("%-64s FAILED %s" % (name, str(e).replace("\n", " ")[:300]))


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("needs a TPU, found %s" % dev.platform)
    say("device", dev.device_kind, "jax", jax.__version__)
    keys = jax.random.split(jax.random.PRNGKey(28), 4)
    q, k, v, do = (jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
                   for key in keys)

    def want(name):
        return not args.only or args.only in name

    base = scoped(lambda q, k, v: sequence.blockwise_attention(
        q, k, v, SCALE, 512))
    merged = scoped(lambda q, k, v: sequence.fused_attention(
        q, k, v, SCALE, 512))
    if want("blockwise"):
        run("blockwise 512 fwd", jax.jit(base), q, k, v)
        run("blockwise 512 grad (fwd+bwd)", grad_of(base), q, k, v, do)
    if want("merged"):
        run("fused_attention 512 fwd", jax.jit(merged), q, k, v)
        run("fused_attention 512 grad (fwd+bwd)", grad_of(merged),
            q, k, v, do)

    if want("flash-fwd"):
        for cfg in [(512, 512, 512), (512, 1024, 512), (512, 1024, 1024),
                    (512, 2048, 512), (512, 512, 256), (512, 1024, 256),
                    (512, 4096, 512), (1024, 1024, 512),
                    (1024, 1024, 1024), (256, 512, 512), (256, 1024, 512),
                    (2048, 512, 512)]:
            run("flash-fwd q=%d kmajor=%d k=%d" % cfg,
                jax.jit(flash(*cfg)), q, k, v)
    if want("flash-grad"):
        fwd = (512, 1024, 512)
        for dkv, dq in [
                ((512, 512, 512, 512), (512, 512, 512)),
                ((512, 512, 1024, 512), (512, 1024, 512)),
                ((512, 256, 512, 512), (512, 512, 256)),
                ((512, 512, 512, 256), (512, 1024, 256)),
                ((512, 256, 512, 256), (512, 2048, 512)),
                ((512, 128, 512, 512), (512, 512, 128)),
                ((512, 512, 512, 128), (512, 4096, 512)),
                ((1024, 512, 512, 512), (1024, 512, 512)),
                ((1024, 256, 1024, 256), (1024, 1024, 256)),
                ((2048, 512, 512, 512), (256, 1024, 512)),
                ((512, 512, 256, 256), (256, 512, 512)),
                ((4096, 512, 512, 512), (512, 1024, 1024))]:
            run("flash-grad fwd=%s dkv(qM,q,kM,k)=%s dq(q,kM,k)=%s" % (
                fwd, dkv, dq), grad_of(flash(*fwd, dkv=dkv, dq=dq)),
                q, k, v, do)

    if want("numerics"):
        # the float32 oracle a head at a time would be fairest; the
        # whole square of one batch element fits: 20 x 4096^2 x 4 B
        f32 = [t[:1].astype(jnp.float32) for t in (q, k, v, do)]
        oracle = functools.partial(sequence.local_attention, causal=True,
                                   scale=SCALE)
        # the TPU's default float32 product is one bf16 pass: it
        # would round the oracle's probabilities as a candidate's are
        with jax.default_matmul_precision("highest"):
            want_out = jax.jit(oracle)(*f32[:3])
            want_grads = grad_of(oracle)(*f32)
        one = [t[:1] for t in (q, k, v, do)]
        for name, core in (("blockwise", base), ("fused", merged)):
            out = jax.jit(core)(*one[:3])
            grads = grad_of(core)(*one)
            say("numerics %-10s out %.5f  dq %.5f  dk %.5f  dv %.5f "
                "(relative L2 against the float32 oracle)" % (
                    name, rel(out, want_out),
                    *(rel(g, w) for g, w in zip(grads, want_grads))))

    if want("trace"):
        from benchmark import trace_reduce
        trace_dir = os.path.join(".veles_cache", "attention_core_trace")
        fn = grad_of(merged)
        jax.block_until_ready(fn(q, k, v, do))
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                jax.block_until_ready(fn(q, k, v, do))
        path = trace_reduce.find_xplane(trace_dir)
        for plane, events in trace_reduce.metadata_stats(
                path, wanted=("tf_op", "hlo_category")).items():
            if "TPU" not in plane:
                continue
            for event, stats in sorted(events.items()):
                if stats.get("tf_op") or "custom" in event:
                    say("trace %s | %s | %s" % (
                        plane, event[:80], stats))
        reduced = trace_reduce.reduce_file(path)
        for device in reduced.devices:
            for op in sorted(device.ops, key=lambda o: -o.self_ns)[:8]:
                say("trace op %s %s %.3f ms" % (
                    device.name, op.name[:70], op.self_ns / 1e6))


if __name__ == "__main__":
    main()
