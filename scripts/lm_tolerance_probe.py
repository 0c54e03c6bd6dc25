#!/usr/bin/env python3
"""The other side of the limits in ``benchmark/reference/moe_lm.py``,
``window_moe_lm.py`` and ``indexed_moe_lm.py``: what a LOWER PRECISION and a WRONG step read
in the comparison that decides ``correct`` in a token cell
(``--cell``, any of the three; ``glm47flash-ep8share.pretrain4k``
without it), each taken through
the harness's own ``agreement``.

    python3 scripts/lm_tolerance_probe.py --seed <n> [--cell <c>] [--more]

On the chip. Builds the system from ``--seed`` exactly as the
benchmark does (``benchmark/builders/moe_lm.py``: the reference's
train step, then one train step of the program) and, on the same
batch and weights, these controls, each laid out as a program's step
and held against the plain float32 reference:

* ``program``: the timed program itself (the sound reading);
* ``int8``: the reference with BOTH operands of every product, in the
  forward and in the backward pass (all but the routers', which the
  program keeps in float32), rounded to 8 bits, absmax over the
  contracted dims: the nearest precision below the configuration's
  bfloat16. Done on the jaxpr of the reference's gradient, every
  ``dot_general`` of it, so the reference itself stays as it is. A
  router's product is known by a MATRIX operand whose last dim is the
  experts' count (the logits, their gradient or the router's weights:
  the references multiply tokens by matrices there and heads by
  batched operands everywhere else; since PR 33, whose cell has heads
  of 128 beside 128 experts: before it any operand of that last dim
  was passed over);
* from the reference's own step, on the host: ``unchanged`` (no
  update at all), ``rate_x2`` (the learning rate twice too large),
  ``bias_reversed`` (the selection bias moved the wrong way);
* with ``--more``, a device pass each: ``half_batch`` (the gradient
  of the batch's first sequence alone), ``no_rope``, ``scale_1`` (the
  routed scale dropped), ``no_shared`` (the shared expert left out).

One line a control: ``ok`` as ``agreement`` gives it, and the numbers.
``--config``/``--traffic`` take other files (a CPU rehearsal)."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "glm47flash-ep8share.pretrain4k"


def int8_step_function(ref, layers, example):
    """``ref.step_function(layers)`` with both operands of every
    ``dot_general`` of its jaxpr but the routers' rounded to int8
    first. Calls are walked into; a rematerialized region stays one
    (the same primitive, bound on the rewritten inner jaxpr)."""
    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import partial_eval as pe

    n_experts = {d["n_experts"] for d in layers if d["type"] == "moe"}
    dots = [0]

    def q8(a, axes):
        scale = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / 127.0
        return jnp.where(scale > 0, jnp.round(a / scale) * scale, a)

    def run(jaxpr, consts, *args):
        env = dict(zip(jaxpr.constvars, consts))
        env.update(zip(jaxpr.invars, args))

        def read(v):
            return v.val if hasattr(v, "val") else env[v]

        for eqn in jaxpr.eqns:
            vals = [read(v) for v in eqn.invars]
            name = eqn.primitive.name
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if name == "dot_general" and not any(
                    v.ndim == 2 and v.shape[-1] in n_experts
                    for v in vals[:2]):
                (lhs_c, rhs_c), _ = eqn.params["dimension_numbers"]
                dots[0] += 1
                out = eqn.primitive.bind(
                    q8(vals[0], tuple(lhs_c)), q8(vals[1], tuple(rhs_c)),
                    **eqn.params)
            elif name == "checkpoint":
                closed = jax.make_jaxpr(
                    lambda *a: run(inner, (), *a))(*vals)
                out = eqn.primitive.bind(
                    *closed.consts, *vals, **dict(
                        eqn.params,
                        jaxpr=pe.convert_constvars_jaxpr(closed.jaxpr)))
            elif name == "scan":
                # a loop's body (the indexed family's reference runs
                # its blocks of queries in one): rewritten like any
                # other jaxpr, the loop bound on the rewritten body
                closed = jax.make_jaxpr(
                    lambda *a: run(inner.jaxpr, inner.consts, *a))(
                        *(v.aval for v in inner.jaxpr.invars))
                out = eqn.primitive.bind(*vals, **dict(eqn.params,
                                                       jaxpr=closed))
            elif name in ("pjit", "jit", "custom_jvp_call", "closed_call",
                          "custom_vjp_call") and inner is not None:
                closed = inner if hasattr(inner, "consts") else None
                out = run(closed.jaxpr if closed else inner,
                          closed.consts if closed else (), *vals)
            else:
                out = eqn.primitive.bind(*vals, **eqn.params)
            outs = out if eqn.primitive.multiple_results or \
                isinstance(out, (list, tuple)) else [out]
            env.update(zip(eqn.outvars, outs))
        return [read(v) for v in jaxpr.outvars]

    closed, shape = jax.make_jaxpr(ref.step_function(layers),
                                   return_shape=True)(*example)
    tree = jax.tree_util.tree_structure(shape)

    def fn(params, tokens, labels):
        flat = jax.tree_util.tree_leaves((params, tokens, labels))
        return jax.tree_util.tree_unflatten(
            tree, run(closed.jaxpr, closed.consts, *flat))
    return jax.jit(fn), dots


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--more", action="store_true")
    parser.add_argument("--cell", default=CELL)
    parser.add_argument("--config")
    parser.add_argument("--traffic")
    args = parser.parse_args()
    import jax
    import numpy

    from benchmark import harness
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.cell)
    config = harness.load_json(args.config) if args.config \
        else bench.config(cell)
    traffic = harness.load_json(args.traffic) if args.traffic \
        else bench.traffic(cell)
    builder = harness.load_module(bench.home, "builders", config["family"])
    ref = harness.load_module(bench.home, "reference", config["family"])
    kept = {}  # the layers and the reference's step
    compared = {}  # control -> what ref.step_comparison makes of it

    def compare(name, step):
        """A step is two copies of the model on the host: compared at
        once and dropped, so that the controls do not add up to the
        machine's memory."""
        compared[name] = ref.step_comparison(kept["layers"], step,
                                             kept["reference"])

    class Probe(object):
        """Stands in for the reference in the builder: does what the
        reference does and compares the controls' steps with its step;
        the device controls run where the reference's own step does,
        before the trainer exists."""
        validation_batch_losses = staticmethod(
            ref.validation_batch_losses)

        @staticmethod
        def train_step(layers, params, tokens, labels, optimizer):
            kept["layers"] = layers
            expected = kept["reference"] = ref.train_step(
                layers, params, tokens, labels, optimizer)
            example = (params, numpy.asarray(tokens),
                       numpy.asarray(labels))
            with jax.default_matmul_precision("highest"):
                fn, dots = int8_step_function(ref, layers, example)
            compare("int8", ref.train_step(
                layers, params, tokens, labels, optimizer, fn=fn))
            print("int8: %d dot_general equations rounded" % dots[0],
                  flush=True)
            if args.more:
                compare("half_batch", ref.train_step(
                    layers, params, tokens[:1], labels[:1], optimizer))
                rope = ref.rope
                ref.rope = lambda x, *table: x
                compare("no_rope", ref.train_step(
                    layers, params, tokens, labels, optimizer))
                ref.rope = rope
                for name, change in (("scale_1", {"scale": 1.0}),
                                     ("no_shared", {"shared_experts": 0})):
                    compare(name, ref.train_step(
                        [dict(d, **change) if d["type"] == "moe" else d
                         for d in layers], params, tokens, labels,
                        optimizer))
            return expected

        @staticmethod
        def step_comparison(layers, program, expected):
            compare("program", program)
            return compared["program"]

    system = builder.build(config, traffic, args.seed, jax.devices()[:1],
                           Probe, print)
    system.trainer.shutdown()
    expected = kept["reference"]
    zero = numpy.float32(0.0)  # broadcasts: no array the model's size
    made = {
        "unchanged": lambda: dict(
            expected,
            changes=[dict.fromkeys(d, zero) for d in expected["changes"]],
            moments=[dict.fromkeys(d, zero) for d in expected["moments"]]),
        "rate_x2": lambda: dict(expected, changes=[
            {k: v if k == "select_bias" else 2 * v for k, v in d.items()}
            for d in expected["changes"]]),
        "bias_reversed": lambda: dict(expected, changes=[
            {k: -v if k == "select_bias" else v for k, v in d.items()}
            for d in expected["changes"]]),
    }
    for name in sorted(made):
        compare(name, made[name]())
    losses = system.reference_losses["losses"]
    report = {}
    for name in ["program", "int8"] + sorted(
            set(compared) - {"program", "int8"}):
        ok, numbers = ref.agreement(losses, {
            "losses": losses, "step": compared[name]})
        report[name] = dict(numbers, ok=ok)
        print("control %s: ok=%s %s" % (name, ok, json.dumps(numbers)),
              flush=True)
    print("probe: " + json.dumps(report))


if __name__ == "__main__":
    sys.exit(main())
