#!/usr/bin/env python3
"""A token cell's two programs, compiled for a v5e that is described
and not attached: what the chip's compiler makes of them, before a
chip call is spent.

    JAX_PLATFORMS=cpu python3 scripts/compile_token_cell.py \\
        --cell <workload> [--positions N] [--text DIR]

Here, on the CPU. Builds the cell's workflow from its configuration
and traffic files as ``benchmark/builders`` does (weights left at
zero: only shapes matter), captures the trainer's jitted train and
eval segments, and lowers them for one chip of a described
``v5e:2x2`` with the default backend read as a TPU, so that every
kernel choice is the chip's. Prints, a program: arguments, temporaries
and their sum against the 15.75 GB the v5e's runtime offers; the
Mosaic custom calls by unit scope; with ``--text`` writes the
optimized HLO there (``<cell>.<program>.txt``), which two checkouts
can be compared by (``diff``; PR 29's method). No time comes out of
it, and nothing here is a device number.
"""

import argparse
import collections
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HBM_BYTES = 15.75e9


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cell", required=True)
    parser.add_argument("--positions", type=int,
                        help="another sequence length than the file's")
    parser.add_argument("--text", help="directory for the optimized HLO")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from benchmark.seeded_tokens import SeededTokenLoader
    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.base import TRAIN, VALIDATION
    from veles_tpu.nn.base import ForwardBase
    from veles_tpu.nn.precision import set_policy
    from veles_tpu.standard_workflow import StandardWorkflow
    from veles_tpu.train import FusedTrainer

    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.cell)
    config, traffic = bench.config(cell), bench.traffic(cell)
    layers = [dict(layer) for layer in config["layers"]]
    if args.positions:
        layers[0]["positions"] = args.positions
    first = layers[0]
    lookahead = 1 + max(d.get("shift", 0) for d in layers)
    optimizer = config["optimizer"]
    set_policy(config["precision"])
    prng.get().seed(1)
    prng.get("loader").seed(2)
    ForwardBase.fill_matrices = lambda self, mem: None  # shapes only
    jitted = {}

    class Capturing(FusedTrainer):
        def _compile_train(self, fn):
            jitted["train_segment"] = super()._compile_train(fn)
            return jitted["train_segment"]

        def _compile_eval(self, fn):
            jitted["eval_segment"] = super()._compile_eval(fn)
            return jitted["eval_segment"]

    t0 = time.time()
    workflow = StandardWorkflow(
        DummyLauncher(),
        loader=lambda wf: SeededTokenLoader(
            wf, n_train=traffic["n_train"], n_valid=traffic["n_valid"],
            length=first["positions"] + lookahead,
            vocabulary=first["vocabulary"], seed=3,
            exponent=traffic["zipf_exponent"],
            minibatch_size=config["batch"]),
        layers=layers, loss=config["loss"], solver=optimizer["solver"],
        learning_rate=optimizer["learning_rate"], momentum=0.0,
        weights_decay=optimizer["weights_decay"],
        solver_hp={k: optimizer[k] for k in (
            "beta1", "beta2", "epsilon", "warmup_steps")})
    workflow.initialize(device=Device(backend="cpu"))
    trainer = Capturing(workflow, stream=traffic["stream"], offload=False)
    params, states = trainer.pull_params()
    print("%s: workflow and trainer in %.0f s; %d parameters" % (
        cell["name"], time.time() - t0,
        sum(v.size for p in params for v in p.values())), flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def abstract(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                    sharding=one_chip)

    def shapes(tree):
        return jax.tree_util.tree_map(abstract, tree)

    train_idx = jnp.asarray(trainer._segment_indices(TRAIN))
    keys = jax.vmap(lambda i: jax.random.fold_in(
        trainer._dropout_base_key(), i))(jnp.arange(train_idx.shape[0]))
    programs = {
        "train_segment": (trainer._data_args, params, states, train_idx,
                          keys),
        "eval_segment": (trainer._data_args, params, jnp.asarray(
            trainer._segment_indices(VALIDATION))),
    }
    jax.default_backend = lambda: "tpu"  # the chip's kernel choices
    for name, operands in programs.items():
        t0 = time.time()
        compiled = jitted[name].lower(*shapes(operands)).compile()
        memory = compiled.memory_analysis()
        held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
        print("%s: compiled for the v5e in %.0f s: arguments %.1f MB, "
              "temporaries %.1f MB, arguments + temporaries %.1f MB "
              "(%.1f%% of %.2f GB), output %.1f MB, aliased %.1f MB" % (
                  name, time.time() - t0,
                  memory.argument_size_in_bytes / 1e6,
                  memory.temp_size_in_bytes / 1e6, held / 1e6,
                  100.0 * held / HBM_BYTES, HBM_BYTES / 1e9,
                  memory.output_size_in_bytes / 1e6,
                  memory.alias_size_in_bytes / 1e6), flush=True)
        text = compiled.as_text()
        calls = collections.Counter()
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' in line:
                found = re.search(r'op_name="([^"]*)"', line)
                op = found.group(1) if found else ""
                scope = re.search(r"veles\.(u\d+\.[\w.\-]+)", op)
                calls[scope.group(1) + "".join(
                    part for part in ("/core", "/experts")
                    if part + "/" in op) if scope
                    else "(no scope)"] += 1
        print("  Mosaic calls: %d; by scope: %s" % (
            sum(calls.values()), "  ".join(
                "%s x%d" % kv for kv in sorted(calls.items()))))
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            path = os.path.join(args.text, "%s.%s.txt" % (cell["name"],
                                                          name))
            with open(path, "w") as f:
                f.write(text)
            print("  optimized HLO: %s (%d lines)" % (
                path, text.count("\n")))
    from veles_tpu.telemetry.registry import get_registry
    for gauge in ("veles_attention_core_fused", "veles_attention_window",
                  "veles_attention_kv_group", "veles_attention_index_topk",
                  "veles_attention_selected_pairs",
                  "veles_remat_kept_bytes", "veles_moe_combine_rows",
                  "veles_short_conv_taps", "veles_short_conv_lowering",
                  "veles_head_tied"):
        metric = get_registry().get(gauge)
        if metric is not None:
            print("  %s: %s" % (gauge, "  ".join(
                "%s=%g" % (labels.get("unit"), child.value)
                for labels, child in metric.series())))


if __name__ == "__main__":
    sys.exit(main())
