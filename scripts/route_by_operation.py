#!/usr/bin/env python3
"""A traced token cell's sparse layers by OPERATION: device self time
a train step of what runs under a sub-scope (``route`` by default) of
the units of one type, split by pass (forward, the rematerialized
forward, backward) and by the operation's own name in ``op_name``
(what stands behind the sub-scope: ``gather``, ``scatter-add``,
``mul`` ...), of the first of the names XLA joined for a fusion.
``PERF.md`` section 5's by-operation tables are this script's output.

    python3 benchmark/run.py --workload <cell> --seed <n> --trace 1 ...
    python3 scripts/route_by_operation.py --cell <cell> --steps <n> \\
        [--part route] [--type moe] [--unit <index>]

Run it where the traced run left its trace (``.veles_cache/
benchmark_trace/<cell>``: on the chip, in the same command).
``--steps``: the train steps the run traced (its log says "traced:").
``--unit``: one unit by its index, not all of the type. Appends to
``chiprun_out/route_by_operation.txt``.
"""

import argparse
import collections
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join("chiprun_out", "route_by_operation.txt")
PASSES = ("forward", "recomputed", "backward")


def operation(op_name, part):
    """What stands behind the sub-scope in the first of a fusion's
    names (``gather``, ``checkpoint/mul``, ``jit(argsort)/sort``),
    trailing digits aside."""
    tail = op_name.split(";", 1)[0].rsplit("/%s/" % part, 1)[-1]
    return re.sub(r"[\d:]+$", "", tail) or tail


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cell", required=True)
    parser.add_argument("--steps", type=int, required=True)
    parser.add_argument("--part", default="route")
    parser.add_argument("--type", default="moe")
    parser.add_argument("--unit", type=int)
    args = parser.parse_args()

    from benchmark import harness, trace_reduce
    from benchmark.readers import trace_lm, trace_scopes

    bench = harness.Benchmark(ROOT)
    layers = bench.config(bench.cell(args.cell))["layers"]
    path = trace_reduce.find_xplane(os.path.join(
        ROOT, ".veles_cache", "benchmark_trace", args.cell))
    if path is None:
        raise SystemExit("no trace of %s here" % args.cell)
    trace = trace_reduce.reduce_file(path)
    names = trace_reduce.metadata_stats(
        path, wanted=(trace_scopes.OP_NAME_STAT,))
    share = 1e3 / (1e9 * args.steps * len(trace.devices))
    table = collections.defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    for device in trace.devices:
        op_names = names.get(device.name, {})
        for op in device.ops:
            op_name = (op_names.get(op.name) or {}).get(
                trace_scopes.OP_NAME_STAT)
            row, which = trace_lm.parse(op_name)
            if which not in ("forward", "backward") \
                    or trace_lm.PROGRAM not in op.program \
                    or args.part not in trace_lm.sub_scopes(
                        op_name, trace_lm.UNIT_PARTS) \
                    or layers[row[0]]["type"] != args.type \
                    or args.unit not in (None, row[0]):
                continue
            if "rematted_computation" in op_name:
                which = "recomputed"
            table[operation(op_name, args.part)][which] += \
                op.self_ns * share
    lines = ["%s, %s of %s, /%s/, ms a train step (%d traced): "
             "forward / recomputed forward / backward" % (
                 args.cell, "unit %d" % args.unit if args.unit is not None
                 else "all units", args.type, args.part, args.steps)]
    for name, row in sorted(table.items(), key=lambda kv: -sum(
            kv[1].values())):
        lines.append("  %-28s %7.3f / %7.3f / %7.3f" % (
            (name,) + tuple(row[p] for p in PASSES)))
    lines.append("  %-28s %7.3f / %7.3f / %7.3f   in all %.3f" % (
        ("sum",) + tuple(sum(r[p] for r in table.values())
                         for p in PASSES)
        + (sum(sum(r.values()) for r in table.values()),)))
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    sys.exit(main())
