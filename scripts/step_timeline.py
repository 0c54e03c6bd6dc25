#!/usr/bin/env python3
"""One train step of a traced benchmark run, operation by operation in
the order the first chip ran them: where a collective's halves sit,
what ran between them, and which half the time went to.

    python3 benchmark/run.py --workload <cell> ... --trace 1
    python3 scripts/step_timeline.py <cell> [OUT]

On the chip, in the same command as the traced run (the trace stays
on that machine: ``.veles_cache`` is not copied back). Reads the run's
trace through ``benchmark/trace_reduce.py`` and writes, for the step
that starts at the fourth occurrence of the train program's heaviest
collective: offset, duration and self time in ms, the reader's bucket
and the operation's name, for every operation over 15 us. The names
are the optimized HLO's (``scripts/partitioned_schedule.py --text``
prints that program off the chip). PR 34 read off it that an
asynchronous all-reduce's ``done`` half still holds the core for most
of the exchange.
"""

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    from benchmark import trace_reduce
    cell = sys.argv[1]
    out = sys.argv[2] if len(sys.argv) > 2 else os.path.join(
        ROOT, "chiprun_out", "step_timeline.txt")
    reduced = trace_reduce.reduce_dir(os.path.join(
        ROOT, ".veles_cache", "benchmark_trace", cell))
    if reduced is None:
        sys.exit("no trace of %s here: run it with --trace 1 first" % cell)
    ops = sorted((op for op in reduced.devices[0].ops
                  if "train_segment" in op.program),
                 key=lambda op: op.start)
    collectives = collections.defaultdict(list)
    for op in ops:
        if op.bucket == trace_reduce.COLLECTIVE_BUCKET:
            collectives[op.name.split(" = ", 1)[0]].append(op)
    if not collectives:
        sys.exit("no collective in the train program of %s" % cell)
    anchor = max(collectives.values(),
                 key=lambda found: sum(op.self_ns for op in found))
    lo, hi = anchor[3].start, anchor[4].start
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        f.write("%s: one train step of %.3f ms, from %s\n"
                "  offset duration     self bucket\n" % (
                    cell, (hi - lo) / 1e6,
                    anchor[3].name.split(" = ", 1)[0]))
        for op in ops:
            if lo <= op.start < hi and op.self_ns > 15000:
                f.write("%8.3f %8.3f %8.3f %-22s %s\n" % (
                    (op.start - lo) / 1e6, (op.end - op.start) / 1e6,
                    op.self_ns / 1e6, op.bucket[:22],
                    op.name.split(" = ", 1)[0][:60]))
    print("%s: %s" % (out, open(out).readline().strip()))


if __name__ == "__main__":
    sys.exit(main())
