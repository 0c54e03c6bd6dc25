#!/usr/bin/env python3
"""One cell of the benchmark, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of
``BENCHMARK.json``'s ``workloads``; ``benchmark/harness.py`` finds the
files that make it up by name. Inputs and weights come from
``--seed``. Set-up (data, staging, compilation or cache reads,
warm-up, the agreement check against the plain reference) is timed as
``setup_s``; then the cell is measured for ``--seconds`` seconds.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
measures the same window and then traces a few more epochs with the
JAX profiler and reports the cell's per-layer metrics and a
breakdown.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``); everything else (device and
versions, sample counts, losses, checks, cache entries) is on earlier
lines. Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits with code 2, naming what it found. It
never falls back to the CPU: rehearsals on the CPU are
``benchmark/tests``.
"""

import time

T0 = time.time()  # the process's start, as near as Python shows it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(line):
    print(line, flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import harness
    bench = harness.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    seconds = (args.seconds if args.seconds is not None
               else bench.spec["run_seconds"])
    try:
        import veles_tpu  # noqa: F401
    except ImportError as e:
        sys.exit("benchmark: the program is not in this checkout "
                 "(%s): nothing to measure" % e)

    import jax
    import jaxlib
    harness.configure_compile_cache(ROOT)
    platform = jax.default_backend()
    devices = jax.devices()
    if platform != "tpu" or len(devices) < cell["chips"]:
        print("benchmark: cell %s needs %d TPU chip(s); JAX offers %d "
              "%s device(s) (JAX_PLATFORMS=%r). No result."
              % (cell["name"], cell["chips"], len(devices), platform,
                 os.environ.get("JAX_PLATFORMS")), file=sys.stderr)
        return 2
    from importlib import metadata
    log("device: platform=%s kind=%s offered=%d used=%d"
        % (platform, devices[0].device_kind, len(devices), cell["chips"]))
    log("versions: jax=%s jaxlib=%s libtpu=%s python=%s" % (
        jax.__version__, jaxlib.__version__, metadata.version("libtpu"),
        sys.version.split()[0]))
    log("cell: %s seed=%d seconds=%g trace=%d; imports and devices "
        "ready %.1f s after the process started"
        % (cell["name"], args.seed, seconds, args.trace,
           time.time() - T0))
    result = harness.run_cell(bench, cell["name"], args.seed, seconds,
                              bool(args.trace), devices, T0, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
