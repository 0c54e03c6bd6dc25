#!/usr/bin/env python3
"""Record the fixture ``benchmark/fixtures/tiny-conv.v5e-1.xplane.pb``:
the tiny configuration of the short-convolution family
(``tests/configs/tiny-conv-lm.json``) traced through the harness on
whatever devices JAX offers (a fixture is recorded on the chip) and cut
by ``cut_xplane.cut`` to what the readers read.

    python3 benchmark/tests/record_conv_lm.py <out.xplane.pb>
"""

import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
ROOT = os.path.dirname(HOME)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

CELL = "tiny-conv-lm.tokens"


def tiny_root(path):
    """A checkout-like directory whose one cell is the tiny one, with
    the real cell's per-layer metrics."""
    from benchmark import harness
    shutil.copytree(HOME, os.path.join(path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(HERE, "traffic", "tiny-tokens.json"),
                os.path.join(path, "benchmark", "traffic"))
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    real = [m["workloads"] for m in spec["per_layer"]
            if m["name"] == "short_conv_device_ms"][0]
    spec["configs"] = [{
        "name": "tiny-conv-lm", "source": "benchmark/tests",
        "reduced": [], "why": "toy",
        "file": "benchmark/tests/configs/tiny-conv-lm.json"}]
    spec["workloads"] = [{
        "name": CELL, "config": "tiny-conv-lm",
        "traffic": "tiny-tokens", "chips": 1, "why": "toy"}]
    spec["per_layer"] = [dict(m, workloads=[CELL])
                         if m.get("workloads") == real else m
                         for m in spec["per_layer"]]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return harness.Benchmark(path)


def main(dst):
    import jax

    import cut_xplane
    from benchmark import harness, trace_reduce
    with tempfile.TemporaryDirectory(dir=os.path.dirname(
            os.path.abspath(dst))) as root:
        result = harness.run_cell(tiny_root(root), CELL, 2500000001, 0.3,
                                  True, jax.devices(), time.time())
        print(json.dumps(result))
        path = trace_reduce.find_xplane(os.path.join(
            root, ".veles_cache", "benchmark_trace"))
        print("%s: %d bytes, cut to %d" % (
            path, os.path.getsize(path), cut_xplane.cut(path, dst)))


if __name__ == "__main__":
    main(sys.argv[1])
