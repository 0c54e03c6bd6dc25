"""The harness rehearsed on the CPU at a tiny size: files are found by
name, a device outside the peak table is refused, the result has the
contract's keys, four forced host devices run the partitioned step,
and a configuration, a traffic mix and a per-layer metric are each
added by new files plus an entry in ``BENCHMARK.json``."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
HOME = os.path.dirname(HERE)
ROOT = os.path.dirname(HOME)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def tiny_spec():
    """The repository's ``BENCHMARK.json`` with its metrics kept and
    its cells replaced by the tiny ones of ``tests/configs``."""
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    spec["configs"] = [
        {"name": name, "source": "benchmark/tests", "reduced": [],
         "file": "benchmark/tests/configs/%s.json" % name, "why": "toy"}
        for name in ("tiny", "tiny-dp4")]
    spec["workloads"] = [
        {"name": "tiny.resident", "config": "tiny",
         "traffic": "tiny-resident", "chips": 1, "why": "toy"},
        {"name": "tiny.streamed", "config": "tiny",
         "traffic": "tiny-streamed", "chips": 1, "why": "toy"},
        {"name": "tiny-dp4.resident", "config": "tiny-dp4",
         "traffic": "tiny-resident", "chips": 4, "why": "toy"}]
    for metric in spec["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = ["tiny-dp4.resident"]
    return spec


def make_root(path, spec):
    """A checkout-like directory: ``BENCHMARK.json`` and a copy of
    ``benchmark/`` with the tests' traffic files put beside the real
    ones, which is how a later PR adds a traffic mix."""
    shutil.copytree(HOME, os.path.join(path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in os.listdir(os.path.join(HERE, "traffic")):
        shutil.copy(os.path.join(HERE, "traffic", name),
                    os.path.join(path, "benchmark", "traffic", name))
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return harness.Benchmark(str(path))


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root"), tiny_spec())


def run(bench, cell, trace, seconds=0.3, seed=5):
    import jax
    lines = []
    result = harness.run_cell(bench, cell, seed, seconds, trace,
                              jax.devices(), time.time(),
                              log=lines.append)
    return result, lines


def test_files_are_found_by_name(bench):
    cell = bench.cell("tiny.streamed")
    assert bench.config(cell)["family"] == "convnet"
    assert bench.traffic(cell)["stream"] is True
    assert [m["name"] for m in bench.metrics("per_layer", cell)].count(
        "collective_ms") == 0
    assert "collective_ms" in [m["name"] for m in bench.metrics(
        "per_layer", bench.cell("tiny-dp4.resident"))]
    with pytest.raises(KeyError):
        bench.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.load_module(bench.home, "drivers", "no-such-driver")
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert bench.peaks("cpu") is None


@pytest.mark.parametrize("cell,chips", [("tiny.resident", 1),
                                        ("tiny.streamed", 1),
                                        ("tiny-dp4.resident", 4)])
def test_untraced_result_is_the_contracts(bench, cell, chips):
    result, lines = run(bench, cell, trace=False)
    assert set(result) == CONTRACT_KEYS
    declared = {m["name"]: m["unit"] for m in bench.spec["end_to_end"]}
    assert set(result["metrics"]) == set(declared)
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"}
        assert metric["unit"] == declared[name]
        assert isinstance(metric["value"], float)
    assert result["metrics"]["train_samples_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert "memory_peak_bytes" in result["device"]
    # every check of the run itself holds; only the device is refused:
    # a CPU is not a TPU and is not in the peak table
    checks = json.loads(next(
        line for line in lines if line.startswith("checks: "))[8:])
    assert result["correct"] is False
    assert {k for k, ok in checks.items() if not ok} == {
        "platform_is_tpu", "device_in_peak_table"}


def test_traced_result_reads_the_host_counters(bench):
    resident, _ = run(bench, "tiny.resident", trace=True)
    streamed, _ = run(bench, "tiny.streamed", trace=True)
    assert set(resident) == CONTRACT_KEYS  # no device plane on a CPU
    declared = {m["name"] for m in bench.metrics(
        "per_layer", bench.cell("tiny.resident"))}
    assert set(resident["metrics"]) <= declared
    # a reader that finds nothing to read is left out of the line
    assert "train_step_device_ms" not in resident["metrics"]
    assert "mfu_pct" not in resident["metrics"]
    assert resident["metrics"]["input_wait_pct"]["value"] == 0.0
    assert streamed["metrics"]["input_wait_pct"]["value"] > 0.0
    assert 0.0 < resident["metrics"]["epoch_gap_pct"]["value"] < 100.0


def test_a_cell_is_added_by_files_and_entries(tmp_path):
    spec = tiny_spec()
    spec["configs"].append(
        {"name": "tiny-b4", "source": "benchmark/tests", "reduced": [],
         "file": "benchmark/configs/tiny-b4.json", "why": "toy"})
    spec["workloads"].append(
        {"name": "tiny-b4.short", "config": "tiny-b4",
         "traffic": "tiny-short", "chips": 1, "why": "toy"})
    spec["per_layer"].append(
        {"name": "sweep_share", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "Epoch loop",
         "moves": "train_samples_per_s",
         "workloads": ["tiny-b4.short"]})
    bench = make_root(tmp_path, spec)
    config = harness.load_json(HERE, "configs", "tiny.json")
    traffic = harness.load_json(HERE, "traffic", "tiny-resident.json")
    new_files = {
        ("configs", "tiny-b4.json"): json.dumps(dict(config, batch=4)),
        ("traffic", "tiny-short.json"): json.dumps(
            dict(traffic, n_train=32, n_valid=8)),
        ("layer_metrics", "sweep_share.json"): json.dumps(
            {"name": "sweep_share", "layer": "Epoch loop", "unit": "%",
             "reader": "complement", "moves": "train_samples_per_s",
             "args": {"of": "gap_s", "over": "window_s"}}),
        ("readers", "complement.py"):
            "def read(context, of, over):\n"
            "    c = context['counters']\n"
            "    return 100.0 * (1.0 - c[of] / c[over])\n"}
    for (kind, name), text in new_files.items():
        with open(os.path.join(bench.home, kind, name), "w") as f:
            f.write(text)
    result, lines = run(bench, "tiny-b4.short", trace=True)
    assert result["attempted"] % 8 == 0  # 32 samples in batches of 4
    share = result["metrics"]["sweep_share"]["value"]
    gap = result["metrics"]["epoch_gap_pct"]["value"]
    assert share == pytest.approx(100.0 - gap)
    assert "collective_ms" not in result["metrics"]


def command(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "alexnet227.resident", "--seed", "1",
         "--seconds", "1", "--trace", "0"] + list(args),
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_run_without_a_chip():
    done = command(ROOT)
    assert done.returncode == 2
    assert done.stdout == ""
    assert "cpu" in done.stderr and "No result" in done.stderr


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(HOME, os.path.join(tmp_path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = command(str(tmp_path))
    assert done.returncode not in (0, 2)
    assert done.stdout == ""
    assert "not in this checkout" in done.stderr
