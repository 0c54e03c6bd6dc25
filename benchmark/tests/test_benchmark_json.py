"""``BENCHMARK.json`` against the parts of its contract that a file
can show: keys, names, units, lengths, and that every entry finds its
files under ``benchmark/``."""

import os
import re

from benchmark import harness

HOME = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HOME)
SPEC = harness.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_keys_names_units_and_lengths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmark"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for entry in SPEC["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(key) for key in entry["reduced"])
    for cell in SPEC["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
    for group, keys in (("end_to_end", {"bound"}), ("per_layer",
                                                    {"layer", "moves"})):
        for metric in SPEC[group]:
            assert set(metric) - {"workloads"} == {
                "name", "unit", "better", "source"} | keys
            assert UNIT.match(metric["unit"])
            assert metric["better"] in ("lower", "higher")
    for metric in SPEC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    names = [e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in SPEC[group]]
    assert all(NAME.match(name) for name in names)
    assert len(set(names)) == len(names)
    for text in [e[k] for group in ("configs", "workloads")
                 for e in SPEC[group] for k in ("why", "source") if k in e]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)
    assert "setup_s" in [m["name"] for m in SPEC["end_to_end"]]


def test_every_entry_finds_its_files():
    bench = harness.Benchmark(ROOT)
    used = set()
    for cell in SPEC["workloads"]:
        config, traffic = bench.config(cell), bench.traffic(cell)
        used.add(cell["config"])
        for kind, name in (("builders", config["family"]),
                           ("reference", config["family"]),
                           ("drivers", traffic["driver"])):
            assert os.path.isfile(os.path.join(HOME, kind, name + ".py"))
        moved = {m["name"] for m in bench.metrics("end_to_end", cell)}
        assert "setup_s" in moved and len(moved) >= 2
        layer = bench.metrics("per_layer", cell)
        assert layer and all(m["moves"] in moved for m in layer)
    assert used == {e["name"] for e in SPEC["configs"]}
    for metric in SPEC["per_layer"]:
        spec = harness.load_json(HOME, "layer_metrics",
                                 metric["name"] + ".json")
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert os.path.isfile(os.path.join(HOME, "readers",
                                           spec["reader"] + ".py"))
    for entry in SPEC["configs"]:
        assert entry["file"].startswith("benchmark/")
        config = harness.load_json(ROOT, entry["file"])
        assert set(entry["reduced"]) == set(config["reduced"])
