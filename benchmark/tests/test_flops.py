"""``benchmark/flops.py`` against the arithmetic it was copied from,
and its roofline floors against numbers worked out by hand."""

import logging
import os

import pytest

from benchmark import flops, harness

HOME = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALEXNET = harness.load_json(HOME, "configs", "alexnet227.json")
SHAPE = (227, 227, 3)


def test_alexnet_total_equals_bench_py():
    """The total equals ``bench.model_train_flops_per_sample`` on the
    program's own AlexNet workflow (shapes only: two samples)."""
    import bench
    logging.disable(logging.NOTSET)  # bench.py silences logging on import
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.alexnet import (ALEXNET_LAYERS, AlexNetWorkflow,
                                          SyntheticImageLoader)
    workflow = AlexNetWorkflow(
        DummyLauncher(),
        loader_factory=lambda w: SyntheticImageLoader(
            w, n_train=2, n_valid=0, side=227, minibatch_size=2),
        layers=ALEXNET_LAYERS)
    workflow.initialize(device=Device(backend="cpu"))
    assert flops.train_flops_per_sample(ALEXNET["layers"], SHAPE) == \
        bench.model_train_flops_per_sample(workflow)


def test_the_configuration_is_the_programs_layer_list():
    from veles_tpu.models.alexnet import ALEXNET_LAYERS
    ours = [{k: tuple(v) if isinstance(v, list) else v
             for k, v in layer.items()} for layer in ALEXNET["layers"]]
    assert ours == ALEXNET_LAYERS


def test_shapes_and_floors():
    shapes = [out for _, _, _, out in
              flops.layer_shapes(ALEXNET["layers"], SHAPE)]
    assert shapes[0] == (56, 56, 96) and shapes[9] == (6, 6, 256)
    assert shapes[-1] == (1000,)
    # dense: 58.6M weights read twice and their gradient written once
    # in float32 dwarf the activations: memory-bound, ~0.88 ms
    floor, bound = flops.roofline_floor_s(
        ALEXNET["layers"], SHAPE, "dense", 128, 197e12, 819e9)
    weights = 9216 * 4096 + 4096 * 4096 + 4096 * 1000
    assert bound == "memory"
    assert floor == pytest.approx(3 * 4 * weights / 819e9, rel=0.03)
    # conv: 2.16 GFLOP forward a sample, compute-bound at batch 128
    floor, bound = flops.roofline_floor_s(
        ALEXNET["layers"], SHAPE, "conv", 128, 197e12, 819e9)
    conv_flops = sum(c["flops"] for c in flops.layer_costs(
        ALEXNET["layers"], SHAPE) if c["kind"] == "conv")
    assert bound == "compute"
    assert floor == pytest.approx(conv_flops * 128 / 197e12, rel=0.01)
    with pytest.raises(ValueError):
        flops.layer_shapes([{"type": "deconv"}], SHAPE)
