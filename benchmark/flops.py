"""Operations and bytes of a convnet's layers, from shapes alone.

The yardstick's arithmetic: nothing here imports the program. A layer
list is the ``layers`` of a file under ``configs/`` (the descriptors
``StandardWorkflow`` takes); shapes are NHWC.

* FLOPs count multiply-adds of convolutions and dense layers as 2, and
  training as 3x the forward pass (forward, gradient to the input,
  gradient to the weights): the accounting of
  ``bench.model_train_flops_per_sample``, copied so that a later PR
  can delete the original. Elementwise layers (LRN, pooling, dropout,
  activations) are bandwidth, not FLOPs, and count 0.
* Bytes are the least a layer's three passes must move through HBM at
  the stated activation and weight widths: each pass reads its two
  operands and writes its result once. It is a floor, not a model of
  what XLA does.
"""

import math

POOL = ("max_pooling", "avg_pooling")
SAME_SHAPE = ("norm", "dropout")


def input_shape(config):
    """``(height, width, channels)`` of a configuration's samples."""
    side = config["input"]["side"]
    return side, side, config["input"]["channels"]


def pair(value, default):
    """A layer's ``sliding``: ``[x, y]``, one number for both, or
    ``default`` where the descriptor has none."""
    if value is None:
        return default
    if isinstance(value, (list, tuple)):
        return int(value[0]), int(value[1])
    return int(value), int(value)


def _window_out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def layer_shapes(layers, input_shape):
    """``[(kind, descr, in_shape, out_shape)]`` per layer, sample
    shapes without the batch axis; ``kind`` is conv, dense, pool or
    other."""
    shape = tuple(input_shape)
    rows = []
    for descr in layers:
        ltype = descr["type"]
        if ltype.startswith("conv"):
            h, w, _ = shape
            sx, sy = pair(descr.get("sliding"), (1, 1))
            pad = descr.get("padding", 0)
            if not isinstance(pad, int):
                raise ValueError("padding %r: only a symmetric integer "
                                 "padding has a formula here" % (pad,))
            out = (_window_out(h, descr["ky"], sy, pad),
                   _window_out(w, descr["kx"], sx, pad),
                   descr["n_kernels"])
            kind = "conv"
        elif ltype.startswith("all2all") or ltype == "softmax":
            out = (int(descr["output_sample_shape"]),)
            kind = "dense"
        elif ltype in POOL:
            h, w, c = shape
            sx, sy = pair(descr.get("sliding"),
                           (descr["kx"], descr["ky"]))
            out = (_window_out(h, descr["ky"], sy, 0),
                   _window_out(w, descr["kx"], sx, 0), c)
            kind = "pool"
        elif ltype in SAME_SHAPE:
            out = shape
            kind = "other"
        else:
            raise ValueError("no shape rule for layer type %r" % ltype)
        rows.append((kind, descr, shape, out))
        shape = out
    return rows


def layer_costs(layers, input_shape, act_bytes=2, weight_bytes=4):
    """Per layer and per TRAINED sample: ``{"kind", "type", "flops",
    "act_bytes", "weight_bytes"}``. ``weight_bytes`` is per step, not
    per sample: a step of any batch reads the weights once a pass and
    writes their gradient once."""
    costs = []
    for kind, descr, shape, out in layer_shapes(layers, input_shape):
        n_in, n_out = math.prod(shape), math.prod(out)
        if kind == "conv":
            n_w = descr["ky"] * descr["kx"] * shape[-1] * out[-1]
            fwd = 2.0 * out[0] * out[1] * n_w
        elif kind == "dense":
            n_w = n_in * n_out
            fwd = 2.0 * n_w
        else:
            n_w, fwd = 0, 0.0
        # forward reads x, writes y; grad-input reads dy, writes dx;
        # grad-weights reads x and dy
        acts = (2 * n_in + 3 * n_out + n_in) * act_bytes
        # forward and grad-input read w, grad-weights writes dw
        weights = 3 * n_w * weight_bytes
        costs.append({"kind": kind, "type": descr["type"],
                      "flops": 3.0 * fwd,
                      "act_bytes": float(acts) if n_w else 0.0,
                      "weight_bytes": float(weights)})
    return costs


def train_flops_per_sample(layers, input_shape):
    return sum(c["flops"] for c in layer_costs(layers, input_shape))


def roofline_floor_s(layers, input_shape, kind, batch, peak_flops,
                     peak_bytes, act_bytes=2, weight_bytes=4):
    """Least seconds one chip could take for the ``kind`` layers
    (conv or dense) of ONE train step of ``batch`` samples:
    ``(seconds, bound)`` with ``bound`` "compute" or "memory" by which
    term is larger, layer by layer, and the bound of the layer group
    by where most of the floor lies."""
    total = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for c in layer_costs(layers, input_shape, act_bytes, weight_bytes):
        if c["kind"] != kind:
            continue
        t_flops = c["flops"] * batch / peak_flops
        t_bytes = (c["act_bytes"] * batch + c["weight_bytes"]) / peak_bytes
        bound = "compute" if t_flops >= t_bytes else "memory"
        by_bound[bound] += max(t_flops, t_bytes)
        total += max(t_flops, t_bytes)
    return total, max(by_bound, key=by_bound.get)
