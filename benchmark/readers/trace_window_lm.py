"""Reader ``trace_window_lm``: the window/full grouped-query attention
family's train step on the device. ``trace_lm``'s reading of the
scopes (its ``Step``, its table, its log), with this family's units
and floors (``flops_window_lm.py``).

``value``:

* ``types`` (with ``types=[...]``), ``moe_route``: ``trace_lm``'s, over
  this configuration's layers: ms a step of the units of those types,
  all passes; the sparse layers outside ``experts`` and ``shared``;
* ``core_roofline`` (with ``window=true|false``): percent: the floor
  of the window layers' (or the full layers') attention cores over the
  time of their ``core`` sub-scopes, which is where the unit puts the
  kernels and what XLA leaves around them;
* ``window_blocks_over_band``: the block pairs the window layers'
  cores run, forward and backward, by the program's gauge
  ``veles_attention_core_blocks{unit,pass}``, over the block pairs the
  band touches at the configuration's block size, counted here from
  the mask's own arithmetic: 1.0 when the band is skipped exactly, 2.4
  where a window layer runs the causal square.

A program without the scopes or the gauges (a parent commit, a CPU)
gives no value and raises nothing.
"""

import collections
import math

from benchmark import flops_window_lm
from benchmark.readers import trace_lm

ATTENTION = flops_window_lm.ATTENTION
#: the program's key block where it divides the sequence
KV_BLOCK = 512


def band_block_pairs(positions, block, window):
    """Block pairs (``block`` queries by ``KV_BLOCK`` keys, or what of
    it divides the sequence) that hold a visible query-key pair."""
    kv = math.gcd(positions, KV_BLOCK)
    pairs = 0
    for start in range(0, positions, block):
        oldest = max(0, start - window + 1)
        pairs += (start + block - 1) // kv - oldest // kv + 1
    return pairs


def read(context, value, types=None, window=None):
    if value in ("types", "moe_route"):
        return trace_lm.read(context, value, types=types)
    layers = context["config"]["layers"]
    positions = layers[0]["positions"]
    if value == "window_blocks_over_band":
        run = collections.Counter()
        for labels, pairs in trace_lm.gauge_series(
                "veles_attention_core_blocks").items():
            run[dict(labels).get("unit")] += pairs
        windows, fused, group = ({
            dict(labels).get("unit"): value for labels, value in
            trace_lm.gauge_series("veles_attention_" + name).items()}
            for name in ("window", "core_fused", "kv_group"))
        if windows:
            context["log"]("attention cores as traced (fused; window; "
                           "query heads to a key/value head; block "
                           "pairs run, forward + backward): %s" % "  ".join(
                               "%s %g; %g; %g; %g" % (
                                   unit, fused.get(unit, -1), windows[unit],
                                   group.get(unit, -1), run[unit])
                               for unit in sorted(windows)))
        ran = touched = 0.0
        for i, descr in enumerate(layers):
            # a unit's name, as StandardWorkflow gives it
            name = descr.get("name", "%s%d" % (descr["type"], i))
            keys = windows.get(name)
            if descr["type"] == ATTENTION and keys and name in run:
                ran += run[name]
                # forward and backward, as the gauge's two passes
                touched += 2 * band_block_pairs(
                    positions, descr["block"], int(keys))
        return ran / touched if touched else None
    made = trace_lm.step(context)
    peaks = context["peaks"]
    if made is None or peaks is None:
        return None
    if value == "core_roofline":
        units = [i for i, d in enumerate(layers) if d["type"] == ATTENTION
                 and (d.get("window") is not None) == bool(window)]
        seconds = sum(made.by_part[i, "core"] for i in units)
        if not seconds:
            return None
        floors = [flops_window_lm.attention_core_floor_s(
            layers[i], positions, context["config"]["batch"], peaks)
            for i in units]
        return trace_lm._share(
            context, "gqa_%s_core_roofline" % (
                "window" if window else "full"),
            sum(t for t, _ in floors),
            "/".join(sorted({b for _, b in floors})), seconds)
    raise ValueError("trace_window_lm: no value %r" % (value,))
