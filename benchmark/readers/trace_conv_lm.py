"""Reader ``trace_conv_lm``: the train step of the gated
short-convolution and grouped-query attention family on the device.
``trace_lm``'s reading of the scopes (its ``Step``, its table, its
log) for the units' whole times, and this family's own sub-scopes,
which ``trace_lm`` does not know: ``mix`` (the short convolution's
gates and taps) beside ``proj`` and ``core``. Floors from
``flops_conv_lm.py``.

``value``:

* ``types`` (with ``types=[...]``): ``trace_lm``'s: ms a step of the
  units of those types, all passes;
* ``parts`` (with ``types=[...]``, ``parts=[...]``): ms a step of
  those units' forward and backward under those sub-scopes;
* ``conv_roofline``: percent: the floor of the short-convolution
  units (their products' FLOPs at the peak rate plus their mix's
  bytes at the peak bandwidth, forward once and the backward) over
  the units' WHOLE forward and backward time: XLA may fuse a gate into
  a product's epilogue, the fused operation carries one scope, and a
  share of ``mix`` alone could then read over 100%;
* ``core_roofline``: percent: the floor of the attention cores (the
  causal triangle at the published head size, whatever lowers it) over
  the time of their ``core`` sub-scopes.

A program without the scopes (a parent commit, a CPU) gives no value
and raises nothing.
"""

import collections

from benchmark import flops_conv_lm, trace_reduce
from benchmark.readers import trace_lm

CONV, ATTENTION = flops_conv_lm.CONV, flops_conv_lm.ATTENTION
KEY = "_trace_conv_lm"
PARTS = ("proj", "mix", "core")


def by_part(context):
    """``{(unit index, part): seconds a step}`` of the train program's
    forward and backward, mean over the devices; made once a run. None
    where :func:`trace_lm.step` finds nothing to read."""
    if KEY in context:
        return context[KEY]
    context[KEY] = None
    if trace_lm.step(context) is None:
        return None
    from benchmark.readers import trace_scopes
    trace, traced = context["trace"], context["traced"]
    names = trace_reduce.metadata_stats(
        trace_scopes.trace_path(), wanted=(trace_scopes.OP_NAME_STAT,))
    share = 1.0 / (1e9 * traced[trace_lm.STEPS] * len(trace.devices))
    parts = collections.Counter()
    for device in trace.devices:
        op_names = {event: stats.get(trace_scopes.OP_NAME_STAT)
                    for event, stats in names.get(device.name, {}).items()}
        for op in device.ops:
            if trace_lm.PROGRAM not in op.program or \
                    op.bucket == trace_reduce.COLLECTIVE_BUCKET:
                continue
            op_name = op_names.get(op.name)
            row, which = trace_lm.parse(op_name)
            if which in ("forward", "backward"):
                for part in trace_lm.sub_scopes(op_name, PARTS):
                    parts[row[0], part] += op.self_ns * share
    context[KEY] = parts
    layers = context["config"]["layers"]
    context["log"]("mixers by sub-scope, ms a step: %s" % "  ".join(
        "u%02d %s" % (i, " ".join(
            "%s %.3f" % (part, parts[i, part] * 1e3) for part in PARTS
            if parts[i, part]))
        for i, d in enumerate(layers) if d["type"] in (CONV, ATTENTION)))
    return parts


def read(context, value, types=None, parts=None):
    if value == "types":
        return trace_lm.read(context, value, types=types)
    made = by_part(context)
    if made is None:
        return None
    config = context["config"]
    layers, peaks = config["layers"], context["peaks"]
    dim, positions = layers[0]["dim"], layers[0]["positions"]
    if value == "parts":
        return sum(made[i, part] for i, d in enumerate(layers)
                   if d["type"] in types for part in parts) * 1e3 or None
    if peaks is None:
        return None
    if value == "conv_roofline":
        units = [i for i, d in enumerate(layers) if d["type"] == CONV]
        seconds = sum(trace_lm.step(context).fwd_bwd[i] for i in units)
        if not seconds:
            return None
        floors = [flops_conv_lm.short_conv_floor_s(
            layers[i], dim, positions, config["batch"], peaks)
            for i in units]
        context["log"]("short_conv_roofline: of the floor the mix's "
                       "bytes are %.3f ms a step" % (
                           sum(mix for _, mix in floors) * 1e3))
        return trace_lm._share(
            context, "short_conv_roofline", sum(t for t, _ in floors),
            "compute+memory", seconds)
    if value == "core_roofline":
        units = [i for i, d in enumerate(layers) if d["type"] == ATTENTION]
        seconds = sum(made[i, "core"] for i in units)
        if not seconds:
            return None
        floors = [flops_conv_lm.attention_core_floor_s(
            layers[i], positions, config["batch"], peaks) for i in units]
        return trace_lm._share(
            context, "gqa64_core_roofline", sum(t for t, _ in floors),
            "/".join(sorted({b for _, b in floors})), seconds)
    raise ValueError("trace_conv_lm: no value %r" % (value,))
