"""Reader ``trace_indexed_lm``: the train step of the grouped-query
attention under a learned selection of keys family on the device.
``trace_lm``'s reading of the scopes (its ``Step``, its table, its
log) for the units' whole times, and this family's own sub-scopes,
which ``trace_lm`` does not know: ``index`` (the index's three
products, its norm and rotary embedding, the index scores), ``select``
(the search for the selected keys), ``core``, ``index_loss`` (the
head-mean of the probabilities and the KL). Floors from
``flops_indexed_lm.py``.

``value``:

* ``types`` (with ``types=[...]``): ``trace_lm``'s: ms a step of the
  units of those types, all passes;
* ``parts`` (with ``parts=[...]``): ms a step of the attention units'
  forward and backward under those sub-scopes;
* ``core_roofline``: percent: the floor of the attention cores over
  the SELECTED pairs (what the mathematics needs, whatever the
  lowering computes) over the time of their ``core`` sub-scopes;
* ``selected_over_causal``: the query-key pairs a head and sequence
  that the units' indexes selected over the causal triangle's: the
  program's gauge ``veles_attention_selected_per_step{unit}``, counted
  on the device over the last train sweep, or where a program has only
  the traced shapes' ``veles_attention_selected_pairs{unit}``, that;
  the mean over the units. 0.4375 at 8,192 positions and 2,048 keys,
  or the selection is off.

A program without the scopes or the gauges (a parent commit, a CPU)
gives no value and raises nothing.
"""

import collections

from benchmark import flops_indexed_lm, trace_reduce
from benchmark.readers import trace_lm

ATTENTION = flops_indexed_lm.ATTENTION
KEY = "_trace_indexed_lm"
PARTS = ("index", "select", "core", "index_loss")


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
    context["log"]("attention units by sub-scope, ms a step: %s" % "  ".join(
        "u%02d %s" % (i, " ".join(
            "%s %.3f" % (part, parts[i, part] * 1e3) for part in PARTS))
        for i, d in enumerate(layers) if d["type"] == ATTENTION))
    return parts


def read(context, value, types=None, parts=None):
    if value == "types":
        return trace_lm.read(context, value, types=types)
    layers = context["config"]["layers"]
    units = [i for i, d in enumerate(layers)
             if d["type"] == ATTENTION and d.get("index")]
    positions = layers[0]["positions"]
    if value == "selected_over_causal":
        counted = trace_lm.gauge_series("veles_attention_selected_per_step")
        series = counted or trace_lm.gauge_series(
            "veles_attention_selected_pairs")
        if not series:
            return None
        context["log"]("pairs selected a head and sequence (%s): %s" % (
            "counted on the device" if counted else "by the traced shapes",
            "  ".join("%s %.0f" % (dict(labels).get("unit"), pairs)
                      for labels, pairs in sorted(series.items()))))
        return sum(series.values()) / len(series) \
            / flops_indexed_lm.causal_pairs(positions)
    made = by_part(context)
    if made is None:
        return None
    if value == "parts":
        return sum(made[i, part] for i in units for part in parts) * 1e3 \
            or None
    if value == "core_roofline":
        peaks = context["peaks"]
        seconds = sum(made[i, "core"] for i in units)
        if not seconds or peaks is None:
            return None
        floors = [flops_indexed_lm.selected_core_floor_s(
            layers[i], positions, context["config"]["batch"], peaks)
            for i in units]
        return trace_lm._share(
            context, "dsa_core_roofline", sum(t for t, _ in floors),
            "/".join(sorted({b for _, b in floors})), seconds)
    raise ValueError("trace_indexed_lm: no value %r" % (value,))
