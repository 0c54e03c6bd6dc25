"""Reader ``trace_lm``: a token model's train step on the device, by
the unit TYPES of its configuration and the SUB-SCOPES its units name
themselves with; shares of two kernels' rooflines; and one counter.

It reads the scopes ``readers/trace_scopes.py`` reads, with that
reader's ``parse`` and ``Table``, and logs the per-unit table
(forward, backward, update; the coverage) for a configuration whose
layers ``flops.py`` has no rule for. Behind a unit's scope the program
puts plain path elements (grammar: ``veles_tpu/train/step.py``
``device_scope``): ``route``, ``experts`` and ``shared`` in a sparse
layer, ``proj`` and ``core`` in latent attention, and the stream
(``main``, or a side branch's name such as ``mtp``) in the head and in
``veles.loss``. Floors come from ``flops_lm.py``.

``value``:

* ``types`` (with ``types=[...]``): ms a step of the units of those
  layer types, all three passes;
* ``moe_route``: ms a step of the sparse layers' forward and backward
  outside their ``experts`` and ``shared`` sub-scopes: scores, top-k,
  sort, gather, combine, the norm;
* ``branch`` (with ``branch="mtp"``): ms a step of the units of that
  side branch, its pass through the head and its loss;
* ``head_loss``: ms a step of the head (every stream) and
  ``veles.loss``;
* ``expert_gemm_roofline``: percent: the floor of the grouped
  products for the rows REALLY routed to the experts held here (the
  program's gauge ``veles_moe_expert_tokens``, a mean over the last
  train sweep's steps, which are the traced ones) over the ``experts``
  sub-scopes' time, so rows of padding and what the backward pass
  recomputes read as lost share;
* ``mla_core_roofline``: percent: causal FLOPs and the least bytes of
  the attention cores over the ``core`` sub-scopes' time;
* ``expert_load_max_over_mean``: the program's gauge
  ``veles_moe_load_max_over_mean``, the largest over the sparse
  layers.

The grouped products themselves reach the trace WITHOUT a scope: the
v5e compiler expands ``lax.ragged_dot`` into custom calls whose
``op_name`` is ``ragged-dot-none`` (and ``ragged-dot-metadata``, the
tile bookkeeping), whatever scope the product was traced under. Their
time is counted to the sparse layers' ``experts`` part as a whole
(``types`` with ``moe``, ``expert_gemm_roofline``), and a side branch
gets the share of its sparse layers among all (same shapes each).
The first call also logs the largest operations no scope claims.

A program without the scopes or the gauges (a parent commit, a CPU)
gives no value and raises nothing.
"""

import collections
import re

from benchmark import flops_lm, trace_reduce
from benchmark.readers import trace_scopes

KEY = "_trace_lm"
PROGRAM, STEPS = "train_segment", "train_steps"
UNIT_PARTS = ("route", "experts", "shared", "proj", "core")
#: ``op_name`` of what the compiler makes of ``lax.ragged_dot``
GROUPED = "ragged-dot"
#: a scope that JAX's transposition wrapped, at any depth of the name
TRANSPOSED = re.compile(r"transpose\((?:[\w.\-]+\()*veles\.")


def parse(op_name):
    """``trace_scopes.parse``, and the pass of a rematerialized unit
    put right: its backward operations read ``transpose(jvp(veles.u03.
    x))/jvp(veles.u03.x)/checkpoint/...``, the inner scope without the
    ``transpose(`` that the outer one has, and the reader of the
    innermost scope alone would count them as forward."""
    row, which = trace_scopes.parse(op_name)
    if which == "forward" and TRANSPOSED.search(
            (op_name or "").split(";", 1)[0]):
        which = "backward"
    return row, which


def sub_scopes(op_name, names):
    """Those of ``names`` that are path elements of ``op_name`` behind
    its innermost ``veles.`` scope (of the first of the names XLA
    joined with ``;``), wrappers' brackets aside."""
    parts = (op_name or "").split(";", 1)[0].split("/")
    last = max((i for i, part in enumerate(parts) if "veles." in part),
               default=None)
    if last is None:
        return set()
    return {part.strip("()") for part in parts[last + 1:]} & set(names)


def stream_of(op_name, streams):
    """The stream (``main`` or a branch) an operation of the head or of
    ``veles.loss`` belongs to: a path element anywhere in its name."""
    parts = {part.strip("()") for part in
             (op_name or "").split(";", 1)[0].split("/")}
    return next((s for s in streams if s in parts), None)


def gauge_series(name):
    """``{labels as a sorted tuple: value}`` of a gauge of the
    program's registry; empty where the program has no such gauge."""
    try:
        from veles_tpu.telemetry.registry import get_registry
        metric = get_registry().get(name)
        if metric is None:
            return {}
        return {tuple(sorted(labels.items())): child.value
                for labels, child in metric.series()}
    except Exception:
        return {}


class Step(object):
    """Device self time a step of one traced program, seconds, mean
    over the devices: by unit and sub-scope, and by stream for the
    head and the loss."""

    def __init__(self, trace, names, config, steps):
        layers = config["layers"]
        streams = ["main"] + sorted(
            {d["branch"] for d in layers if d.get("branch")})
        self.by_part = collections.Counter()  # (index, part) -> s
        self.by_unit = collections.Counter()  # index -> s, all passes
        self.fwd_bwd = collections.Counter()  # index -> s, no update
        self.by_stream = collections.Counter()  # head and loss, stream
        self.loss = 0.0
        self.grouped = 0.0  # the grouped products' custom calls
        self.unclaimed = collections.Counter()  # (event, category) -> s
        head = len(layers) - 1
        share = 1.0 / (1e9 * steps * len(trace.devices))
        for device in trace.devices:
            op_names = names.get(device.name, {})
            for op in device.ops:
                if PROGRAM not in op.program or \
                        op.bucket == trace_reduce.COLLECTIVE_BUCKET:
                    continue
                op_name = op_names.get(op.name)
                row, which = parse(op_name)
                seconds = op.self_ns * share
                if which:  # a unit
                    index = row[0]
                    self.by_unit[index] += seconds
                    if which != "update":
                        self.fwd_bwd[index] += seconds
                        for part in sub_scopes(op_name, UNIT_PARTS):
                            self.by_part[index, part] += seconds
                    if index == head:
                        self.by_stream[stream_of(op_name, streams)] \
                            += seconds
                elif row == "veles.loss":
                    self.loss += seconds
                    self.by_stream[stream_of(op_name, streams)] += seconds
                elif (op_name or "").startswith(GROUPED):
                    self.grouped += seconds
                elif row == trace_scopes.UNSCOPED:
                    self.unclaimed[op.name.split(" = ", 1)[0],
                                   op.category] += seconds

    def of_types(self, layers, types):
        return sum(seconds for index, seconds in self.by_unit.items()
                   if index < len(layers)
                   and layers[index]["type"] in types) \
            + (self.grouped if "moe" in types else 0.0)

    def part(self, layers, ltype, part):
        return sum(seconds for (index, p), seconds in self.by_part.items()
                   if p == part and index < len(layers)
                   and layers[index]["type"] == ltype) \
            + (self.grouped if (ltype, part) == ("moe", "experts") else 0.0)


def step(context):
    """The run's :class:`Step`, made and logged once and kept in
    ``context``; None where the trace has no device operation or no
    scope."""
    if KEY in context:
        return context[KEY]
    context[KEY] = None
    trace, traced = context["trace"], context["traced"]
    path = trace_scopes.trace_path() \
        if trace is not None and traced and traced.get(STEPS) else None
    if path is None:
        return None
    names = {plane: {event: stats.get(trace_scopes.OP_NAME_STAT)
                     for event, stats in events.items()}
             for plane, events in trace_reduce.metadata_stats(
                 path, wanted=(trace_scopes.OP_NAME_STAT,)).items()}
    rows = {plane: {event: parse(name)
                    for event, name in events.items()}
            for plane, events in names.items()}
    table = trace_scopes.Table(trace, rows, PROGRAM, traced[STEPS])
    if not table.scoped:
        return None
    log, config = context["log"], context["config"]
    for line in table.lines(config, {}, []):
        log(line)
    log("  coverage: %.2f%% of the program's device time carries a "
        "scope" % (100.0 * table.scoped / max(
            table.total - table.plain[trace_reduce.COLLECTIVE_BUCKET],
            1e-12)))
    made = context[KEY] = Step(trace, names, config, traced[STEPS])
    parts = collections.defaultdict(dict)
    for (index, part), seconds in made.by_part.items():
        parts[index][part] = seconds
    for index in sorted(parts):
        log("  u%02d %-18s %s" % (
            index, config["layers"][index]["type"], "  ".join(
                "%s %.3f" % (p, s * 1e3)
                for p, s in sorted(parts[index].items()))))
    log("  grouped products' custom calls (%s*, no scope; counted to "
        "the sparse layers' experts): %.3f ms" % (GROUPED,
                                                   made.grouped * 1e3))
    log("  largest operations no scope claims, ms: %s" % "  ".join(
        "%s[%s] %.3f" % (name, category, seconds * 1e3)
        for (name, category), seconds in made.unclaimed.most_common(10)))
    log("  head and loss by stream, ms: %s" % "  ".join(
        "%s %.3f" % (name, seconds * 1e3)
        for name, seconds in sorted(made.by_stream.items(),
                                    key=lambda kv: str(kv[0]))))
    return made


def _share(context, name, floor, bound, seconds):
    context["log"]("%s: floor %.3f ms a step, %s-bound; measured "
                   "%.3f ms" % (name, floor * 1e3, bound, seconds * 1e3))
    return 100.0 * floor / seconds


def read(context, value, types=None, branch=None):
    if value == "expert_load_max_over_mean":
        series = gauge_series("veles_moe_load_max_over_mean")
        return max(series.values()) if series else None
    made = step(context)
    if made is None:
        return None
    layers, peaks = context["config"]["layers"], context["peaks"]
    dim, positions = layers[0]["dim"], layers[0]["positions"]
    if value == "types":
        return made.of_types(layers, types) * 1e3 or None
    if value == "moe_route":
        total = sum(seconds for index, seconds in made.fwd_bwd.items()
                    if index < len(layers)
                    and layers[index]["type"] == "moe")
        # the grouped products' custom calls carry no scope: they are
        # in ``part`` and were never in ``total``
        return (total + made.grouped
                - made.part(layers, "moe", "experts")
                - made.part(layers, "moe", "shared")) * 1e3 or None
    if value == "branch":
        units = sum(seconds for index, seconds in made.by_unit.items()
                    if index < len(layers)
                    and layers[index].get("branch") == branch)
        sparse = [d.get("branch") for d in layers if d["type"] == "moe"]
        grouped = made.grouped * sparse.count(branch) / max(len(sparse), 1)
        return (units + grouped + made.by_stream[branch]) * 1e3 or None
    if value == "head_loss":
        return (made.by_unit[len(layers) - 1] + made.loss) * 1e3 or None
    if peaks is None:
        return None
    if value == "expert_gemm_roofline":
        seconds = made.part(layers, "moe", "experts")
        tokens = collections.Counter()
        for labels, count in gauge_series(
                "veles_moe_expert_tokens").items():
            tokens[dict(labels)["unit"]] += count
        if not seconds or not tokens:
            return None
        floor, bounds = 0.0, set()
        for unit, rows in tokens.items():
            descr = layers[int(unit[1:3])]
            t, bound = flops_lm.expert_gemm_floor_s(descr, dim, rows, peaks)
            floor += t
            bounds.add(bound)
        context["log"]("expert rows a step, by unit: %s" % "  ".join(
            "%s %.0f" % kv for kv in sorted(tokens.items())))
        return _share(context, value, floor, "/".join(sorted(bounds)),
                      seconds)
    if value == "mla_core_roofline":
        seconds = made.part(layers, "latent_attention", "core")
        if not seconds:
            return None
        floors = [flops_lm.attention_core_floor_s(
            d, positions, context["config"]["batch"], peaks)
            for d in layers if d["type"] == "latent_attention"]
        return _share(context, value, sum(t for t, _ in floors),
                      "/".join(sorted({b for _, b in floors})), seconds)
    raise ValueError("trace_lm: no value %r" % (value,))
