"""Reader ``trace_scopes``: device time of a program's operations by
the UNIT and PASS the program itself names them with, per step, in
milliseconds; the per-unit table; and two numbers on the table.

The program wraps what it traces in ``jax.named_scope`` (grammar:
``veles_tpu/train/step.py`` ``device_scope``): ``veles.in`` the
minibatch gather, ``veles.u<ii>.<unit name>`` forward unit ``<ii>``,
``veles.loss``, ``veles.update.u<ii>.<unit name>`` its solver update,
``veles.gradnorm``. JAX wraps the scope under ``value_and_grad``, so a
unit's operation is of the BACKWARD pass iff its scope sits inside
``transpose(``, of the UPDATE iff under ``veles.update.``, else of the
FORWARD pass (the residuals a forward saves for its backward count as
forward). The scope reaches the trace as the HLO ``op_name``, which
the v5e's profiler keeps as the stat ``tf_op`` of an ``XLA Ops``
event's metadata (looked at by hand in PR 24); it is joined to
``trace_reduce``'s operations by the event's name, as ``source`` is.

A fusion carries the ``op_name`` of the one instruction XLA took its
metadata from: where XLA fuses across a unit's boundary, or a weight's
update into the matmul that makes its gradient, the whole fusion is
counted where that instruction was. The by-file buckets blur in the
same way. Collectives stay out of every scope, as in ``trace_reduce``
(a combined gradient all-reduce carries one arbitrary layer's name):
their row is ``<collective>``, and the table shows, for reading only,
the collective time that carried each row's name.

``value``: ``forward``, ``backward`` or ``update`` (ms a step, summed
over the units), ``conv_worst_roofline`` (the lowest roofline share
among conv units, %: the unit's own floor from ``flops.layer_costs``,
which counts the three passes together and leaves the update out, over
its forward + backward time) or ``coverage`` (the share of the
program's non-collective device time that carries any ``veles.``
scope, %). The trace file is the newest under
``<root>/.veles_cache/benchmark_trace``, where ``harness.run_cell``
had the driver write it. The first call of a run reduces the trace,
keeps the result in ``context`` and logs the table of each traced
program; the table's total is ``train_step_device_ms``. A trace in
which no operation carries a ``veles.`` scope (a program from before
PR 24, a cache filled by one, a CPU) gives no value.
"""

import collections
import os
import re

from benchmark import flops, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OP_NAME_STAT = "tf_op"
#: (part of the program's name, the driver's count of its steps,
#: whether it trains: only then do the three-pass floors apply)
PROGRAMS = (("train_segment", "train_steps", True),
            ("eval_segment", "eval_steps", False))
PASSES = ("forward", "backward", "update")
UNSCOPED = "<unscoped>"
KEY = "_trace_scopes"

#: one path element of an op_name that holds a scope: the wrappers JAX
#: put around it, then a unit (with or without ``update.``) or one of
#: the three plain scopes
SCOPE = re.compile(
    r"(?:^|/)((?:[\w.\-]+\()*)veles\."
    r"(?:(update\.)?u(\d+)\.([\w.\-]+)|(in|loss|gradnorm))\)*(?=[/:]|$)")


def trace_path():
    return trace_reduce.find_xplane(
        os.path.join(ROOT, ".veles_cache", "benchmark_trace"))


def parse(op_name):
    """``(row, pass)`` of an operation's ``op_name``: ``((index, unit
    name), "forward" | "backward" | "update")`` for a unit,
    ``("veles.in" | "veles.loss" | "veles.gradnorm", None)``, or
    ``(UNSCOPED, None)``. The innermost scope counts, and of the
    names XLA joined with ``;`` for one fusion, the first."""
    found = SCOPE.findall((op_name or "").split(";", 1)[0])
    if not found:
        return UNSCOPED, None
    wrappers, update, index, unit, plain = found[-1]
    if plain:
        return "veles." + plain, None
    return (int(index), unit), (
        "update" if update else
        "backward" if "transpose(" in wrappers else "forward")


class Table(object):
    """One program's device self time a step, seconds, mean over the
    devices, by row and pass."""

    def __init__(self, trace, rows, program, steps):
        """``rows``: ``{plane: {event name: parse(its op_name)}}``."""
        self.program, self.steps = program, steps
        self.units = collections.defaultdict(
            lambda: dict.fromkeys(PASSES, 0.0))
        self.plain = collections.Counter()
        self.named_collective = collections.Counter()
        self.unscoped_categories = collections.Counter()
        share = 1.0 / (1e9 * steps * len(trace.devices))
        for device in trace.devices:
            parsed = rows.get(device.name, {})
            for op in device.ops:
                if program not in op.program:
                    continue
                seconds = op.self_ns * share
                row, which = parsed.get(op.name, (UNSCOPED, None))
                if op.bucket == trace_reduce.COLLECTIVE_BUCKET:
                    self.plain[op.bucket] += seconds
                    self.named_collective[row] += seconds
                elif which:
                    self.units[row][which] += seconds
                else:
                    self.plain[row] += seconds
                    if row == UNSCOPED:
                        self.unscoped_categories[op.category] += seconds
        self.scoped = sum(sum(u.values()) for u in self.units.values()) \
            + sum(v for k, v in self.plain.items() if k.startswith("veles."))
        self.total = self.scoped + self.plain[UNSCOPED] \
            + self.plain[trace_reduce.COLLECTIVE_BUCKET]

    def of_pass(self, which):
        return sum(u[which] for u in self.units.values())

    def floors(self, config, peaks, chips):
        """``{row: (kind, floor seconds, bound)}`` for the conv and
        dense units whose name says they are ``config["layers"][i]``,
        and the lines that say which units got none."""
        costs = flops.layer_costs(config["layers"],
                                  flops.input_shape(config))
        batch = config["batch"] // chips
        floors, notes = {}, []
        for index, unit in sorted(self.units):
            if index >= len(costs) or not unit.startswith(
                    costs[index]["type"]):
                notes.append("u%02d.%s is not layers[%d] of the "
                             "configuration: no floor" % (index, unit, index))
                continue
            cost = costs[index]
            if cost["kind"] not in ("conv", "dense"):
                continue
            t_flops = cost["flops"] * batch / peaks["bf16_flops_per_s"]
            t_bytes = (cost["act_bytes"] * batch + cost["weight_bytes"]) \
                / peaks["hbm_bytes_per_s"]
            floors[index, unit] = (
                cost["kind"], max(t_flops, t_bytes),
                "compute" if t_flops >= t_bytes else "memory")
        return floors, notes

    def roofline_shares(self, floors):
        """``{row: percent}``: floor over forward + backward."""
        return {row: 100.0 * floor / (self.units[row]["forward"]
                                      + self.units[row]["backward"])
                for row, (_, floor, _) in floors.items()
                if self.units[row]["forward"] + self.units[row]["backward"]}

    def lines(self, config, floors, notes):
        def ms(seconds):
            return "%8.3f" % (seconds * 1e3) if seconds else "       ."
        shares = self.roofline_shares(floors)
        form = "  %-22s %-12s" + " %8s" * 5 + " %6s %-8s %10s"
        out = ["units of %s: device self time a step, ms, mean over the "
               "chips, %d steps traced" % (self.program, self.steps),
               form % ("scope", "type", "forward", "backward", "update",
                       "all", "floor", "share", "bound", "collective")]
        for row in sorted(self.units):
            unit = self.units[row]
            _, floor, bound = floors.get(row, ("", 0.0, ""))
            out.append(form % (
                "u%02d.%s" % row,
                config["layers"][row[0]]["type"]
                if row[0] < len(config["layers"]) else "?",
                ms(unit["forward"]), ms(unit["backward"]),
                ms(unit["update"]), ms(sum(unit.values())), ms(floor),
                "%.1f" % shares[row] if row in shares else "", bound,
                ms(self.named_collective[row])))
        for row in ("veles.in", "veles.loss", "veles.gradnorm",
                    trace_reduce.COLLECTIVE_BUCKET, UNSCOPED):
            if row in self.plain or row in self.named_collective:
                out.append(form % (
                    row, "", "", "", "", ms(self.plain[row]), "", "", "",
                    "" if row == trace_reduce.COLLECTIVE_BUCKET
                    else ms(self.named_collective[row])))
        out.append(form % (
            "total", "", ms(self.of_pass("forward")),
            ms(self.of_pass("backward")), ms(self.of_pass("update")),
            ms(self.total), "", "", "", ""))
        if self.unscoped_categories:
            out.append("  %s by hlo_category: %s" % (UNSCOPED, ", ".join(
                "%s %.3f" % (name or "none", seconds * 1e3) for name, seconds
                in self.unscoped_categories.most_common(5))))
        return out + ["  " + note for note in notes]


def tables(context):
    """``{program: (Table, floors)}`` of the run's trace, made and
    logged once and kept in ``context``; empty where the trace has no
    device operation or no scope."""
    if KEY in context:
        return context[KEY]
    made = context[KEY] = {}
    trace, traced = context["trace"], context["traced"]
    path = trace_path() if trace is not None and traced else None
    if path is None:
        return made
    rows = {plane: {event: parse(stats.get(OP_NAME_STAT))
                    for event, stats in events.items()}
            for plane, events in trace_reduce.metadata_stats(
                path, wanted=(OP_NAME_STAT,)).items()}
    for program, steps, trains in PROGRAMS:
        if not traced.get(steps):
            continue
        table = Table(trace, rows, program, traced[steps])
        if not table.scoped:
            continue
        floors, notes = table.floors(
            context["config"], context["peaks"], context["chips"]) \
            if trains and context["peaks"] else ({}, [])
        made[program] = table, floors
        for line in table.lines(context["config"], floors, notes):
            context["log"](line)
    return made


def read(context, program, value):
    made = tables(context)
    if program not in made:
        return None
    table, floors = made[program]
    if value in PASSES:
        return table.of_pass(value) * 1e3
    if value == "coverage":
        return 100.0 * table.scoped / (
            table.total - table.plain[trace_reduce.COLLECTIVE_BUCKET])
    if value == "conv_worst_roofline":
        shares = {row: share for row, share
                  in table.roofline_shares(floors).items()
                  if floors[row][0] == "conv"}
        if not shares:
            return None
        worst = min(shares, key=shares.get)
        context["log"]("conv_worst_roofline: u%02d.%s, %s-bound, floor "
                       "%.3f ms a step" % (worst + (
                           floors[worst][2], floors[worst][1] * 1e3)))
        return shares[worst]
    raise ValueError("trace_scopes: no value %r" % (value,))
