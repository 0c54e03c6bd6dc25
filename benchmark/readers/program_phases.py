"""Reader ``program_phases``: where the process's time went before its
first steady epoch, from the program's own start-up rows.

The program records every start-up phase as a row with a start, an
end and a parent (``veles_tpu/telemetry/profiler.py`` ``phase_rows``:
``trainer_build`` over ``dataset_stage``, ``model_residency`` and
``dataset_shard``; ``params_place``; ``segment_first_call``;
``cost_harvest``; ``epoch``) and folds in what JAX reports of every
program it builds (``trace``, ``lower``, ``build`` and, inside it,
``cache_read``, each with ``program`` and ``cause``: ``harvest`` under
a cost harvest, else ``call``). SET-UP is every row that starts
before the first STEADY epoch: the first ``epoch`` row in which
nothing was built, which for the ``epochs`` driver is the window's
first. The rows stand on the wall clock, so the span from
``process_started()`` to that epoch is ``setup_s`` to within the
interpreter's own start.

Every instant a row covers is given to ONE row, the innermost (of the
rows that cover it, the one that started last): a row's self time is
its duration less what its children cover, and head + self times +
rest = the span, by construction. ``value``:

* ``data_stage`` (s): ``dataset_stage`` + ``dataset_shard`` rows, less
  what JAX built inside them (which is counted below);
* ``trace_lower`` (s): ``trace`` + ``lower`` rows of every program,
  cause ``call``: Python's share, which no cache shortens;
* ``build`` (s): ``build`` rows, cause ``call``: XLA's compile cold;
  the key, the fetch and the deserialization warm (``cache_read`` is
  part of it; the table splits it out);
* ``cost_harvest`` (s): ``cost_harvest`` rows' wall time, their own
  stages (cause ``harvest``) included: the four are disjoint;
* ``accounted`` (%): what the set-up rows cover of the span, the
  instrument's own health. What they do not cover is the HEAD (process
  start to the first row: imports and devices) and the REST (the
  benchmark's own reference, data and comparison, and the program's
  host work between phases).

The first call of a run reduces the rows, keeps the result in
``context`` and logs one table. A program without ``phase_rows`` (a
checkout from before PR 35), or a run in which no steady epoch was
seen, gives no value.
"""

import collections

KEY = "_program_phases"
STAGES = ("trace", "lower", "build", "cache_read")
DATA = ("dataset_stage", "dataset_shard")
#: rows shorter than this are summed into one line of the table
SHOWN_S = 0.010


def first_steady_epoch(rows):
    steady = [row for row in rows if row.name == "epoch"
              and row.attrs.get("builds") == 0]
    return min(steady, key=lambda row: row.start) if steady else None


def self_times(rows, horizon):
    """``{row id: seconds}``: every instant before ``horizon`` that
    some row covers, given to the innermost row that covers it."""
    spans = sorted((row.start, row.id, min(row.end, horizon))
                   for row in rows)
    edges = sorted({edge for start, _, end in spans
                    for edge in (start, end)})
    own = collections.defaultdict(float)
    covering, opened = [], 0
    for lo, hi in zip(edges, edges[1:]):
        while opened < len(spans) and spans[opened][0] <= lo:
            covering.append(spans[opened])
            opened += 1
        covering = [span for span in covering if span[2] >= hi]
        if covering:
            own[max(covering)[1]] += hi - lo
    return own


def bucket(row, by_id):
    """The metric a row's self time belongs to, or None."""
    if row.name in STAGES:
        if row.attrs.get("cause") == "harvest":
            return "cost_harvest"
        return "trace_lower" if row.name in ("trace", "lower") else "build"
    while row is not None:
        if row.name == "cost_harvest":
            return "cost_harvest"
        if row.name in DATA:
            return "data_stage"
        row = by_id.get(row.parent)
    return None


def depth(row, by_id):
    n = 0
    while row.parent in by_id:
        row, n = by_id[row.parent], n + 1
    return n


def describe(attrs):
    return " ".join("%s=%s" % (k, attrs[k]) for k in sorted(attrs)
                    if k not in ("program", "cause"))


def account(rows, started, source, log):
    """The five values of one run's ``rows`` against the process's
    start, or None where no steady epoch was seen; logs the table."""
    steady = first_steady_epoch(rows)
    if steady is None:
        log("set-up by the program's phases: %d rows and no epoch in "
            "which nothing was built: no value" % len(rows))
        return None
    horizon = steady.start
    setup = sorted((row for row in rows if row.start < horizon),
                   key=lambda row: (row.start, row.id))
    by_id = {row.id: row for row in setup}
    own = self_times(setup, horizon)
    span = horizon - started
    head = max(0.0, min([row.start for row in setup] + [horizon])
               - started)
    covered = sum(own.values())
    rest = span - head - covered
    totals = collections.defaultdict(float)
    for row in setup:
        totals[bucket(row, by_id)] += own[row.id]

    log("set-up by the program's own phases: process start (%s) to the "
        "first steady epoch (epoch %s): %.3f s in %d rows"
        % (source, steady.attrs.get("epoch"), span, len(setup)))
    log("%9s %9s %9s  %s" % ("at s", "took s", "self s", "phase"))
    log("%9.3f %9.3f %9.3f  <head: before the first row>"
        % (0.0, head, head))
    hidden = [row for row in setup
              if min(row.end, horizon) - row.start < SHOWN_S]
    for row in setup:
        if row.end - row.start < SHOWN_S:
            continue
        name = row.name
        if row.name in STAGES:
            name = "%s %s (%s)" % (row.name, row.attrs.get("program"),
                                   row.attrs.get("cause"))
        log(("%9.3f %9.3f %9.3f  %s%s %s" % (
            row.start - started, min(row.end, horizon) - row.start,
            own[row.id], "  " * depth(row, by_id), name,
            describe(row.attrs))).rstrip())
    log("%9s %9s %9.3f  <%d rows under %.3f s each: %s>" % (
        "", "", sum(own[row.id] for row in hidden), len(hidden), SHOWN_S,
        ", ".join("%d %s" % (n, name) for name, n in collections.Counter(
            row.name for row in hidden).most_common())))
    log("%9s %9s %9.3f  <rest: between the rows>" % ("", "", rest))

    programs = collections.defaultdict(lambda: collections.defaultdict(
        float))
    for row in setup:
        parent = by_id.get(row.parent)
        if row.name in STAGES and (
                row.name == "cache_read" or parent is None
                or parent.name not in STAGES):
            programs[row.attrs.get("program")][
                row.attrs.get("cause"), row.name] += (
                min(row.end, horizon) - row.start)
    log("by program, s: trace lower build (of which cache_read), "
        "caused by a call | by the cost harvest")
    order = sorted(programs, key=lambda p: -sum(
        v for (_, stage), v in programs[p].items()
        if stage != "cache_read"))
    shown = [p for p in order if sum(
        v for (_, stage), v in programs[p].items()
        if stage != "cache_read") >= SHOWN_S]
    for program in shown:
        log("  %-32s %s" % (program, " | ".join(
            "%8.3f %8.3f %8.3f (%7.3f)" % tuple(
                programs[program][cause, stage] for stage in STAGES)
            for cause in ("call", "harvest"))))
    log("  %d more programs under %.3f s each: %.3f s" % (
        len(order) - len(shown), SHOWN_S, sum(
            v for p in order[len(shown):]
            for (_, stage), v in programs[p].items()
            if stage != "cache_read")))
    made = {
        "data_stage": totals["data_stage"],
        "trace_lower": totals["trace_lower"],
        "build": totals["build"],
        "cost_harvest": totals["cost_harvest"],
        "accounted": 100.0 * covered / span if span > 0 else 0.0,
    }
    log("head %.3f s + rows' self times %.3f s + rest %.3f s = %.3f s; "
        "data stage %.3f, trace and lower %.3f, build %.3f, cost harvest "
        "%.3f s; accounted %.2f%%" % (
            head, covered, rest, span, made["data_stage"],
            made["trace_lower"], made["build"], made["cost_harvest"],
            made["accounted"]))
    made.update(head=head, rest=rest, span=span, covered=covered,
                self_times=dict(own))
    return made


def reduce(context):
    if KEY in context:
        return context[KEY]
    context[KEY] = None
    try:
        from veles_tpu.telemetry import profiler
    except ImportError:
        return None
    rows = getattr(profiler, "phase_rows", None)
    started = getattr(profiler, "process_started", None)
    if rows is None or started is None:
        return None
    moment, source = started()
    context[KEY] = account(rows(), moment, source, context["log"])
    return context[KEY]


def read(context, value):
    made = reduce(context)
    return None if made is None else made[value]
