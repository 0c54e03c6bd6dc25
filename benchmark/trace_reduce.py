"""From a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer readers report, with ``jax.profiler.ProfileData`` and thirty
lines that read what it leaves out.

What a TPU trace holds (looked at by hand in PR 22; ``python
benchmark/trace_reduce.py <file>`` prints the same survey of any
trace):

* one plane ``/device:TPU:<n>`` per chip. Its line ``XLA Modules`` has
  one event per program execution (``jit_train_segment(...)``); its
  line ``XLA Ops`` has one event per HLO operation executed, nested
  where an operation contains others (a ``while`` holds every
  operation of every trip of its body). An event's name is the
  operation's HLO text. Its ``hlo_category`` and, where XLA kept it,
  its ``source`` (the file and line of the Python that produced it)
  are stats of the event's METADATA, which ``ProfileData`` does not
  show: ``metadata_stats`` reads them from the file's protobuf wire
  format and they are joined to the events by name.
* the plane ``/host:CPU`` has one line per host thread, with the
  benchmark's ``jax.profiler.TraceAnnotation`` spans by name.

All planes share one clock. The reduction:

* **window**: first ``bench:epoch`` span's start to the last one's
  end (the whole trace if there is none).
* **busy**: per device, the union of the ``XLA Ops`` intervals inside
  the window; ``busy_s`` is the mean over devices.
* **self time**: an operation's duration less the operations nested
  in it, so that a ``while`` or a fusion wrapper is not counted on
  top of its contents.
* **bucket**: ``nn/conv.py`` for ``source=.../veles_tpu/nn/conv.py:190``
  (the path below the program's package, without the line), the base
  name for other files, ``<category>`` for operations without source.
* **program**: the ``XLA Modules`` event that contains the
  operation's start.
* **collectives**: operations whose category or own name says
  all-reduce, all-gather, reduce-scatter, all-to-all or
  collective-permute; their bucket is ``<collective>`` whatever their
  source (a gradient all-reduce carries the source of the layer whose
  gradient it sums). Their time is the self time they hold the
  operation line; while one holds it no other operation of that core
  runs, so that time is exposed. An asynchronous pair (``-start`` /
  ``-done``) also spans the compute issued between its halves: that
  span, less the halves themselves, is collective time that was
  hidden. (In the v5e traces of PR 22 every large collective of the
  partitioned AlexNet step is synchronous: nothing is hidden.)
* **idle gaps**: the window less busy, each gap named by the host
  span that holds its midpoint: a sweep's name, or
  ``bench:epoch_boundary`` inside an epoch but outside both sweeps, or
  ``outside bench:epoch``.
"""

import bisect
import collections
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
EPOCH_SPAN = "bench:epoch"
SWEEP_SPANS = ("bench:train_sweep", "bench:eval_sweep")
PACKAGE = "veles_tpu/"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
COLLECTIVE_BUCKET = "<collective>"

Op = collections.namedtuple(
    "Op", "name start end self_ns bucket category program")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def is_collective(name, category):
    """``name`` is an operation's HLO text: only the part before
    `` = `` is its own name, the rest names its operands."""
    return bool(COLLECTIVE.search(name.split(" = ", 1)[0])
                or COLLECTIVE.search(category or ""))


def bucket_of(source, category):
    if source:
        path = str(source).rsplit(":", 1)[0]
        if PACKAGE in path:
            return path.split(PACKAGE, 1)[1]
        return os.path.basename(path)
    return "<%s>" % (category or "no source")


def _fields(buf):
    """``(number, wire type, value)`` of a protobuf message's fields;
    a length-delimited value is a memoryview, not decoded further."""
    pos, end = 0, len(buf)

    def varint():
        nonlocal pos
        value = shift = 0
        while True:
            byte = buf[pos]
            pos += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while pos < end:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = varint()
        elif wire in (1, 2, 5):
            size = varint() if wire == 2 else 8 if wire == 1 else 4
            value = buf[pos:pos + size]
            pos += size
        else:
            raise ValueError("wire type %d at byte %d" % (wire, pos))
        yield number, wire, value


def metadata_stats(path, wanted=("source", "hlo_category")):
    """``{plane name: {event name: {stat name: text}}}`` from the
    ``event_metadata`` of each plane of an ``.xplane.pb`` (tsl's
    ``xplane.proto``: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 and .stat_metadata = 5, maps with key = 1 and
    value = 2; XEventMetadata.name = 2, .stats = 5; XStatMetadata.name
    = 2; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7, a
    reference to the stat metadata whose name is the text). Lines and
    events are skipped by their length, not read."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, _, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field in (4, 5):
                entry = dict((n, v) for n, _, v in _fields(value))
                if field == 5:
                    stat_names[entry[1]] = next(
                        (bytes(v).decode() for n, _, v
                         in _fields(entry[2]) if n == 2), "")
                else:
                    events.append(entry[2])
        by_event = {}
        for event in events:
            event_name, stats = "", {}
            for field, _, value in _fields(event):
                if field == 2:
                    event_name = bytes(value).decode()
                elif field == 5:
                    stat = dict((n, v) for n, _, v in _fields(value))
                    key = stat_names.get(stat.get(1))
                    if key in wanted:
                        stats[key] = (bytes(stat[5]).decode() if 5 in stat
                                      else stat_names.get(stat.get(7), ""))
            by_event[event_name] = stats
        planes[name] = by_event
    return planes


def _union(intervals):
    """Sorted disjoint union of ``(start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(events):
    """``events``: ``[(start, end, payload)]``. Yields ``(start, end,
    self, payload)`` with the time of nested events taken out."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    stack = []  # [start, end, child_time, payload]
    for start, end, payload in events:
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            yield done[0], done[1], done[1] - done[0] - done[2], done[3]
        if stack:
            stack[-1][2] += min(end, stack[-1][1]) - start
        stack.append([start, end, 0, payload])
    while stack:
        done = stack.pop()
        yield done[0], done[1], done[1] - done[0] - done[2], done[3]


class Device(object):
    """One chip's plane, reduced."""

    def __init__(self, plane, window, metadata):
        self.name = plane.name
        modules, raw = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events)
            elif line.name == OPS_LINE:
                for ev in line.events:
                    stats = metadata.get(ev.name, {})
                    raw.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                (ev.name, stats.get("source"),
                                 stats.get("hlo_category"))))
        self.modules = modules
        lo, hi = window if window else (
            min((s for s, _, _ in raw), default=0),
            max((e for _, e, _ in raw), default=0))
        self.window = (lo, hi)
        self.busy = _union(_clip([(s, e) for s, e, _ in raw], lo, hi))
        self.busy_ns = sum(e - s for s, e in self.busy)
        self.ops = []
        starts = [m[0] for m in modules]
        for start, end, self_ns, (name, source, category) in \
                _self_times(raw):
            if start < lo or start >= hi:
                continue
            i = bisect.bisect_right(starts, start) - 1
            program = modules[i][2] if i >= 0 and start < modules[i][1] \
                else ""
            bucket = (COLLECTIVE_BUCKET if is_collective(name, category)
                      else bucket_of(source, category))
            self.ops.append(Op(name, start, end, self_ns, bucket,
                               str(category or ""), program))

    def gaps(self):
        lo, hi = self.window
        edges = [lo] + [t for pair in self.busy for t in pair] + [hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def self_seconds(self, program="", bucket="*"):
        """Self time of the operations of programs whose name contains
        ``program``, in ``bucket`` (``*``: all)."""
        return sum(op.self_ns for op in self.ops
                   if program in op.program
                   and bucket in ("*", op.bucket)) / 1e9

    def collective_seconds(self, program=""):
        """``(exposed, hidden)`` seconds of collectives."""
        exposed = hidden = 0
        open_start = {}
        for op in sorted(self.ops, key=lambda op: op.start):
            if program not in op.program or op.bucket != COLLECTIVE_BUCKET:
                continue
            exposed += op.self_ns
            own = op.name.split(" = ", 1)[0]
            pair = re.sub(r"-(start|done)", "", own)
            if "-start" in own:
                open_start[pair] = op.end
            elif "-done" in own and pair in open_start:
                hidden += max(0, op.start - open_start.pop(pair))
        return exposed / 1e9, hidden / 1e9


class Reduced(object):
    def __init__(self, profile, metadata):
        self.spans = []
        for plane in profile.planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        self.spans.append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
        epochs = [(s, e) for name, s, e in self.spans if name == EPOCH_SPAN]
        window = (min(s for s, _ in epochs), max(e for _, e in epochs)) \
            if epochs else None
        self.devices = [
            Device(plane, window, metadata.get(plane.name, {}))
            for plane in profile.planes if DEVICE_PLANE.match(plane.name)]
        self.devices = [d for d in self.devices if d.ops]
        if self.devices:
            lo, hi = self.devices[0].window
            self.window_s = (hi - lo) / 1e9
            self.busy_s = sum(d.busy_ns for d in self.devices) / 1e9 \
                / len(self.devices)

    def self_seconds(self, program="", bucket="*"):
        """Mean over devices."""
        return sum(d.self_seconds(program, bucket)
                   for d in self.devices) / len(self.devices)

    def collective_seconds(self, program=""):
        """``(exposed, hidden)`` of the device with most exposed."""
        return max(d.collective_seconds(program) for d in self.devices)

    def host_span_at(self, t):
        inside_epoch = False
        for name, start, end in self.spans:
            if start <= t < end:
                if name in SWEEP_SPANS:
                    return name
                inside_epoch = inside_epoch or name == EPOCH_SPAN
        return "bench:epoch_boundary" if inside_epoch \
            else "outside bench:epoch"

    def breakdown(self, top=10):
        """The contract's ``breakdown``: device time by bucket (self
        time, mean over devices) and idle time by what the host was
        doing (first device), largest first, seconds."""
        buckets = collections.Counter()
        for device in self.devices:
            for op in device.ops:
                buckets[op.bucket] += op.self_ns / 1e9 / len(self.devices)
        idle = collections.Counter()
        for start, end in self.devices[0].gaps():
            idle[self.host_span_at((start + end) // 2)] += \
                (end - start) / 1e9
        return {"device_ops": [list(kv) for kv in buckets.most_common(top)],
                "idle_gaps": [list(kv) for kv in idle.most_common(top)]}


def reduce_file(path):
    """``Reduced`` of one ``.xplane.pb``, or None where the trace has
    no device plane with operations (a CPU rehearsal)."""
    from jax.profiler import ProfileData
    reduced = Reduced(ProfileData.from_file(path), metadata_stats(path))
    return reduced if reduced.devices else None


def reduce_dir(trace_dir):
    path = find_xplane(trace_dir)
    return reduce_file(path) if path else None


def describe(path, events=3):
    """Survey of a trace, for reading by hand."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("PLANE %s" % plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE %s: %d events" % (line.name, len(evs)))
            for ev in evs[:events]:
                print("    %s start=%d dur=%d %s" % (
                    ev.name[:80], ev.start_ns, ev.duration_ns,
                    {k: str(v)[:60] for k, v in ev.stats}))


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 3)
