#!/usr/bin/env python3
"""A recorded trace, cut to what the benchmark's readers read, so that
a fixture under ``benchmark/fixtures`` stays small.

    python3 benchmark/tests/cut_xplane.py cut <in.xplane.pb> <out>
    python3 benchmark/tests/cut_xplane.py survey <in.xplane.pb>
    python3 benchmark/tests/cut_xplane.py record <tiny cell> <out>

``cut`` keeps, of each ``/device:TPU:<n>`` plane, the ``XLA Ops`` and
``XLA Modules`` lines without their events' own stats, and of their
event metadata the name and the stats in ``KEEP_STATS``; of
``/host:CPU`` the events whose name starts with ``bench:``; nothing
else. It works on the protobuf wire format (tsl's ``xplane.proto``,
field numbers in ``trace_reduce.metadata_stats`` and below) with
``trace_reduce._fields`` and the twenty-line writer here. ``survey``
prints what ``trace_reduce.describe`` cannot: the stat names of every
plane and the metadata stats of a few events. ``record`` runs one cell
of ``test_harness.tiny_spec`` with ``--trace 1`` on whatever devices
JAX offers (a fixture is recorded on the chip) and cuts its trace.

XPlane: name = 2, lines = 3, event_metadata = 4, stat_metadata = 5.
XLine: name = 2, events = 4. XEvent: metadata_id = 1, stats = 4.
XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5.
XStat: metadata_id = 1, ref_value = 7.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import trace_reduce  # noqa: E402
from benchmark.trace_reduce import _fields  # noqa: E402

KEEP_STATS = ("source", "hlo_category", "tf_op")
KEEP_LINES = (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)


def _varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _encode(fields):
    """The inverse of ``_fields``."""
    out = bytearray()
    for number, wire, value in fields:
        out += _varint(number << 3 | wire)
        if wire == 0:
            out += _varint(value)
        else:
            if wire == 2:
                out += _varint(len(value))
            out += bytes(value)
    return bytes(out)


def _get(message, number, default=None):
    return next((v for n, _, v in _fields(message) if n == number), default)


def _map_entries(plane, number):
    """``{key: value message}`` of one of a plane's two maps."""
    return {_get(entry, 1): _get(entry, 2)
            for n, _, entry in _fields(plane) if n == number}


def _cut_plane(plane, keep_stats):
    name = bytes(_get(plane, 2, b"")).decode()
    device = bool(trace_reduce.DEVICE_PLANE.match(name))
    if not device and name != trace_reduce.HOST_PLANE:
        return None
    events, stats = _map_entries(plane, 4), _map_entries(plane, 5)
    stat_name = {key: bytes(_get(meta, 2, b"")).decode()
                 for key, meta in stats.items()}
    event_name = {key: bytes(_get(meta, 2, b"")).decode()
                  for key, meta in events.items()}
    out, used_events, used_stats = [], set(), set()
    for number, wire, value in _fields(plane):
        if number in (1, 2):
            out.append((number, wire, value))
        elif number == 3:
            line = list(_fields(value))
            if device:
                if bytes(_get(value, 2, b"")).decode() not in KEEP_LINES:
                    continue
                kept = [(n, w, _encode([f for f in _fields(v) if f[0] != 4])
                         if n == 4 else v) for n, w, v in line]
            else:
                kept = [(n, w, v) for n, w, v in line if n != 4
                        or event_name.get(_get(v, 1), "").startswith(
                            trace_reduce.SPAN_PREFIX)]
                for n, _, v in kept:
                    if n == 4:
                        used_stats.update(
                            _get(s, 1) for m, _, s in _fields(v) if m == 4)
            ids = [_get(v, 1) for n, _, v in kept if n == 4]
            if ids:
                used_events.update(ids)
                out.append((3, 2, _encode(kept)))
    for key in sorted(used_events):
        meta = []
        for n, w, v in _fields(events[key]):
            if n in (1, 2, 4):
                meta.append((n, w, v))
            elif n == 5 and stat_name.get(_get(v, 1)) in keep_stats:
                meta.append((n, w, v))
                used_stats.update((_get(v, 1), _get(v, 7)))
        out.append((4, 2, _encode([(1, 0, key), (2, 2, _encode(meta))])))
    for key in sorted(k for k in used_stats if k in stats):
        out.append((5, 2, _encode([(1, 0, key), (2, 2, stats[key])])))
    return _encode(out)


def cut(src, dst, keep_stats=KEEP_STATS):
    with open(src, "rb") as f:
        space = memoryview(f.read())
    planes = [_cut_plane(plane, keep_stats)
              for number, _, plane in _fields(space) if number == 1]
    with open(dst, "wb") as f:
        f.write(_encode([(1, 2, p) for p in planes if p is not None]))
    return os.path.getsize(dst)


def survey(path, events=12):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        stats = {key: bytes(_get(meta, 2, b"")).decode()
                 for key, meta in _map_entries(plane, 5).items()}
        metas = _map_entries(plane, 4)
        print("PLANE %s: %d event metadata, %d bytes; stat names: %s" % (
            bytes(_get(plane, 2, b"")).decode(), len(metas), len(plane),
            " ".join(sorted(stats.values()))))
        shown = 0
        for meta in metas.values():
            own = [(stats.get(_get(s, 1)), [
                (n, bytes(v[:300]).decode("utf8", "replace") if w == 2
                 else stats.get(v, v) if n == 7 else v)
                for n, w, v in _fields(s) if n != 1])
                for n, _, s in _fields(meta) if n == 5]
            if own and shown < int(events):
                shown += 1
                print("  EVENT %s" % bytes(_get(meta, 2, b""))[:100].decode(
                    "utf8", "replace"))
                for key, value in own:
                    print("    %s = %s" % (key, value))


def record(cell, dst, seed=2500000001, seconds=0.3):
    import tempfile
    import time

    import jax

    from benchmark import harness
    sys.path.insert(0, HERE)
    import test_harness
    with tempfile.TemporaryDirectory(dir=os.path.dirname(
            os.path.abspath(dst))) as root:
        bench = test_harness.make_root(root, test_harness.tiny_spec())
        result = harness.run_cell(bench, cell, seed, seconds, True,
                                  jax.devices(), time.time())
        print(result)
        path = trace_reduce.find_xplane(os.path.join(
            root, ".veles_cache", "benchmark_trace"))
        print("%s: %d bytes, cut to %d" % (
            path, os.path.getsize(path), cut(path, dst)))


if __name__ == "__main__":
    {"cut": cut, "survey": survey, "record": record}[sys.argv[1]](
        *sys.argv[2:])
