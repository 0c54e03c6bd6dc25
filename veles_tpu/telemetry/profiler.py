"""Performance attribution: cost analysis, roofline, memory, phases.

The PR 4 telemetry core records *how long* things take; this module
attributes *where* the FLOPs, bytes and seconds go, so the MFU plateau
(ROADMAP item 2) and the cold-start wall (item 4) can be chased with
numbers instead of ablations:

* **per-op cost attribution** — every jitted computation the system
  runs (train/eval segments, serving replica forwards) registers
  with the :class:`CostBook`, which
  harvests XLA's ``Compiled.cost_analysis()`` (analytic FLOPs and
  bytes-accessed of the whole executable) and pairs it with the op's
  *measured* wall time from the registry to publish achieved FLOP/s,
  arithmetic intensity and a compute-vs-memory-bound roofline verdict
  against the device's peak specs (``veles_op_flops``,
  ``veles_op_bytes``, ``veles_op_ms``);

* **step MFU** — the train segment's analytic FLOPs over its measured
  wall time, as a fraction of device peak (``veles_step_mfu``) — the
  number BENCH rounds have been estimating indirectly;

* **startup phases** — :func:`phase` marks the first-class cold-start
  stages (:data:`PHASES`) as spans, cumulative
  ``veles_phase_ms{phase}`` gauges and :class:`PhaseRow` s with a
  start, an end and a parent (:func:`phase_rows`), and
  :func:`watch_builds` folds in what JAX itself reports of every
  program it builds (trace, lowering, build, cache read), so a bench
  round can prove which stage a cold-start fix actually killed;

* **memory** — :class:`MemorySampler` periodically folds
  ``device.memory_stats()`` (live/peak HBM per device) and the host
  RSS into gauges; :func:`dump_memory_profile` writes
  ``jax.profiler.device_memory_profile`` (per-buffer attribution,
  pprof format) alongside a ``--trace-out`` dump.

Everything here is advisory instrumentation: every harvest path is
wrapped so a cost-analysis failure can never take down training, and
``VELES_COST_ATTRIBUTION=0`` turns harvesting off entirely.
"""

import collections
import functools
import itertools
import json
import os
import re
import threading
import time

from veles_tpu.envknob import env_flag, env_knob
from veles_tpu.telemetry import tracing
from veles_tpu.telemetry.registry import get_registry

#: (peak dense TFLOP/s, HBM GB/s) per JAX ``device_kind`` prefix —
#: public per-chip specs, bf16 peak where the hardware has one. The
#: roofline ridge point is their ratio. This table is the only source:
#: for a kind it does not list (CPU included) attribution reports
#: absolute numbers and omits MFU and the bound verdict.
DEVICE_SPECS = (
    ("TPU v6", (918.0, 1640.0)),
    ("TPU v5p", (459.0, 2765.0)),
    ("TPU v5e", (197.0, 819.0)),
    ("TPU v5 lite", (197.0, 819.0)),
    ("TPU v4", (275.0, 1228.0)),
    ("TPU v3", (123.0, 900.0)),
    ("TPU v2", (45.0, 700.0)),
)


def _env_positive(name):
    """float(env) or None — a typo'd value must degrade, never unwind
    the CLI entry points at startup."""
    value = env_knob(name, parse=float, on_error="default")
    return value if value is not None and value > 0 else None


def device_spec(device=None):
    """``(peak_flops_per_s, hbm_bytes_per_s)`` for ``device`` (default:
    the first local device), or ``(None, None)`` when its
    ``device_kind`` is not in :data:`DEVICE_SPECS`."""
    if device is None:
        import jax
        try:
            device = jax.local_devices()[0]
        except RuntimeError:  # no backend could start
            return None, None
    kind = getattr(device, "device_kind", "")
    for prefix, (tf, gb) in DEVICE_SPECS:
        if kind.startswith(prefix):
            return tf * 1e12, gb * 1e9
    return None, None


def attribution_enabled():
    return env_flag("VELES_COST_ATTRIBUTION", True)


def _first(costs, *keys):
    """cost_analysis() returns one dict per program; sum a key over
    them (TPU returns a single-element list, CPU sometimes several)."""
    if isinstance(costs, dict):
        costs = [costs]
    total = 0.0
    for c in costs or ():
        for key in keys:
            if key in c:
                total += float(c[key])
                break
    return total


def harvest_cost_analysis(compiled):
    """``{"flops": f, "bytes": b}`` from a ``jax.stages.Compiled`` (or
    anything with ``cost_analysis()``); None when unavailable."""
    try:
        costs = compiled.cost_analysis()
    except Exception:
        return None
    if not costs:
        return None
    return {"flops": _first(costs, "flops"),
            "bytes": _first(costs, "bytes accessed")}


#: bytes per element for the HLO shape tokens collective outputs use
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_RE = None


def _compiled_texts(compiled):
    """The optimized HLO of a ``jax.stages.Compiled`` as a list of
    module texts; None when it is not to be had."""
    try:
        texts = compiled.as_text()
    except Exception:
        return None
    if not texts:
        return None
    return [texts] if isinstance(texts, str) else list(texts)


def collective_bytes_estimate(compiled):
    """Per-execution bytes moved by the COMPILER-INSERTED collectives
    of a partitioned program (ISSUE 15): ``{"bytes": b, "count": n}``,
    or None when the program text is unavailable.

    ``cost_analysis()`` reports only whole-program aggregates (no
    per-instruction-category split on any backend this repo meets), so
    the collective share is read from the optimized HLO itself: the
    summed output-shape bytes of every ``all-reduce`` / ``all-gather``
    / ``all-to-all`` / ``collective-permute`` / ``reduce-scatter``
    instruction, per participating device. Async pairs are counted
    once via their ``-done`` half — a ``-start``'s result tuple
    aliases the operand buffers too, which would double the bytes —
    while synchronous lowerings (CPU) match on the bare name. An estimate — the gradient psum's wire
    traffic depends on the ICI algorithm — but it moves exactly when
    the partitioning moves, which is what the gauge is for."""
    global _COLLECTIVE_RE
    import re
    if _COLLECTIVE_RE is None:
        _COLLECTIVE_RE = (
            re.compile(r"=\s*([^=]*?)\s"
                       r"(?:all-reduce|all-gather|all-to-all|"
                       r"collective-permute|reduce-scatter|"
                       r"collective-broadcast)(?:-done)?\("),
            re.compile(r"([a-z]\w*)\[([0-9,]*)\]"))
    line_re, shape_re = _COLLECTIVE_RE
    texts = _compiled_texts(compiled)
    if texts is None:
        return None
    total = 0
    count = 0
    for text in texts:
        for match in line_re.finditer(text):
            count += 1
            for dtype, dims in shape_re.findall(match.group(1)):
                size = _HLO_DTYPE_BYTES.get(dtype)
                if size is None:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += n * size
    return {"bytes": total, "count": count}


#: HLO instructions that hand a buffer on without touching its bytes
_HLO_PASS_THROUGH = frozenset((
    "parameter", "get-tuple-element", "bitcast", "tuple", "while",
    "opt-barrier", "call", "conditional"))
#: ``%name = type opcode(``, the type an array's or a tuple's
_HLO_INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(")
_HLO_ARRAY_RE = re.compile(r"^([a-z]\w*)\[([\d,]*)\](?:\{([^}]*)\})?$")
_HLO_TILED_RE = re.compile(r"([\d,]*):T\(([\d,]+)\)")
_HLO_NAME_RE = re.compile(r"%[\w.\-]+")


def _hlo_padded_bytes(dtype, dims, layout):
    """Bytes of ``dtype[dims]{layout}`` on the device: the dims its
    first tile (``T(8,128)``) covers, minor-most last, are rounded up
    to whole tiles. No layout, or none with a tile: the plain size."""
    dims = [int(d) for d in dims.split(",") if d]
    tiled = _HLO_TILED_RE.match(layout or "")
    if tiled:
        order = [int(d) for d in tiled.group(1).split(",") if d]
        tile = [int(t) for t in tiled.group(2).split(",")]
        for dim, t in zip(order, reversed(tile)):
            dims[dim] = -(-dims[dim] // t) * t
    n = _HLO_DTYPE_BYTES.get(dtype, 0)
    for d in dims:
        n *= d
    return n


def relayout_bytes_in_text(text, shape):
    """Bytes written by every instruction of an optimized HLO ``text``
    that makes an array of the data set's full ``shape`` out of an
    operand of that shape: a relayout ``copy``, a transpose, a
    convert.

    The result is counted padded to its own tiling, which is what the
    instruction writes. Instructions that only hand the buffer on
    (``parameter``, ``tuple``, ``while``, ``get-tuple-element``,
    ``bitcast``) are not counted, and neither is the minibatch gather
    inside the scan's body, whose result is a minibatch (one as large
    as the data set cannot be told apart). Operands are read in both
    spellings: typed (``copy(bf16[..]{..} %data)``, as a profile
    quotes a program) and by name (``copy(%data)``, as
    ``compiled.as_text()`` does), the name resolved through the
    instruction that defined it. An asynchronous pair counts once, at
    its ``-done`` half."""
    dims = ",".join(str(d) for d in shape)
    full = "[%s]" % dims
    types = {}
    total = 0
    for line in text.splitlines():
        m = _HLO_INSTRUCTION_RE.match(line)
        if m is None:
            continue
        name, result, opcode = m.groups()
        types[name] = result
        array = _HLO_ARRAY_RE.match(result)
        if array is None or array.group(2) != dims or \
                opcode in _HLO_PASS_THROUGH or opcode.endswith("-start"):
            continue
        # the operand list runs to the parenthesis that closes the
        # opcode's own (tilings nest more of them)
        depth, end = 1, m.end()
        while end < len(line) and depth:
            depth += {"(": 1, ")": -1}.get(line[end], 0)
            end += 1
        operands = line[m.end():end]
        if full in operands or any(
                full in types.get(operand, "")
                for operand in _HLO_NAME_RE.findall(operands)):
            total += _hlo_padded_bytes(*array.groups())
    return total


def dataset_relayout_bytes(compiled, shape):
    """:func:`relayout_bytes_in_text` over a compiled segment, for the
    ``shape`` ONE device holds of its resident data set; None when the
    text is not to be had. 0 says the program reads the staged data
    set where it lies; on a TPU whose default layout for the staged
    shape is not sample-major it reads the padded size of the data
    set, once a call."""
    texts = _compiled_texts(compiled)
    if texts is None:
        return None
    return sum(relayout_bytes_in_text(text, shape) for text in texts)


class CostBook(object):
    """Per-op ledger: analytic cost (harvested once per op) joined with
    measured wall time (observed per call) and the device roofline.

    ``note_cost(op, flops, bytes)`` records analytics directly (the
    offload engine's transfers — it counts their bytes itself);
    ``harvest(op, compiled)`` reads them off the executable its caller
    built and runs (a trainer's segments), ``harvest_function(op,
    jit_fn, args)`` lowers and compiles a function for the reading (a
    serving replica's buckets); either runs at most once per op name.
    """

    def __init__(self, registry=None):
        registry = registry or get_registry()
        self._lock = threading.Lock()
        self._costs = {}          # op -> {"flops", "bytes"}
        self._harvested = set()   # op names already attempted
        self._g_flops = registry.gauge(
            "veles_op_flops", "Analytic FLOPs per execution of a "
            "compiled op (XLA cost model)", labels=("op",))
        self._g_bytes = registry.gauge(
            "veles_op_bytes", "Analytic bytes accessed per execution "
            "of a compiled op (XLA cost model)", labels=("op",))
        self._h_ms = registry.histogram(
            "veles_op_ms", "Measured wall time per compiled-op call",
            labels=("op",))
        self._g_mfu = registry.gauge(
            "veles_step_mfu", "Model FLOPs utilization of the train "
            "step (analytic FLOPs / measured time / device peak)")
        self._g_coll = registry.gauge(
            "veles_op_collective_bytes",
            "Estimated bytes moved per execution by the "
            "compiler-inserted collectives of a partitioned op "
            "(summed HLO collective output shapes, per device)",
            labels=("op",))
        self._g_relayout = registry.gauge(
            "veles_dataset_relayout_bytes",
            "Bytes a compiled segment writes per call to re-lay the "
            "resident data set out at full size before its scan "
            "(0: the staged layout is read in place)", labels=("op",))

    # -- recording ---------------------------------------------------------

    def note_cost(self, op, flops, bytes_accessed):
        with self._lock:
            self._costs[op] = {"flops": float(flops),
                               "bytes": float(bytes_accessed)}
            self._harvested.add(op)
        self._g_flops.labels(op=op).set(flops)
        self._g_bytes.labels(op=op).set(bytes_accessed)

    def needs_harvest(self, op):
        if not attribution_enabled():
            return False
        with self._lock:
            return op not in self._harvested

    def harvest(self, op, compiled, dataset_shape=None):
        """Record under ``op`` what a ``jax.stages.Compiled`` says of
        itself: its cost analysis and, read from its optimized text,
        its collectives' bytes and its relayout of the data set.
        Builds nothing: the caller hands in the executable it runs.
        Never raises; at most one attempt per op (failures record an
        empty entry so they are not retried on the hot path).
        ``dataset_shape``: the shape one device holds of the resident
        data set among the operands, for
        ``veles_dataset_relayout_bytes``."""
        with self._lock:
            if op in self._harvested:
                return
            self._harvested.add(op)
        cost = harvest_cost_analysis(compiled)
        if cost is None:
            return
        # the partitioned (GSPMD) ops also surface their collective
        # share — zero collectives is a meaningful reading too (a
        # "sharded" step that inserted none is not actually sharded)
        coll = collective_bytes_estimate(compiled)
        if coll is not None:
            cost["collective_bytes"] = coll["bytes"]
            cost["collective_count"] = coll["count"]
        relayout = (dataset_relayout_bytes(compiled, dataset_shape)
                    if dataset_shape is not None else None)
        with self._lock:
            self._costs[op] = cost
        self._g_flops.labels(op=op).set(cost["flops"])
        self._g_bytes.labels(op=op).set(cost["bytes"])
        if coll is not None:
            self._g_coll.labels(op=op).set(coll["bytes"])
        if relayout is not None:
            self._g_relayout.labels(op=op).set(relayout)

    def harvest_function(self, op, jit_fn, args):
        """:meth:`harvest` for a caller that holds no executable (a
        serving replica calls its jitted forward): lower and compile
        ``jit_fn`` at ``args`` for the reading, once per op."""
        if not self.needs_harvest(op):
            return
        try:
            compiled = jit_fn.lower(*args).compile()
        except Exception:
            compiled = None  # recorded as an attempt, like a failure
        self.harvest(op, compiled)

    def observe_ms(self, op, elapsed_s):
        self._h_ms.labels(op=op).observe(elapsed_s * 1e3)

    def cost(self, op):
        with self._lock:
            return dict(self._costs.get(op) or {}) or None

    # -- derived -----------------------------------------------------------

    def record_step_mfu(self, op, elapsed_s):
        """Set ``veles_step_mfu`` from one measured execution of ``op``
        (the train segment). Returns the MFU or None."""
        cost = self.cost(op)
        if not cost or not cost["flops"] or elapsed_s <= 0:
            return None
        peak, _ = device_spec()
        if not peak:
            return None
        mfu = cost["flops"] / elapsed_s / peak
        self._g_mfu.set(mfu)
        return mfu

    def report(self):
        """The attribution table: one row per op with analytic cost,
        measured time (registry percentiles) and the roofline verdict.
        JSON-able — this is what ``/profile.json`` and
        ``profile_step.py --attribution`` render."""
        peak_flops, peak_bw = device_spec()
        ridge = (peak_flops / peak_bw
                 if peak_flops and peak_bw else None)
        with self._lock:
            costs = {op: dict(c) for op, c in self._costs.items()}
        measured = {}
        for labels, child in self._h_ms.series():
            measured[labels.get("op")] = child.summary()
        ops = []
        for op in sorted(set(costs) | set(measured)):
            cost = costs.get(op) or {}
            times = measured.get(op) or {}
            row = {"op": op,
                   "flops": cost.get("flops"),
                   "bytes": cost.get("bytes"),
                   "calls": times.get("count", 0),
                   "p50_ms": times.get("p50"),
                   "p95_ms": times.get("p95")}
            if "collective_bytes" in cost:
                row["collective_bytes"] = cost["collective_bytes"]
                row["collective_count"] = cost.get("collective_count")
            flops, byts = cost.get("flops"), cost.get("bytes")
            if flops and byts:
                row["arithmetic_intensity"] = flops / byts
                if ridge is not None:
                    row["bound"] = ("compute"
                                    if row["arithmetic_intensity"] >= ridge
                                    else "memory")
            p50 = times.get("p50")
            if flops and p50:
                row["achieved_tflops"] = flops / (p50 / 1e3) / 1e12
                if peak_flops:
                    row["utilization"] = (flops / (p50 / 1e3) /
                                          peak_flops)
            if byts and p50:
                row["achieved_gbps"] = byts / (p50 / 1e3) / 1e9
            ops.append(row)
        out = {"ops": ops,
               "device": {"peak_tflops": (peak_flops / 1e12
                                          if peak_flops else None),
                          "hbm_gbps": (peak_bw / 1e9
                                       if peak_bw else None),
                          "ridge_flops_per_byte": ridge}}
        try:
            out["step_mfu"] = self._g_mfu.value
        except ValueError:  # never set this process
            out["step_mfu"] = None
        return out


_book = None
_book_lock = threading.Lock()


def get_cost_book():
    global _book
    with _book_lock:
        if _book is None:
            _book = CostBook()
        return _book


def reset_cost_book():
    """Tests only: drop the book so a fresh registry gets fresh gauges."""
    global _book
    with _book_lock:
        _book = None


class timed_op(object):
    """Context manager timing one execution of a named op into the
    cost book (span + ``veles_op_ms``); the cheap always-on half of
    attribution (the harvest half is one-time)."""

    __slots__ = ("op", "_start", "_book")

    def __init__(self, op, book=None):
        self.op = op
        self._book = book or get_cost_book()

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        elapsed = time.perf_counter() - self._start
        self._book.observe_ms(self.op, elapsed)
        tracing.add_complete("op:%s" % self.op, self._start, elapsed)
        return False


# -- startup phases ----------------------------------------------------------

#: the canonical order of :func:`phase_report`; every name is recorded
#: by some code of the package. ``trace``, ``lower``, ``build`` and
#: ``cache_read`` (inside ``build``) are JAX's own stages of building a
#: program (:func:`watch_builds`); ``compile`` is the sum of the first
#: three where a call, not a cost harvest, caused the build (a
#: trainer's harvest builds nothing: it reads the executable the call
#: built).
PHASES = ("dataset_generate", "dataset_load", "trainer_build",
          "dataset_stage", "dataset_shard", "model_residency",
          "offload_plan", "params_place", "segment_first_call",
          "compile", "trace", "lower", "build", "cache_read",
          "cost_harvest", "replica_warmup", "pipeline_fill",
          "first_step")

#: JAX's monitoring events of one build -> the stage's name
BUILD_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "build",
}
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: rows kept a process. A process's START is what the rows are for:
#: once the list is full, later rows are dropped (totals still count)
MAX_PHASE_ROWS = 8192

#: ``id`` numbers the rows of a process in the order they were opened;
#: ``start`` and ``end`` are seconds on :func:`tracing.to_wall_s`'s
#: clock; ``parent`` is the ``id`` of the phase that was open on the
#: same thread when this one opened, or None
PhaseRow = collections.namedtuple(
    "PhaseRow", ("id", "name", "start", "end", "parent", "attrs"))

_phase_lock = threading.Lock()
_phase_ms = {}  # phase -> cumulative ms this process
_phase_rows = []
_row_ids = itertools.count(1)
_open = threading.local()  # .stack: this thread's open phases
_builds = 0  # "build" stages seen this process, on any thread
_startup_s = None  # veles_startup_s, once it is set
_watching = False


def _stack():
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def _add_total(name, elapsed_s):
    with _phase_lock:
        total = _phase_ms[name] = _phase_ms.get(name, 0.0) + elapsed_s * 1e3
    get_registry().gauge(
        "veles_phase_ms", "Cumulative startup-phase wall time",
        labels=("phase",)).labels(phase=name).set(total)


def _add_row(row_id, name, start, end, parent, attrs):
    with _phase_lock:
        if len(_phase_rows) < MAX_PHASE_ROWS:
            _phase_rows.append(
                PhaseRow(row_id, name, start, end, parent, attrs))


class _Phase(object):
    """One open phase of the calling thread; ``attrs`` may be filled
    while it is open. A phase opened inside one of the same name (a
    subclass's wrapped method calling its parent's) is part of it: no
    row and no total of its own."""

    __slots__ = ("name", "attrs", "id", "parent", "_start")

    #: whether the time also goes to ``veles_phase_ms{phase}``
    total = True

    def __init__(self, name, attrs):
        self.name = name
        self.attrs = attrs
        self.id = None

    def __enter__(self):
        stack = _stack()
        if not any(p.name == self.name for p in stack):
            self.parent = stack[-1].id if stack else None
            self.id = next(_row_ids)
            stack.append(self)
        self._start = time.perf_counter()
        return self

    def _keep(self):
        return True

    def __exit__(self, *exc):
        if self.id is None:
            return False
        elapsed = time.perf_counter() - self._start
        stack = _stack()
        if self in stack:  # and whatever an exception left above it
            del stack[stack.index(self):]
        if self._keep():
            if self.total:
                _add_total(self.name, elapsed)
            start = tracing.to_wall_s(self._start)
            _add_row(self.id, self.name, start, start + elapsed,
                     self.parent, self.attrs)
            tracing.add_complete("phase:%s" % self.name, self._start,
                                 elapsed, **self.attrs)
        return False


class _FirstCall(_Phase):
    """A call that may build its program: kept only when JAX built
    one inside it, with ``builds`` among its attributes."""

    __slots__ = ("_built",)

    def __enter__(self):
        self._built = _builds
        return super(_FirstCall, self).__enter__()

    @property
    def builds(self):
        """Programs built since the phase opened: an integer compare
        for the caller that wants to say more about a call that
        built."""
        return _builds - self._built

    def _keep(self):
        self.attrs["builds"] = self.builds
        return self.builds > 0


class _Epoch(_FirstCall):
    """One epoch: always a row, never a total (it is no start-up
    stage). The first one in which nothing was built is the process's
    first steady epoch: ``veles_startup_s`` is set at its start,
    once."""

    __slots__ = ()
    total = False

    def _keep(self):
        global _startup_s
        if not super(_Epoch, self)._keep() and _startup_s is None:
            _startup_s = (tracing.to_wall_s(self._start)
                          - process_started()[0])
            get_registry().gauge(
                "veles_startup_s", "Process start to the start of the "
                "first epoch in which no program was built").set(
                _startup_s)
        return True


def phase(name, **attrs):
    """One startup stage: ``veles_phase_ms{phase}``, a ``phase:<name>``
    span in the tracing ring and a :class:`PhaseRow`. Totals
    ACCUMULATE within a process (two datasets load = one total), which
    is the quantity a cold-start bench wants; the rows say when each
    part ran and inside what."""
    return _Phase(name, attrs)


def phased(name):
    """Decorator: the whole call is a :func:`phase`."""
    def wrap(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)
        return wrapped
    return wrap


def first_call(op, **attrs):
    """Around the build of a segment's executable and its first call
    (or a call of a segment that may build, the offload engine's): a
    ``segment_first_call`` row and total, with ``op`` and ``builds``,
    only when a program was built inside it (:func:`watch_builds`);
    the build's own stages are its children. A call that built nothing
    leaves no record."""
    return _FirstCall("segment_first_call", dict(attrs, op=op))


def epoch_phase(epoch):
    """Around one epoch: an ``epoch`` row with ``epoch`` and
    ``builds``; sets ``veles_startup_s`` (see :class:`_Epoch`)."""
    return _Epoch("epoch", {"epoch": epoch})


def record_phase(name, elapsed_s, **attrs):
    """A stage the caller timed itself, ending now."""
    stack = _stack()
    end = tracing.to_wall_s(time.perf_counter())
    _add_total(name, elapsed_s)
    _add_row(next(_row_ids), name, end - elapsed_s, end,
             stack[-1].id if stack else None, attrs)


def phase_rows():
    """The :class:`PhaseRow` s of this process so far, in the order
    they ENDED (a parent after its children)."""
    with _phase_lock:
        return list(_phase_rows)


@functools.lru_cache(maxsize=None)
def process_started():
    """``(seconds, source)``: when this process started, on the rows'
    clock. ``"os"``: the kernel's record of it (``/proc/self/stat``,
    10 ms fine); ``"import"``: where that cannot be read, the moment
    the telemetry package was imported."""
    imported = tracing._WALL_EPOCH
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command, which may hold blanks
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        started = time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return imported, "import"
    if not 0.0 <= age or started > imported + 0.02:
        return imported, "import"  # a clock the sandbox does not keep
    return started, "os"


# -- JAX's own stages of building a program, as phases ------------------------


class _Stage(object):
    """A trace, lowering, build or cache read that JAX reported, under
    what is open on the calling thread (``stack``). ``inside`` counts
    the stages open inside it: a jitted helper traced inside the
    segment's trace, the small functions a lowering rule traces
    (15,000 events a build of a large model) are part of it, with no
    row and no object of their own."""

    __slots__ = ("name", "id", "parent", "program", "cause", "inside")

    def __init__(self, name, program, stack):
        self.name, self.program = name, program
        self.parent = stack[-1].id if stack else None
        self.cause = ("harvest" if any(p.name == "cost_harvest"
                                       for p in stack) else "call")
        self.id = next(_row_ids)
        self.inside = 0


def _program(fun_name):
    """JAX names the function at its trace and the module,
    ``jit(<function>)``, at its lowering and build: one name for the
    three."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _stage_opened(event, value, fun_name=None, **_):
    """``jax.monitoring`` scalar listener: JAX announces a stage's
    start (dispatch.py ``log_elapsed_time``)."""
    name = BUILD_STAGES.get(event)
    if name is None:
        return
    stack = _stack()
    if stack and type(stack[-1]) is _Stage:
        stack[-1].inside += 1
    else:
        stack.append(_Stage(name, _program(fun_name), stack))


def _stage_closed(event, start, end, fun_name=None, **_):
    """``jax.monitoring`` time-span listener: a stage's real start and
    end, on ``time.time``."""
    global _builds
    name = BUILD_STAGES.get(event)
    if name is None:
        return
    if name == "build":
        with _phase_lock:
            _builds += 1
    stack = _stack()
    stage = stack[-1] if stack else None
    if type(stage) is not _Stage:
        # its start was not announced: a row under what is open
        stage = _Stage(name, _program(fun_name), stack)
    elif stage.inside or stage.name != name:
        stage.inside = max(0, stage.inside - 1)
        return
    else:
        stack.pop()
    _stage_row(stage, start, end)


def _cache_read(event, duration, **_):
    """``jax.monitoring`` duration listener: the persistent cache gave
    the executable back after ``duration`` seconds (the key is made
    before, the executable loaded within); it ends now, inside the
    ``build`` that is open."""
    if event != CACHE_READ_EVENT:
        return
    stack = _stack()
    if stack and type(stack[-1]) is _Stage and not stack[-1].inside:
        end = time.time()
        _stage_row(_Stage("cache_read", stack[-1].program, stack),
                   end - duration, end)


def _stage_row(stage, start, end):
    _add_total(stage.name, end - start)
    if stage.name != "cache_read" and stage.cause == "call":
        _add_total("compile", end - start)
    get_registry().counter(
        "veles_program_builds_total", "Stages of building a program "
        "that JAX reported (trace, lower, build, cache_read), by what "
        "caused the build: a call or the cost harvest",
        labels=("stage", "cause")).labels(
        stage=stage.name, cause=stage.cause).inc()
    _add_row(stage.id, stage.name, start, end, stage.parent,
             {"program": stage.program, "cause": stage.cause})


def watch_builds():
    """Listen, once a process, to what JAX reports of every program it
    builds and fold it into the phases: rows and totals ``trace``,
    ``lower``, ``build`` and ``cache_read`` (inside ``build``), each
    with ``program`` and ``cause`` (``harvest`` while a
    ``cost_harvest`` phase is open on the thread, else ``call``), and
    ``veles_program_builds_total{stage,cause}``. JAX calls the
    listeners only while it builds something: a program that runs
    from its cache of executables costs nothing here."""
    global _watching
    with _phase_lock:
        if _watching:
            return
        _watching = True
    import jax.monitoring
    jax.monitoring.register_scalar_listener(_stage_opened)
    jax.monitoring.register_event_time_span_listener(_stage_closed)
    jax.monitoring.register_event_duration_secs_listener(_cache_read)


def build_count():
    """Programs JAX has built (compiled, or read from the persistent
    cache) since :func:`watch_builds`."""
    return _builds


def phase_report():
    """``{phase: ms}`` in canonical order (extras appended)."""
    with _phase_lock:
        snap = dict(_phase_ms)
    out = {}
    for name in PHASES:
        if name in snap:
            out[name] = round(snap.pop(name), 3)
    for name in sorted(snap):
        out[name] = round(snap[name], 3)
    return out


def reset_phases():
    """Tests only."""
    global _startup_s
    with _phase_lock:
        _phase_ms.clear()
        del _phase_rows[:]
        _startup_s = None


# -- memory ------------------------------------------------------------------


def host_rss_bytes():
    """Resident set size of this process, or None off-Linux."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def sample_memory(registry=None):
    """One sample of per-device HBM + host RSS into gauges. Returns
    the JSON-able sample (what ``/profile.json`` embeds)."""
    registry = registry or get_registry()
    g_live = registry.gauge(
        "veles_hbm_live_bytes", "Live device memory", labels=("device",))
    g_peak = registry.gauge(
        "veles_hbm_peak_bytes", "Peak device memory", labels=("device",))
    g_limit = registry.gauge(
        "veles_hbm_limit_bytes", "Device memory capacity",
        labels=("device",))
    g_rss = registry.gauge("veles_host_rss_bytes", "Host process RSS")
    sample = {"devices": {}, "host_rss_bytes": None}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        devices = ()
    for dev in devices:
        try:
            stats = dev.memory_stats() or {}
        except Exception:
            stats = {}
        if not stats:
            continue
        label = "%s:%d" % (dev.platform, dev.id)
        live = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        limit = stats.get("bytes_limit")
        entry = {}
        if live is not None:
            g_live.labels(device=label).set(live)
            entry["live_bytes"] = int(live)
        if peak is not None:
            g_peak.labels(device=label).set(peak)
            entry["peak_bytes"] = int(peak)
        if limit is not None:
            g_limit.labels(device=label).set(limit)
            entry["limit_bytes"] = int(limit)
        if entry:
            sample["devices"][label] = entry
    rss = host_rss_bytes()
    if rss is not None:
        g_rss.set(rss)
        sample["host_rss_bytes"] = rss
    return sample


class MemorySampler(object):
    """Daemon thread folding :func:`sample_memory` into the registry
    every ``interval`` seconds. Start once per process; stop() is only
    needed by tests (the thread is a daemon)."""

    def __init__(self, interval=5.0, registry=None):
        self.interval = float(interval)
        self._registry = registry
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="memory-sampler")
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop.wait(self.interval):
            try:
                sample_memory(self._registry)
            except Exception:
                pass

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


_sampler = None


def start_memory_sampler(interval=None):
    """Process-wide sampler (idempotent). ``VELES_MEMORY_SAMPLE_S``
    overrides the interval; 0 disables."""
    global _sampler
    if interval is None:
        env = _env_positive("VELES_MEMORY_SAMPLE_S")
        if env is None and \
                env_knob("VELES_MEMORY_SAMPLE_S") is not None:
            return None  # explicit 0 / unparsable: sampling off
        interval = env if env is not None else 5.0
    if interval <= 0:
        return None
    with _book_lock:
        if _sampler is None:
            _sampler = MemorySampler(interval=interval).start()
    return _sampler


def stop_memory_sampler():
    """Join the process-wide sampler (tests / orderly shutdown)."""
    global _sampler
    with _book_lock:
        sampler, _sampler = _sampler, None
    if sampler is not None:
        sampler.stop()


def dump_memory_profile(path):
    """Write ``jax.profiler.device_memory_profile()`` (per-buffer HBM
    attribution, pprof gzip) to ``path``. Returns True on success —
    callers pair this with a ``--trace-out`` dump."""
    try:
        import jax.profiler
        blob = jax.profiler.device_memory_profile()
        tmp = "%s.%d.tmp" % (path, os.getpid())
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except Exception:
        return False


# -- the /profile.json payload ----------------------------------------------


def profile_report():
    """Everything the observability surfaces render: attribution table,
    step MFU, startup phases, the latest memory sample, and the last
    flight-record path (when the recorder has written one)."""
    from veles_tpu.telemetry import flight
    report = get_cost_book().report()
    report["phases_ms"] = phase_report()
    try:
        report["memory"] = sample_memory()
    except Exception:
        report["memory"] = None
    report["flight_record"] = flight.last_record_path()
    return report


def render_profile_json():
    return json.dumps(profile_report())
