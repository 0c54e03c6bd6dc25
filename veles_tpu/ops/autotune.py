"""Shape-aware Pallas kernel autotuner with a persistent per-device cache.

The hand-tiled Pallas kernels in this package carry ONE fixed block
config each, which is why level-0 GEMM stayed on XLA dot: the fixed
tiling beats XLA on bandwidth-bound shapes but loses ~2x on large
compute-bound squares (docs/PERF.md "GEMM disciplines"). This module
replaces the static rules with measurement: keyed by
``(op, M, N, K, dtype, transpose flags, device kind)`` it times a
bounded candidate grid of block/tile/pipeline configs against the
XLA-native implementation and persists the winner to an on-disk JSON
cache (sibling to the persistent XLA compile cache wired up in
:mod:`veles_tpu.backends`) that later runs consult at trace time —
the TPU re-realization of the reference's per-device OpenCL autotune
database (``veles/backends.py:672-731``, BLOCK_SIZE/VECTOR_OPT per
device) and of CUDA-L2-style per-shape config search (PAPERS.md).

Modes (``VELES_AUTOTUNE`` env > ``root.common.engine.autotune`` config
> default ``cache``):

* ``off``    — every consult returns ("default", None): callers use
  their legacy static dispatch, bit-for-bit today's behavior;
* ``cache``  — consult the persistent cache; a miss returns
  ("default", None) without measuring (zero startup cost, never
  blocks — the production serving mode);
* ``search`` — a miss triggers a time-budgeted measurement sweep
  (``VELES_AUTOTUNE_BUDGET_S`` per key, default 20 s) whose winner is
  persisted immediately. Searching runs ONLY where kernels can run:
  on TPU, or anywhere under ``VELES_AUTOTUNE_FORCE=interpret`` (tests
  and CI exercise the full seam in Pallas interpret mode on CPU).

Untunable environments degrade gracefully by construction: on CPU
(tier-1 CI) every plan returns the default path without measuring,
and a corrupt or stale cache file is treated as empty, never fatal.

Telemetry (the PR 4 registry): ``veles_autotune_searches_total``,
``veles_autotune_cache_hits_total``, ``veles_autotune_misses_total``
counters and a ``veles_autotune_best_tflops{op,shape}`` gauge; each
sweep runs under a ``span("autotune:search")`` so tuning shows up in
``--trace-out`` timelines.
"""

import json
import os
import re
import threading
import time

import numpy

from veles_tpu.config import root
from veles_tpu.envknob import env_knob
from veles_tpu.telemetry import tracing
from veles_tpu.telemetry.registry import get_registry

_MODES = ("off", "cache", "search")
#: a measured alternative must beat the baseline by this margin to win
#: (re-measure noise must not flap the dispatch between runs)
_WIN_MARGIN = 0.02
#: schema version: bump to invalidate every persisted entry at once
CACHE_VERSION = 1

_DEFAULT = ("default", None)
_search_lock = threading.Lock()
_caches = {}
_caches_lock = threading.Lock()
_warned_corrupt = set()


# -- mode / environment ------------------------------------------------------

def mode():
    """Resolve the tuning mode. Env knob wins over the config tree."""
    m = env_knob("VELES_AUTOTUNE")
    if not m:
        m = root.common.engine.get("autotune", "cache")
    return m if m in _MODES else "cache"


def forced_interpret():
    """True when VELES_AUTOTUNE_FORCE requests interpret-mode kernels
    (the CPU test/CI path through the full search machinery)."""
    return env_knob("VELES_AUTOTUNE_FORCE") in ("1", "interpret")


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


def tunable():
    """May this process measure kernels at all?"""
    return _on_tpu() or forced_interpret()


def _trace_state_clean():
    """False when called from inside a jax trace (jit/grad/vmap),
    where wall-clock measurement is impossible. JAX 0.9 keeps this
    only under ``jax._src``; tests/test_autotune.py pins it so that a
    JAX that drops it fails there, not as a search over tracers."""
    from jax._src import core
    return core.trace_state_clean()


def kernel_interpret():
    """``interpret=`` flag consumers must pass to tuned Pallas calls:
    real kernels on TPU, interpret mode ONLY under the forced test
    path. On an untunable backend (e.g. a host where TPU init failed
    and JAX fell back to CPU) this returns False, so a shipped
    TPU-tuned cache entry degrades to each kernel's XLA fallback
    instead of silently running interpret-mode Pallas."""
    return forced_interpret() and not _on_tpu()


def device_kind():
    """Cache-file identity: one tuning database per device model."""
    import jax
    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    return re.sub(r"[^a-z0-9]+", "-", str(kind).lower()).strip("-") or \
        "unknown"


def cache_path():
    explicit = env_knob("VELES_AUTOTUNE_CACHE")
    if explicit:
        return explicit
    from veles_tpu.backends import veles_cache_dir
    return os.path.join(veles_cache_dir("autotune"),
                        device_kind() + ".json")


# -- telemetry ---------------------------------------------------------------

def _metrics():
    reg = get_registry()
    return (
        reg.counter("veles_autotune_searches_total",
                    "Autotune measurement sweeps run"),
        reg.counter("veles_autotune_cache_hits_total",
                    "Autotune plans answered from the cache"),
        reg.counter("veles_autotune_misses_total",
                    "Autotune plans that fell back to the default path"),
        reg.gauge("veles_autotune_best_tflops",
                  "Best measured rate per tuned op/shape",
                  labels=("op", "shape")),
    )


# -- persistent cache --------------------------------------------------------

class AutotuneCache(object):
    """One JSON file of ``{key: entry}`` winners; load-tolerant,
    atomically rewritten, merged with on-disk state on every put so
    concurrently tuning processes do not clobber each other."""

    def __init__(self, path):
        self.path = path
        self._lock = threading.Lock()
        self._entries = None

    def _read_disk(self):
        try:
            with open(self.path) as f:
                blob = json.load(f)
            if (isinstance(blob, dict) and
                    blob.get("version") == CACHE_VERSION and
                    isinstance(blob.get("entries"), dict)):
                return dict(blob["entries"])
            raise ValueError("schema mismatch")
        except FileNotFoundError:
            return {}
        except Exception as e:  # corrupt/stale cache == empty cache
            if self.path not in _warned_corrupt:
                _warned_corrupt.add(self.path)
                import logging
                logging.getLogger("autotune").warning(
                    "ignoring unreadable autotune cache %s (%s: %s)",
                    self.path, type(e).__name__, e)
            return {}

    def _ensure(self):
        """Lazy first load. Caller holds ``self._lock``."""
        if self._entries is None:
            self._entries = self._read_disk()
        return self._entries

    def get(self, key):
        with self._lock:
            return self._ensure().get(key)

    def put(self, key, entry):
        with self._lock:
            # merge-then-write: pick up winners other processes
            # persisted since our load, keep ours for the key we own
            merged = self._read_disk()
            self._ensure().update(
                {k: v for k, v in merged.items()
                 if k not in self._entries})
            self._entries[key] = entry
            self._persist()

    def _persist(self):
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = "%s.%d.tmp" % (self.path, os.getpid())
            with open(tmp, "w") as f:
                json.dump({"version": CACHE_VERSION,
                           "device": device_kind(),
                           "entries": self._entries}, f, indent=1,
                          sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            pass  # the cache is an optimization, never a failure

    def __len__(self):
        with self._lock:
            return len(self._ensure())

    def items(self):
        with self._lock:
            return sorted(self._ensure().items())


def get_cache(path=None):
    path = path or cache_path()
    with _caches_lock:
        cache = _caches.get(path)
        if cache is None:
            cache = _caches[path] = AutotuneCache(path)
        return cache


def reset():
    """Drop in-memory cache singletons (tests; disk files survive)."""
    with _caches_lock:
        _caches.clear()
    _warned_corrupt.clear()
    _warmed.clear()


_warmed = set()


def warm():
    """Pull the persistent cache for this device into memory ahead of
    first trace — the per-device cache consultation
    :class:`veles_tpu.accelerated_units.AcceleratedUnit` performs at
    initialize, mirroring the reference's program-build/binary-cache
    discipline (``veles/backends.py``: load the device's tuned
    BLOCK_SIZE database before building kernels). One JSON read per
    cache file per process; returns the entry count (0 when off)."""
    if mode() == "off":
        return 0
    from veles_tpu.telemetry import profiler
    cache = get_cache()
    with profiler.phase("autotune_load"):
        n = len(cache)  # forces the lazy disk load
    if cache.path not in _warmed:
        _warmed.add(cache.path)
        import logging
        logging.getLogger("autotune").info(
            "autotune cache %s: %d tuned shapes (mode=%s)",
            cache.path, n, mode())
    return n


# -- measurement -------------------------------------------------------------

def _measure(fn, args, iters=None):
    """Steady-state seconds per call of ``fn(*args)``: ``iters``
    applications chained inside ONE jit by a scalar carry perturbing
    the first operand (defeats CSE), ended by one scalar read that
    waits for the whole chain — per-call timing would count one
    dispatch per kernel, which is not the kernel's time."""
    import jax
    import jax.numpy as jnp

    if iters is None:
        iters = env_knob("VELES_AUTOTUNE_ITERS", 10, parse=int)

    def body(c, _):
        out = fn(args[0] + c.astype(args[0].dtype), *args[1:])
        return out.ravel()[0].astype(jnp.float32) * 1e-30, None

    chain = jax.jit(lambda: jax.lax.scan(
        body, jnp.float32(0), None, length=iters)[0])
    float(chain())  # compile + settle
    t0 = time.perf_counter()
    float(chain())
    return (time.perf_counter() - t0) / iters


def _rand(shape, dtype, seed=0):
    import jax.numpy as jnp
    arr = (numpy.random.RandomState(seed)
           .rand(*shape).astype(numpy.float32) - 0.5)
    return jnp.asarray(arr).astype(dtype)


# -- the plan/search core ----------------------------------------------------

def _key(op, **fields):
    return op + "|" + "|".join(
        "%s=%s" % (k, fields[k]) for k in sorted(fields))


def _plan(op, fields, candidates_fn, runner_fn, flops=None,
          shape_label=None):
    """Answer ``(impl, config)`` for one op instance.

    ``candidates_fn()`` -> ordered [(impl, config)] with the NATIVE
    baseline first; ``runner_fn(impl, config)`` -> (callable, args)
    measured by :func:`_measure`, or None to skip. Consults the cache
    first; searches only in ``search`` mode on a tunable backend.
    """
    m = mode()
    if m == "off":
        return _DEFAULT
    searches, hits, misses, best_gauge = _metrics()
    cache = get_cache()
    key = _key(op, **fields)
    entry = cache.get(key)
    if entry is not None:
        hits.inc()
        return entry["impl"], entry.get("config")
    if m != "search" or not tunable():
        misses.inc()
        return _DEFAULT
    if not _trace_state_clean():
        # Consulted from inside a jit trace (e.g. a unit's jitted
        # apply()): _measure would hit tracers and every candidate
        # would fail. Defer — an eager consult (gemm_bench --autotune,
        # profile_step --tune, or accelerated_units warm-load) tunes
        # the shape; persisting a failed search here would poison the
        # cache with a permanent "default" winner.
        misses.inc()
        return _DEFAULT
    with _search_lock:
        entry = cache.get(key)  # lost the race: someone else tuned it
        if entry is None:
            entry = _search(op, key, candidates_fn(), runner_fn,
                            flops, shape_label)
            if entry is None:  # nothing measured: don't persist
                misses.inc()
                return _DEFAULT
            cache.put(key, entry)
    return entry["impl"], entry.get("config")


def _search(op, key, candidates, runner_fn, flops, shape_label):
    searches, hits, misses, best_gauge = _metrics()
    searches.inc()
    budget = env_knob("VELES_AUTOTUNE_BUDGET_S", 20.0, parse=float)
    results = []
    with tracing.span("autotune:search", op=op, key=key):
        t0 = time.perf_counter()
        for impl, cfg in candidates:
            # the baseline is always measured; alternatives only
            # within the budget (compile time counts against it)
            if results and time.perf_counter() - t0 > budget:
                break
            made = runner_fn(impl, cfg)
            if made is None:
                continue
            fn, args = made
            try:
                results.append((impl, cfg, _measure(fn, args)))
            except Exception:
                continue  # unbuildable candidate (e.g. VMEM overflow)
    if not results:
        return None  # every candidate failed: not a tunable context
    # the baseline is candidates[0] by contract, but it may itself have
    # failed to build (e.g. a VMEM-hungry default block): only apply
    # the anti-flap win margin against a baseline that actually ran,
    # and never mislabel a surviving alternative as the baseline
    base_id = (candidates[0][0], candidates[0][1])
    base = next((r for r in results if (r[0], r[1]) == base_id), None)
    impl, cfg, best_s = min(results, key=lambda r: r[2])
    if base is not None:
        base_impl, base_cfg, base_s = base
        if (impl, cfg) != (base_impl, base_cfg) and \
                best_s > base_s * (1.0 - _WIN_MARGIN):
            impl, cfg, best_s = base_impl, base_cfg, base_s
    by_impl = {}
    for r_impl, _, r_s in results:
        by_impl[r_impl] = min(by_impl.get(r_impl, r_s), r_s)
    entry = {"impl": impl, "config": cfg,
             "baseline_impl": base[0] if base else None,
             "best_ms": round(best_s * 1e3, 4),
             "impl_ms": {k: round(v * 1e3, 4)
                         for k, v in sorted(by_impl.items())},
             "candidates": len(results)}
    if base is not None:
        entry["baseline_ms"] = round(base[2] * 1e3, 4)
    if flops:
        if base is not None:
            entry["baseline_tflops"] = round(flops / base[2] / 1e12, 3)
        entry["best_tflops"] = round(flops / best_s / 1e12, 3)
        # the winning candidate joins the cost book: tuned kernels get
        # the same roofline row as the compiled segments
        try:
            from veles_tpu.telemetry import profiler
            book = profiler.get_cost_book()
            label = "autotune:%s:%s" % (op, shape_label or "?")
            book.note_cost(label, flops, 0.0)
            book.observe_ms(label, best_s)
        except Exception:
            pass
        best_gauge.labels(op=op, shape=shape_label or "?").set(
            entry["best_tflops"])
    return entry


def summary():
    """Report blob for scripts: path, mode, entries, counters."""
    reg = get_registry()

    def _val(name):
        metric = reg.get(name)
        try:
            return metric.value if metric is not None else 0.0
        except ValueError:
            return 0.0
    cache = get_cache()
    return {"path": cache.path, "mode": mode(),
            "device": device_kind(), "entries": dict(cache.items()),
            "searches": _val("veles_autotune_searches_total"),
            "hits": _val("veles_autotune_cache_hits_total"),
            "misses": _val("veles_autotune_misses_total")}


# -- candidate spaces --------------------------------------------------------

#: scoped-VMEM budget for one grid step's working set (of ~16 MB/core;
#: leave headroom for pipelining's double buffers)
_VMEM_BUDGET = 10 * 1024 * 1024
_DS_OPTIONS = (("parallel", "parallel", "arbitrary"),
               ("arbitrary", "arbitrary", "arbitrary"))


def _block_divisors(dim, options, floor):
    """Candidate block sizes: divisors of ``dim`` from ``options``;
    if none divide, the dimension itself when it is small and aligned
    to ``floor`` (thin shapes run as one block)."""
    out = [b for b in options if b <= dim and dim % b == 0]
    if not out and dim <= max(options) and dim % floor == 0:
        out = [dim]
    return out


def _itemsize(dtype):
    try:
        return numpy.dtype(dtype).itemsize
    except TypeError:
        return 2 if "bfloat16" in str(dtype) else 4


def gemm_candidates(m, n, k, dtype, scratch=1):
    """(impl, config) grid for a tiled MXU GEMM, XLA baseline first.
    ``scratch`` = number of (bm, bn) f32 VMEM accumulators the kernel
    keeps (2 for the Kahan variant)."""
    isz = _itemsize(dtype)
    sub = 16 if isz == 2 else 8  # min sublane tile for the dtype
    cands = [("xla", None)]
    for bm in _block_divisors(m, (128, 256, 512), sub):
        for bn in _block_divisors(n, (128, 256, 512), 128):
            for bk in _block_divisors(k, (128, 256, 512, 1024, 2048),
                                      128):
                vmem = ((bm * bk + bk * bn) * isz +
                        bm * bn * 4 * (scratch + 1))
                if vmem > _VMEM_BUDGET:
                    continue
                for ds in _DS_OPTIONS:
                    cands.append(("pallas", {"bm": bm, "bn": bn,
                                             "bk": bk, "ds": list(ds)}))
    return cands


def ds_tuple(cfg, default=("parallel", "parallel", "arbitrary")):
    """Config-dict -> hashable dimension_semantics tuple."""
    return tuple(cfg.get("ds") or default) if cfg else default


# -- op plans ----------------------------------------------------------------

def _gemm_mod():
    """The :mod:`veles_tpu.ops.gemm` MODULE. ``from veles_tpu.ops
    import gemm`` yields the re-exported function (the package
    ``__init__`` shadows the submodule attribute), so resolve through
    ``sys.modules`` after a plain import."""
    import sys
    import veles_tpu.ops.gemm  # noqa: F401 -- ensures sys.modules entry
    return sys.modules["veles_tpu.ops.gemm"]

def gemm_plan(m, n, k, dtype, ta=False, tb=False, level=0):
    """Plan one GEMM: ('default'|'xla'|'pallas'|'loop'|'pairwise',
    config). Keyed the ISSUE way: (op, M, N, K, dtype, transpose
    flags, device kind) — device kind keys the cache FILE."""
    if mode() == "off":
        return _DEFAULT
    import jax.numpy as jnp
    gemm_mod = _gemm_mod()

    fields = dict(m=m, n=n, k=k, dtype=str(dtype),
                  ta=int(bool(ta)), tb=int(bool(tb)))
    flops = 2.0 * m * n * k
    label = "%dx%dx%d" % (m, n, k)
    interp = kernel_interpret()

    # ta/tb are part of the key AND of the measured workload: runtime
    # callers (e.g. fused_linear's backward) hand the dot a transposed
    # view, so candidates must be timed WITH the in-graph transpose —
    # operands stay stored in the pre-transpose layout and the op
    # itself does the .T, exactly as at the call site.
    def operands(seed_b=1):
        a = _rand((k, m) if ta else (m, k), dtype)
        b = _rand((n, k) if tb else (k, n), dtype, seed=seed_b)
        return a, b

    def opa(a):
        return a.T if ta else a

    def opb(b):
        return b.T if tb else b

    if level <= 0:
        def run(impl, cfg):
            a, b = operands()
            if impl == "xla":
                return (lambda a, b: jnp.dot(
                    opa(a), opb(b),
                    preferred_element_type=jnp.float32)), (a, b)
            return (lambda a, b: gemm_mod.pallas_gemm(
                opa(a), opb(b), bm=cfg["bm"], bn=cfg["bn"],
                bk=cfg["bk"], out_dtype=jnp.float32,
                dimension_semantics=ds_tuple(cfg),
                interpret=interp)), (a, b)
        return _plan("gemm", fields,
                     lambda: gemm_candidates(m, n, k, dtype),
                     run, flops, label)

    if level == 1:
        def kahan_cands():
            cands = [("loop", {"chunk": None})]
            cands += [("loop", {"chunk": c})
                      for c in (256, 1024) if c < k]
            cands += [c for c in gemm_candidates(m, n, k, dtype,
                                                 scratch=2)
                      if c[0] == "pallas"]
            return cands

        def run(impl, cfg):
            a, b = operands()
            if impl == "loop":
                return (lambda a, b: gemm_mod._kahan_matmul_loop(
                    opa(a), opb(b), chunk=cfg.get("chunk"))), (a, b)
            return (lambda a, b: gemm_mod.pallas_kahan_gemm(
                opa(a), opb(b), bm=cfg["bm"], bn=cfg["bn"],
                bk=cfg["bk"], dimension_semantics=ds_tuple(cfg),
                interpret=interp)), (a, b)
        return _plan("gemm_kahan", fields, kahan_cands, run, flops,
                     label)

    # level 2: pairwise split-K — tune the partial count
    def pairwise_cands():
        cands, p = [("pairwise", {"parts": None})], 2
        while p < k and len(cands) < 8:
            if k % p == 0:
                cands.append(("pairwise", {"parts": p}))
            p *= 2
        return cands

    def run(impl, cfg):
        a, b = operands()
        return (lambda a, b: gemm_mod.pairwise_matmul(
            opa(a), opb(b), parts=cfg.get("parts"))), (a, b)
    return _plan("gemm_pairwise", fields, pairwise_cands, run, flops,
                 label)


def linear_plan(m, n, k, dtype, activation, out_dtype):
    """Plan the fused All2All forward: GEMM with a bias+activation
    epilogue absorbed into the kernel's output step vs the XLA
    dot -> add -> activation chain."""
    if mode() == "off":
        return _DEFAULT
    import jax.numpy as jnp
    gemm_mod = _gemm_mod()

    fields = dict(m=m, n=n, k=k, dtype=str(dtype), act=str(activation),
                  out=str(out_dtype))
    interp = kernel_interpret()

    def run(impl, cfg):
        x = _rand((m, k), dtype)
        w = _rand((k, n), dtype, seed=1)
        b = _rand((n,), jnp.float32, seed=2)
        if impl == "xla":
            act = gemm_mod.epilogue_fn(activation)
            return (lambda x, w, b: act(jnp.dot(
                x, w, preferred_element_type=jnp.float32) + b)
                .astype(out_dtype)), (x, w, b)
        return (lambda x, w, b: gemm_mod.pallas_gemm(
            x, w, bias=b, activation=activation,
            bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
            out_dtype=out_dtype, dimension_semantics=ds_tuple(cfg),
            interpret=interp)), (x, w, b)
    return _plan("linear", fields,
                 lambda: gemm_candidates(m, n, k, dtype),
                 run, 2.0 * m * n * k, "%dx%dx%d" % (m, n, k))


def lrn_plan(rows, channels, dtype, which="fwd"):
    """Tune the fused-LRN kernels' row-block size (the one free
    parameter: the channel window never crosses rows, so any row
    tiling is halo-free)."""
    if mode() == "off":
        return _DEFAULT
    from veles_tpu.ops import lrn as lrn_mod

    fields = dict(rows=rows, c=channels, dtype=str(dtype), which=which)
    isz = _itemsize(dtype)

    def cands():
        out = [("pallas", {"block_rows": lrn_mod._BLOCK_ROWS})]
        for br in (128, 256, 1024, 2048):
            if br == lrn_mod._BLOCK_ROWS or br > rows:
                continue
            # fwd keeps ~4 (br, C) f32 temporaries live, bwd ~6
            live = 4 if which == "fwd" else 6
            if br * channels * (4 * live + isz) > _VMEM_BUDGET:
                continue
            out.append(("pallas", {"block_rows": br}))
        return out

    def run(impl, cfg):
        x = _rand((rows, channels), dtype)
        g = _rand((rows, channels), dtype, seed=1)
        interp = kernel_interpret()
        if which == "fwd":
            return (lambda x: lrn_mod._call_fwd(
                x, 2.0, 1e-4, 0.75, 5, interp,
                block_rows=cfg["block_rows"])), (x,)
        return (lambda x, g: lrn_mod._call_bwd(
            x, g, 2.0, 1e-4, 0.75, 5, interp,
            block_rows=cfg["block_rows"])), (x, g)
    return _plan("lrn_" + which, fields, cands, run,
                 shape_label="%dx%d" % (rows, channels))


def reduce_plan(m, n, dtype):
    """Tune the Pallas column reduction's row-block size vs XLA sum."""
    if mode() == "off":
        return _DEFAULT
    import jax.numpy as jnp
    from veles_tpu.ops import reduce as reduce_mod

    fields = dict(m=m, n=n, dtype=str(dtype))

    def cands():
        out = [("xla", None)]
        out += [("pallas", {"block_rows": br})
                for br in (128, 256, 512, 1024)
                if br <= m and m % br == 0]
        return out

    def run(impl, cfg):
        x = _rand((m, n), dtype)
        if impl == "xla":
            return (lambda x: jnp.sum(
                x.astype(jnp.float32), axis=0)), (x,)
        return (lambda x: reduce_mod.pallas_column_reduce(
            x, block_rows=cfg["block_rows"],
            interpret=kernel_interpret())), (x,)
    return _plan("col_reduce", fields, cands, run,
                 shape_label="%dx%d" % (m, n))
