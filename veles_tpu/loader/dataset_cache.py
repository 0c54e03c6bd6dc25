"""On-disk cache for generated benchmark datasets.

ROADMAP item 4's first half: BENCH_r05 spends 86-107 s in ``loader
init (generation)`` against a 30 s timed window, so every tuning
iteration pays ~3x its measurement time in synthetic-data generation.
Generation is deterministic from its config (sizes, seed, dtype), so
the arrays are cached to disk keyed by a hash of that config and a
schema version: any config change produces a different hash, which IS
the invalidation. Files live under the veles cache dir
(:func:`veles_tpu.backends.veles_cache_dir`), sibling to the XLA
compile cache.

Layout: one directory per dataset, ``datasets/<name>-<hash12>/``
holding ``meta.json`` plus one raw little-endian ``.bin`` per array
(``tofile``/``fromfile`` — npz cannot hold bfloat16 and would buffer
the ~5 GB flagship set through zlib). A partially-written cache is
impossible to observe: arrays land in a ``.tmp-<pid>`` directory that
is renamed into place only after ``meta.json`` (written last) is
complete, and any load error falls back to regeneration.

``VELES_DATASET_CACHE=0`` disables (generation always runs);
``VELES_DATASET_CACHE=rw`` (default) reads and writes.
"""

import hashlib
import json
import logging
import os
import shutil

import numpy

from veles_tpu.envknob import env_flag

#: bump to invalidate every cached dataset at once
CACHE_VERSION = 1

_log = logging.getLogger("dataset_cache")


def enabled():
    return env_flag("VELES_DATASET_CACHE", True)


def config_hash(config):
    """Stable short hash of a JSON-able config dict."""
    blob = json.dumps({"version": CACHE_VERSION, "config": config},
                      sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def _dataset_dir(name, config):
    from veles_tpu.backends import veles_cache_dir
    return os.path.join(veles_cache_dir("datasets"),
                        "%s-%s" % (name, config_hash(config)))


def _dtype_of(spec):
    """dtype string -> numpy dtype, accepting ml_dtypes names
    (bfloat16) that ``numpy.dtype`` alone rejects."""
    try:
        return numpy.dtype(spec)
    except TypeError:
        import ml_dtypes
        return numpy.dtype(getattr(ml_dtypes, spec))


def _load(path):
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    if meta.get("version") != CACHE_VERSION:
        raise ValueError("stale schema %r" % meta.get("version"))
    arrays = {}
    for name, spec in meta["arrays"].items():
        dtype = _dtype_of(spec["dtype"])
        shape = tuple(spec["shape"])
        arr = numpy.fromfile(os.path.join(path, name + ".bin"),
                             dtype=numpy.uint8)
        arrays[name] = arr.view(dtype).reshape(shape)
    return arrays


def _sweep_stale_tmp(path):
    """Remove ``.tmp-<pid>`` staging dirs abandoned by dead processes
    (a kill/OOM mid-store would otherwise leak the ~5 GB flagship set
    per crashed run). A pid that is still alive keeps its dir."""
    base = os.path.dirname(path)
    for entry in os.listdir(base):
        full = os.path.join(base, entry)
        if ".tmp-" not in entry or not os.path.isdir(full):
            continue
        try:
            pid = int(entry.rsplit(".tmp-", 1)[1])
        except ValueError:
            pid = -1
        try:
            if pid > 0:
                os.kill(pid, 0)  # alive: writer still at work
                continue
        except ProcessLookupError:
            pass  # no such process: orphan
        except OSError:
            continue  # EPERM etc.: alive but not ours — keep it
        _log.info("removing orphaned dataset staging dir %s", full)
        shutil.rmtree(full, ignore_errors=True)


def _store(path, arrays):
    _sweep_stale_tmp(path)
    tmp = "%s.tmp-%d" % (path, os.getpid())
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    meta = {"version": CACHE_VERSION, "arrays": {}}
    for name, arr in arrays.items():
        arr = numpy.ascontiguousarray(arr)
        arr.view(numpy.uint8).tofile(os.path.join(tmp, name + ".bin"))
        meta["arrays"][name] = {"dtype": str(arr.dtype),
                                "shape": list(arr.shape)}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1, sort_keys=True)
    shutil.rmtree(path, ignore_errors=True)
    try:
        os.replace(tmp, path)
    except OSError:
        # a concurrent process won the rename; its arrays equal ours
        shutil.rmtree(tmp, ignore_errors=True)


def cached_build(name, config, builder):
    """``builder() -> {name: ndarray}``, memoized on disk.

    Cache hit: the arrays are read back (no generation). Miss or any
    load failure: ``builder`` runs and its output is persisted for the
    next process. With the cache disabled the builder always runs and
    nothing is written.
    """
    from veles_tpu.telemetry import profiler
    if not enabled():
        with profiler.phase("dataset_generate"):
            return builder()
    path = _dataset_dir(name, config)
    if os.path.isdir(path):
        try:
            with profiler.phase("dataset_load"):
                arrays = _load(path)
            _log.info("dataset cache hit: %s", path)
            return arrays
        except Exception as e:  # corrupt cache == miss, regenerate
            _log.warning("ignoring unreadable dataset cache %s (%s: %s)",
                         path, type(e).__name__, e)
    with profiler.phase("dataset_generate"):
        arrays = builder()
    try:
        _store(path, arrays)
        _log.info("dataset cache store: %s", path)
    except OSError as e:
        _log.warning("dataset cache store failed for %s (%s)", path, e)
    return arrays
