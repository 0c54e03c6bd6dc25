"""Async double-buffered input pipeline (ISSUE 8, ROADMAP item 4).

The fused step compiler eliminated per-step host traffic for datasets
that fit in device memory — but only for those. This module supplies
the other half of ROADMAP item 4:

* :class:`PrefetchPipeline` — a bounded-depth background pipeline.
  Worker threads run host ETL (``fill_minibatch``-style row gathers)
  and issue the host→device transfer for shard N+k while the step
  thread computes shard N, so the step thread's input wait collapses
  to the pipeline's warm fill plus whatever ETL cannot be hidden
  behind compute (the libhclooc out-of-core overlap pattern,
  PAPERS.md). Depth is ``VELES_PREFETCH`` (default 2 =
  double-buffered; 0 reproduces the synchronous path exactly).

* :class:`StagingRing` — a small ring of device staging slots the
  transfers land in. Residency is bounded to ``depth + 2`` shards
  (the one in compute, the queued ones, one being placed); a slot's
  previous occupant is deleted deterministically when the slot is
  reused, so out-of-core streaming has a flat HBM footprint however
  long the epoch.

* residency planning — :func:`plan_residency` decides
  "device-resident when it fits, streamed when it doesn't" against
  the device budget (``VELES_DEVICE_BUDGET_MB`` override — the
  artificial cap the out-of-core tests and benches use — else a
  fraction of the device's reported ``bytes_limit``), and
  :func:`shard_batches` sizes the fixed shards (``VELES_SHARD_MB``).

Telemetry (docs/OBSERVABILITY.md): every consumer-side wait lands in
the ``veles_step_input_wait_ms`` histogram; per-segment starvation
fraction (wait / wall) is published as the
``veles_input_starvation_fraction`` gauge by the streamed drivers in
:mod:`veles_tpu.train.step`; ETL / transfer times ride
``veles_prefetch_etl_ms`` / ``veles_prefetch_h2d_ms`` and
``prefetch:*`` trace spans; the time to the first ready item is the
``pipeline_fill`` startup phase.

``VELES_ETL_THROTTLE_MS`` injects a per-shard host-ETL sleep — the
deliberately slow loader that ``scripts/input_bench.py`` and the perf
gate's overlap probe use to measure (not assert) the overlap win.
"""

import threading
import time
import weakref

import numpy

from veles_tpu.envknob import env_knob
from veles_tpu.telemetry import tracing

#: live pipelines (weak): the conftest session teardown closes any a
#: crashed test left running before the interpreter starts dying
_live_lock = threading.Lock()
_live = weakref.WeakSet()


def default_depth():
    """``VELES_PREFETCH`` (default 2; 0 = synchronous)."""
    return max(0, env_knob("VELES_PREFETCH", 2, parse=int,
                           on_error="default"))


def default_workers():
    """``VELES_PREFETCH_WORKERS`` ETL threads (default 1)."""
    return max(1, env_knob("VELES_PREFETCH_WORKERS", 1, parse=int,
                           on_error="default"))


def etl_throttle_s():
    """Injected per-shard ETL sleep (``VELES_ETL_THROTTLE_MS``) — the
    slow-loader simulation knob for benches/tests; 0 in production."""
    return max(0.0, env_knob("VELES_ETL_THROTTLE_MS", 0.0, parse=float,
                             on_error="default")) / 1e3


def _registry():
    from veles_tpu.telemetry.registry import get_registry
    return get_registry()


def input_wait_histogram():
    return _registry().histogram(
        "veles_step_input_wait_ms",
        "Step-thread wait for the next prefetched input shard")


def starvation_gauge():
    return _registry().gauge(
        "veles_input_starvation_fraction",
        "Input wait / wall fraction of the last streamed segment",
        labels=("phase",))


# -- the pipeline ------------------------------------------------------------


class PrefetchPipeline(object):
    """Ordered bounded-depth producer pipeline over ``n_items`` items.

    ``produce(i)`` runs on worker threads (host ETL + async H2D
    dispatch); the consumer calls :meth:`get` and receives items
    strictly in index order. At most ``depth`` produced-but-unconsumed
    items exist at any time, so device staging memory is bounded.

    A worker exception is delivered to the consumer: the :meth:`get`
    that reaches the failed index re-raises it (after closing the
    pipeline), so a broken loader fails the step loop loudly instead
    of hanging it. ``depth=0`` runs ``produce`` inline on the consumer
    thread — bit-identical to the pre-pipeline synchronous path, with
    the same telemetry (the wait IS the ETL+transfer time).
    """

    def __init__(self, produce, n_items, depth=None, workers=None,
                 name="input", wait_hist=None, fill_phase="pipeline_fill"):
        self.produce = produce
        self.n_items = int(n_items)
        self.depth = default_depth() if depth is None else max(0, depth)
        self.workers = default_workers() if workers is None \
            else max(1, workers)
        self.name = name
        self.wait_s = 0.0          #: cumulative consumer wait
        self.first_wait_s = None   #: warm fill (wait for item 0)
        self._cond = threading.Condition()
        self._results = {}         # index -> ("ok", item) | ("error", e)
        self._next_claim = 0
        self._next_get = 0
        self._stop = False
        self._threads = []
        # non-input consumers (the model-offload ring, ISSUE 17) keep
        # their waits out of the input-starvation accounting: they pass
        # their own histogram and opt out of the pipeline_fill phase
        self._wait_hist = (input_wait_histogram() if wait_hist is None
                           else wait_hist)
        self._fill_phase = fill_phase

    # -- worker side --------------------------------------------------------

    def start(self):
        if self.depth == 0 or self.n_items == 0:
            return self  # synchronous mode: no threads at all
        for k in range(min(self.workers, self.n_items)):
            t = threading.Thread(
                target=self._work, daemon=True,
                name="veles-prefetch-%s-%d" % (self.name, k))
            t.start()
            self._threads.append(t)
        with _live_lock:
            _live.add(self)
        return self

    def _work(self):
        while True:
            with self._cond:
                while (not self._stop and
                       self._next_claim < self.n_items and
                       self._next_claim - self._next_get >= self.depth):
                    self._cond.wait(0.1)
                if self._stop or self._next_claim >= self.n_items:
                    return
                i = self._next_claim
                self._next_claim += 1
            try:
                with tracing.span("prefetch:produce", index=i,
                                  pipeline=self.name):
                    out = ("ok", self.produce(i))
            except BaseException as e:  # delivered to the consumer
                out = ("error", e)
            with self._cond:
                self._results[i] = out
                self._cond.notify_all()
                if out[0] == "error":
                    # stop claiming new work; indices already claimed
                    # by other workers still complete, so the consumer
                    # reaches this error without gaps
                    self._next_claim = self.n_items

    # -- consumer side ------------------------------------------------------

    def get(self):
        """Next item in order. Returns ``(item, wait_s)``; re-raises a
        worker exception at its index."""
        i = self._next_get
        if i >= self.n_items:
            raise IndexError("pipeline of %d items exhausted"
                             % self.n_items)
        start = time.perf_counter()
        if self.depth == 0:
            try:
                payload = self.produce(i)
            finally:
                self._next_get = i + 1
            kind = "ok"
        else:
            with self._cond:
                while i not in self._results and not self._stop:
                    self._cond.wait(0.1)
                if i not in self._results:
                    raise RuntimeError(
                        "prefetch pipeline %r closed while the step "
                        "thread waited for item %d" % (self.name, i))
                kind, payload = self._results.pop(i)
                self._next_get = i + 1
                self._cond.notify_all()
        wait = time.perf_counter() - start
        self.wait_s += wait
        self._wait_hist.observe(wait * 1e3)
        tracing.add_complete("prefetch:wait", start, wait, index=i,
                             pipeline=self.name)
        if self.first_wait_s is None:
            self.first_wait_s = wait
            if self._fill_phase:
                from veles_tpu.telemetry import profiler
                profiler.record_phase(self._fill_phase, wait)
        if kind == "error":
            self.close()
            raise payload
        return payload, wait

    def __iter__(self):
        while self._next_get < self.n_items:
            yield self.get()[0]

    def close(self, timeout=10.0):
        """Stop the workers and join every pipeline thread."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout)
        self._threads = [t for t in self._threads if t.is_alive()]
        with _live_lock:
            # a worker stuck past the join timeout keeps the pipeline
            # registered so shutdown_all() can retry before teardown
            if not self._threads:
                _live.discard(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()
        return False


def shutdown_all(timeout=10.0):
    """Close every live pipeline (conftest session teardown: worker
    threads must not outlive pytest into interpreter shutdown)."""
    with _live_lock:
        pipes = list(_live)
    for p in pipes:
        p.close(timeout)


# -- device staging ----------------------------------------------------------


class StagingRing(object):
    """Fixed ring of device staging slots for streamed shards.

    ``place()`` transfers a PYTREE of host arrays (a loader shard's
    ``(data, truth)`` tuple, or a model layer group's params/opt-state
    dicts — ISSUE 17) through the next slot and deletes the slot's
    previous occupant first, so at most ``slots`` shards are ever
    device-resident — the flat-HBM guarantee out-of-core streaming
    depends on. ``placer`` maps one host LEAF to its device form
    (plain ``device_put``, a ``NamedSharding`` placement for
    data-parallel meshes, or the measured ``reshard.host_placer``).
    """

    def __init__(self, slots, placer):
        self._lock = threading.Lock()
        self._slots = [None] * max(1, int(slots))
        self._pos = 0
        self._placer = placer
        self._closed = False

    @staticmethod
    def _delete(arrays):
        import jax
        for arr in jax.tree_util.tree_leaves(arrays):
            try:
                # PJRT defers the actual free until in-flight executions
                # using the buffer complete, so deleting here (while the
                # previous shard may still be computing) is safe — the
                # residency BOUND is what this ring guarantees
                arr.delete()
            except Exception:
                pass  # already consumed/deleted: bound still holds

    def place(self, host_arrays):
        with self._lock:
            idx = self._pos % len(self._slots)
            self._pos += 1
            old = self._slots[idx]
            self._slots[idx] = None
        if old is not None:
            self._delete(old)
        import jax
        placed = jax.tree_util.tree_map(self._placer, host_arrays)
        with self._lock:
            if self._closed:
                # clear() raced an in-flight place (a worker past its
                # join timeout): don't re-insert into the emptied ring
                # — drop our own shard so shutdown's residency promise
                # holds; the (dead) consumer never uses it
                drop, placed_slot = placed, None
            else:
                drop, placed_slot = None, placed
                self._slots[idx] = placed_slot
        if drop is not None:
            self._delete(drop)
        return placed

    def reopen(self):
        """Accept placements again after a :meth:`clear` (a trainer
        reused across runs reopens its ring per segment)."""
        with self._lock:
            self._closed = False

    def clear(self):
        with self._lock:
            self._closed = True
            slots, self._slots = self._slots, [None] * len(self._slots)
        for old in slots:
            if old is not None:
                self._delete(old)


def default_placer(device=None):
    """Host ndarray -> committed ``jax.Array`` (async on TPU)."""
    import jax
    if device is not None and getattr(device, "is_jax", False):
        return device.put
    return jax.device_put


def sharded_placer(sharding, n_shards, interleave=False):
    """Host rows -> addressable per-device shards of a data-axis
    ``NamedSharding`` (ISSUE 15): THE pad-and-place implementation the
    GSPMD/data-parallel trainers hand the staging ring — streamed
    shards of the global batch land directly on their owning devices
    with no gather-then-scatter hop, the sample dim padded with zero
    rows to divide the axis (local shard indices never reach the pad
    rows). Placement goes through the measured reshard primitive, so
    per-shard H2D shows up as ``veles_reshard_ms{src="host"}``
    alongside ``veles_prefetch_h2d_ms``.

    A streamed shard is placed as it is: shard ``c`` holds rows
    ``[c R, (c + 1) R)``. The resident data set is placed with
    ``interleave``: row ``g`` is local row ``g // n_shards`` of shard
    ``g % n_shards`` (:func:`interleaved_home`), the pad rows last on
    the shards they fall to, so that a run of consecutive samples is
    spread evenly over the shards (:func:`deal_rows`; no
    ``veles_reshard_ms`` there: the caller's ``dataset_shard``
    start-up row times it)."""

    def place(host_array):
        tail = host_array.shape[1:]
        if interleave:  # host_array: on the host or on one device
            import jax
            shards = deal_rows(host_array, n_shards)
            return jax.make_array_from_callback(
                (len(shards[0]) * n_shards,) + tail, sharding,
                # (an axis of one shard asks for slice(None))
                lambda index: shards[
                    (index[0].start or 0) // len(shards[0])])
        pad = -host_array.shape[0] % n_shards
        if pad:
            host_array = numpy.concatenate([
                host_array, numpy.zeros((pad,) + tail, host_array.dtype)])
        from veles_tpu.parallel import reshard
        return reshard.reshard(host_array, sharding)
    return place


def deal_rows(source, n_shards, chunk_bytes=128 << 20):
    """The rows of ``source`` dealt to ``n_shards`` host arrays, row
    ``g`` as row ``g // n_shards`` of array ``g % n_shards``, the last
    rows zero where the count does not divide. ``source`` may lie on
    one device: it comes to the host in contiguous chunks of about
    ``chunk_bytes``, the next one on its way while one is dealt, so
    the device holds two chunks beside it and the host never a second
    copy of the whole (v5e, 5.5 GB of bfloat16, PR 36: 9.5 s, where
    the whole in one transfer takes 13.5-17 s and dealing it 6.4 s
    more; a shard taken as ONE strided slice on the device costs that
    device twice the shard)."""
    n, tail = source.shape[0], source.shape[1:]
    shards = [numpy.zeros((-(-n // n_shards),) + tail, source.dtype)
              for _ in range(n_shards)]

    def as_bytes(rows):
        # numpy copies an extension dtype (bfloat16) element by element
        return rows.reshape(len(rows), -1).view(numpy.uint8)

    row_bytes = max(1, source.dtype.itemsize * int(numpy.prod(tail)))
    chunk = n_shards * max(1, chunk_bytes // (row_bytes * n_shards))
    ahead = source[:chunk]
    for at in range(0, n, chunk):
        piece, ahead = ahead, source[at + chunk:at + 2 * chunk]
        if hasattr(ahead, "copy_to_host_async"):
            ahead.copy_to_host_async()
        piece = as_bytes(numpy.asarray(piece))
        for shard in range(n_shards):
            mine = piece[shard::n_shards]
            as_bytes(shards[shard])[
                at // n_shards:at // n_shards + len(mine)] = mine
    return shards


def interleaved_home(idx, n_shards):
    """``(shard, local row)`` of sample ids in a data set that
    :func:`sharded_placer` placed with ``interleave``: the one
    statement of that placement's rule."""
    idx = numpy.asarray(idx)
    return idx % n_shards, idx // n_shards


def interleaved_rows(idx, n_samples, n_shards):
    """Sample ids -> their rows in a data set of ``n_samples`` placed
    with ``interleave``, as one array over the shards (-1 stays)."""
    shard, local = interleaved_home(idx, n_shards)
    return numpy.where(numpy.asarray(idx) < 0, -1, shard * -(
        -n_samples // n_shards) + local).astype(numpy.int32)


def warmup_ring(slots=2, device=None):
    """A small :class:`StagingRing` for serving-replica warm-up.

    The replica bucket sweep (``serving/replica.py``) stages each
    bucket's zeros through this ring instead of materializing every
    bucket on device at once: two slots bound the sweep's HBM
    footprint to the two largest consecutive buckets, and on real
    accelerators the async ``device_put`` overlaps the previous
    bucket's compile — the same double-buffering the training input
    pipeline uses, reused as the H2D path for serving cold starts
    (ROADMAP item 4, serving half)."""
    return StagingRing(slots, default_placer(device))


# -- residency planning ------------------------------------------------------


def device_budget_bytes(device=None):
    """Bytes of device memory the DATASET may occupy resident.

    ``VELES_DEVICE_BUDGET_MB`` wins (the artificial cap out-of-core
    tests/benches set; ``0``/empty = unknown); else 60% of the
    device's reported ``bytes_limit`` (params, activations and XLA
    scratch need the rest); else None (unknown — stay resident, the
    pre-pipeline behavior)."""
    mb = env_knob("VELES_DEVICE_BUDGET_MB", parse=float,
                  on_error="default")
    if mb is not None:
        return mb * 1e6 if mb > 0 else None
    stats = {}
    try:
        if device is not None and getattr(device, "is_jax", False):
            stats = device.memory_stats or {}
        else:
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        stats = {}
    limit = stats.get("bytes_limit")
    return 0.6 * limit if limit else None


def plan_residency(dataset_bytes, device=None, force=None):
    """``"resident"`` or ``"streamed"`` for a dataset of
    ``dataset_bytes``.

    ``force`` (or ``VELES_STREAM``: ``1``/``force``/``on`` stream
    always, ``0``/``off``/``no`` never; anything else is ignored and
    the budget decides) overrides the budget comparison."""
    if force is None:
        env = env_knob("VELES_STREAM")
        if env in ("1", "force", "on", "yes", "true"):
            force = True
        elif env in ("0", "off", "no", "false"):
            force = False
    if force is not None:
        return "streamed" if force else "resident"
    budget = device_budget_bytes(device)
    if budget is not None and dataset_bytes > budget:
        return "streamed"
    return "resident"


def shard_batches(batch_bytes, depth=None, budget_bytes=None):
    """Minibatches per fixed-size streamed shard.

    Targets ``VELES_SHARD_MB`` (default 256) per shard, shrunk so the
    ring's ``depth + 2`` resident shards still fit the device budget
    when one is known."""
    target = env_knob("VELES_SHARD_MB", 256.0, parse=float,
                      on_error="default") * 1e6
    depth = default_depth() if depth is None else depth
    if budget_bytes:
        target = min(target, budget_bytes / (depth + 2))
    return max(1, int(target // max(1, batch_bytes)))


# -- host ETL ----------------------------------------------------------------


def gather_rows(data, truth, indices):
    """``fill_minibatch``-style host ETL for one shard: gather rows of
    ``data``/``truth`` by global sample index.

    Matches the on-device gather's padding contract exactly
    (:meth:`FusedTrainer._gather`): index −1 produces a ZERO data row;
    truth is taken at ``max(idx, 0)`` and masked later by the loss
    math. Pure function over host arrays — safe from worker threads.
    """
    throttle = etl_throttle_s()
    if throttle:
        time.sleep(throttle)
    indices = numpy.asarray(indices).reshape(-1)
    safe = numpy.maximum(indices, 0)
    rows = data[safe]  # fancy index: always a fresh writable copy
    invalid = indices < 0
    if invalid.any():
        rows[invalid] = 0
    return rows, truth[safe]


def local_indices(global_idx):
    """Shard-local index matrix for a shard built by
    :func:`gather_rows`: row i of the shard replaces global sample
    ``global_idx.flat[i]``, pads stay −1 so the in-scan valid mask
    (and therefore the loss math) is unchanged."""
    global_idx = numpy.asarray(global_idx)
    flat = global_idx.reshape(-1)
    local = numpy.where(flat < 0, -1,
                        numpy.arange(flat.size)).astype(numpy.int32)
    return local.reshape(global_idx.shape)
