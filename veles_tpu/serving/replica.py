"""Model replicas: warm JIT caches, least-loaded dispatch, hot-swap.

A :class:`Replica` owns one jitted forward of the current model plus a
worker thread draining its private work queue — the thread-backed
analog of a per-chip serving process (process isolation is a deployment
choice layered on top; inside one host, threads share the XLA compile
cache and the weights' device buffers, which is exactly what we want
for N replicas of the same model on one chip).

Batch shapes are bucketed to powers of two up to ``max_batch_size``
(``bucket_for``): the padded batch always hits a warm compilation, so
tail latency never pays a compile. ``warm()`` pre-compiles every bucket
at startup and after every swap — a swapped-in model serves its first
request from a warm cache.

:class:`ReplicaPool` fans work out across replicas by least queued
work, and :meth:`ReplicaPool.swap` hot-swaps the model: the swap rides
the same work queue as inference, so each replica drains everything
already accepted, swaps, re-warms, and only then takes new work — no
request ever observes a half-swapped replica.

The pool is **elastic** (ISSUE 14): :meth:`ReplicaPool.add_replica`
grows it under fire (the new replica warms its buckets BEFORE joining
dispatch, so scale-up never routes traffic onto a cold JIT cache) and
:meth:`ReplicaPool.remove_replica` shrinks it by removing a replica
from dispatch first and only then draining what it already accepted —
zero in-flight requests die on a scale-down. Warm-up H2D rides the
PR 8 :class:`~veles_tpu.loader.prefetch.StagingRing` (bounded device
residency during the bucket sweep) and is recorded as the
``veles_phase_ms{phase="replica_warmup"}`` startup gauge — the
serving half of ROADMAP item 4's cold-start hunt.
"""

import queue
import threading
import time

import numpy

from veles_tpu.logger import Logger


def bucket_for(n, max_batch_size):
    """Smallest power-of-two >= n, clamped to max_batch_size."""
    if n >= max_batch_size:
        return max_batch_size
    b = 1
    while b < n:
        b <<= 1
    return min(b, max_batch_size)


def buckets_upto(max_batch_size):
    out, b = [], 1
    while b < max_batch_size:
        out.append(b)
        b <<= 1
    out.append(max_batch_size)
    return out


class _Swap(object):
    """Queue sentinel: drain, then swap to ``model``."""

    def __init__(self, model):
        self.model = model
        self.done = threading.Event()


class Replica(Logger):
    """One warm copy of the model with a private dispatch queue."""

    #: load charged while a swap is queued/running: a swapping replica
    #: must look maximally busy to pick()/any_idle(), or new batches
    #: would be routed behind its drain + full re-warm while the other
    #: replicas sit idle
    SWAP_LOAD = 1 << 20

    def __init__(self, model, index=0, max_batch_size=64, warm=True):
        super(Replica, self).__init__()
        self.index = index
        self.max_batch_size = int(max_batch_size)
        self._queue = queue.Queue()
        self._pending = 0           # queued + running rows, approx load
        self._pending_lock = threading.Lock()
        self._retired = False       # out of dispatch, refusing batches
        self.batches_done = 0
        self.rows_done = 0
        self._stop = threading.Event()
        self._bind(model, warm=warm)
        self._thread = threading.Thread(
            target=self._work_loop, daemon=True,
            name="replica-%d" % index)
        self._thread.start()

    # -- model binding -----------------------------------------------------

    def _bind(self, model, warm=True):
        import jax
        self.model = model
        self._forward = jax.jit(model.forward_fn())
        self.warmed_buckets = []
        if warm:
            self.warm()

    def warm(self):
        """Compile every batch bucket ahead of traffic.

        The warm-up batches reach the device through the input
        pipeline's :class:`~veles_tpu.loader.prefetch.StagingRing`
        (the same H2D path streamed training shards ride): at most
        two buckets are device-resident during the sweep instead of
        every bucket's zeros accumulating, and on real accelerators
        the placement overlaps the previous bucket's compile. The
        sweep is the ``replica_warmup`` startup phase — scale-up cost
        is measured, not guessed."""
        from veles_tpu.loader.prefetch import warmup_ring
        from veles_tpu.telemetry import profiler
        book = profiler.get_cost_book()
        ring = warmup_ring()
        try:
            with profiler.phase("replica_warmup"):
                for bucket in buckets_upto(self.max_batch_size):
                    x = numpy.zeros(
                        (bucket,) + self.model.sample_shape,
                        numpy.float32)
                    staged, = ring.place((x,))
                    # force compile + execute
                    numpy.asarray(self._forward(staged))
                    # cost harvest AFTER the warming call: its compile
                    # populated the persistent XLA cache, so the
                    # harvest's lower().compile() deserializes instead
                    # of paying a second full compile — and the
                    # roofline table then covers every serving bucket
                    # alongside the train segments
                    book.harvest_function("serve_forward:b%d" % bucket,
                                          self._forward, (x,))
                    self.warmed_buckets.append(bucket)
        finally:
            ring.clear()
        self.debug("replica %d warm: %s v%d, buckets %s", self.index,
                   self.model.name, self.model.version,
                   self.warmed_buckets)

    # -- inference ---------------------------------------------------------

    def infer(self, batch):
        """Synchronous padded forward (runs on the worker thread)."""
        from veles_tpu.telemetry import profiler
        rows = batch.shape[0]
        bucket = bucket_for(rows, self.max_batch_size)
        if rows < bucket:
            pad = numpy.zeros((bucket - rows,) + batch.shape[1:],
                              batch.dtype)
            batch = numpy.concatenate([batch, pad], axis=0)
        with profiler.timed_op("serve_forward:b%d" % bucket):
            out = numpy.asarray(self._forward(batch))
        return out[:rows], bucket

    @property
    def load(self):
        with self._pending_lock:
            return self._pending

    def submit(self, batch, on_done):
        """Queue a batch; ``on_done(result_rows, bucket, error)`` fires
        on the worker thread. Returns False (nothing queued) once the
        replica is retired — the check shares the load-accounting lock,
        so a True return guarantees :meth:`wait_drained` sees the
        batch."""
        with self._pending_lock:
            if self._retired:
                return False
            self._pending += int(batch.shape[0])
        self._queue.put((batch, on_done))
        return True

    def retire(self, retired=True):
        """Mark the replica as leaving dispatch: subsequent
        :meth:`submit` calls are refused, so a drain that observed an
        empty queue cannot be invalidated by a late batch."""
        with self._pending_lock:
            self._retired = retired

    def swap(self, model):
        """Queue a drain-then-swap; returns an event set when done."""
        op = _Swap(model)
        with self._pending_lock:
            if self._retired:
                # leaving the pool anyway: promoting would only delay
                # the drain, and the queue may already be dead
                op.done.set()
                return op.done
            self._pending += self.SWAP_LOAD
        self._queue.put(op)
        return op.done

    def _work_loop(self):
        while not self._stop.is_set():
            try:
                item = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            if item is None:
                break
            if isinstance(item, _Swap):
                try:
                    self._bind(item.model)
                    self.info("replica %d promoted to %s v%d",
                              self.index, item.model.name,
                              item.model.version)
                finally:
                    with self._pending_lock:
                        self._pending -= self.SWAP_LOAD
                    item.done.set()
                continue
            batch, on_done = item
            try:
                result, bucket = self.infer(batch)
                error = None
            except Exception as e:  # scatter the failure, don't die
                result, bucket = None, 0
                error = e
                self.exception("replica %d batch failed", self.index)
            finally:
                with self._pending_lock:
                    self._pending -= int(batch.shape[0])
            self.batches_done += 1
            self.rows_done += int(batch.shape[0])
            on_done(result, bucket, error)

    def wait_drained(self, timeout=60.0):
        """Block until everything this replica accepted has been
        answered (load 0, queue empty). Callers must have removed the
        replica from dispatch first, or the drain never converges."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.load == 0 and self._queue.empty():
                return True
            time.sleep(0.005)
        return self.load == 0 and self._queue.empty()

    def stop(self):
        self._stop.set()
        self._queue.put(None)
        self._thread.join(timeout=10)
        # fail whatever was still queued: a stranded batch would leave
        # its clients blocked until their response timeout
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if isinstance(item, _Swap):
                with self._pending_lock:
                    self._pending -= self.SWAP_LOAD
                item.done.set()
            elif item is not None:
                batch, on_done = item
                on_done(None, 0, RuntimeError("replica stopped"))

    def stats(self):
        return {"index": self.index, "load": self.load,
                "batches": self.batches_done, "rows": self.rows_done,
                "model": self.model.name, "version": self.model.version}


class ReplicaPool(Logger):
    """Elastic replica set: least-loaded dispatch, atomic swap,
    grow/shrink under live traffic."""

    def __init__(self, model, n_replicas=1, max_batch_size=64,
                 warm=True):
        super(ReplicaPool, self).__init__()
        self.max_batch_size = int(max_batch_size)
        self._dispatch_lock = threading.Lock()
        self._rr = 0
        self._warm = bool(warm)
        self._next_index = 0
        self._model = model
        self.replicas = []
        for _ in range(max(1, int(n_replicas))):
            self.add_replica()

    @property
    def model(self):
        return self._model

    def pick(self):
        """Least-loaded replica; round-robin breaks ties so idle
        replicas alternate instead of replica 0 taking everything."""
        with self._dispatch_lock:
            self._rr += 1
            order = self.replicas[self._rr % len(self.replicas):] + \
                self.replicas[:self._rr % len(self.replicas)]
            return min(order, key=lambda r: r.load)

    def any_idle(self):
        """True when some replica has no queued/running work — the
        batcher's dispatch gate: while every replica is busy, a forming
        batch keeps growing instead of queueing up small fragments."""
        with self._dispatch_lock:
            replicas = list(self.replicas)
        return any(r.load == 0 for r in replicas)

    def submit(self, batch, on_done):
        # pick() releases the dispatch lock before the replica accepts
        # the batch, so the picked replica may retire (scale-down)
        # in between — it refuses atomically and the batch is simply
        # re-picked; by then the victim has left the dispatch list
        while not self.pick().submit(batch, on_done):
            pass

    # -- elasticity --------------------------------------------------------

    def size(self):
        with self._dispatch_lock:
            return len(self.replicas)

    def add_replica(self):
        """Grow the pool by one warm replica. The replica compiles and
        warms every bucket BEFORE it enters the dispatch list, so
        scale-up traffic never lands on a cold JIT cache — the warm-up
        cost lands in ``veles_phase_ms{phase="replica_warmup"}``, not
        in some unlucky client's tail."""
        with self._dispatch_lock:
            index = self._next_index
            self._next_index += 1
            current = self._model
        replica = Replica(current, index=index,
                          max_batch_size=self.max_batch_size,
                          warm=self._warm)
        while True:
            with self._dispatch_lock:
                if replica.model is self._model:
                    self.replicas.append(replica)
                    n = len(self.replicas)
                    break
                # swap() promoted the pool while this replica spent
                # seconds warming against the OLD version — joining
                # dispatch now would serve stale results (and poison
                # the cache under the new version's keys) forever
                current = self._model
            replica.swap(current).wait(120)
        self.info("pool grew to %d replica(s) (+ replica %d)", n, index)
        return replica

    def remove_replica(self, timeout=60.0):
        """Shrink by one: the victim leaves the dispatch list FIRST
        (new batches can no longer route to it), then drains whatever
        it already accepted, then stops — zero in-flight requests die.
        The last replica is never removed. Returns the drained replica
        or None when the pool is already at one."""
        with self._dispatch_lock:
            if len(self.replicas) <= 1:
                return None
            # take the least-loaded: the shortest drain, so capacity
            # recovers to the target fastest
            victim = min(self.replicas, key=lambda r: r.load)
            self.replicas.remove(victim)
            n = len(self.replicas)
        # refuse batches from a concurrent submit() that picked the
        # victim before it left the list — without this, a batch can
        # land AFTER the drain check and strand its futures forever
        victim.retire()
        if not victim.wait_drained(timeout):
            # drain stalled (wedged forward): put it back rather than
            # kill requests — the autoscaler retries next tick
            self.warning("replica %d did not drain in %.0fs; "
                         "returning it to dispatch", victim.index,
                         timeout)
            victim.retire(False)
            with self._dispatch_lock:
                self.replicas.append(victim)
            return None
        victim.stop()
        self.info("pool shrank to %d replica(s) (- replica %d)", n,
                  victim.index)
        return victim

    # -- swap / stats / lifecycle ------------------------------------------

    def swap(self, model, timeout=120.0):
        """Hot-swap every replica, one at a time: each drains its
        accepted work, promotes, re-warms, and rejoins dispatch while
        the others keep serving — capacity dips by 1/N, never to 0.
        A replica added concurrently (autoscaler) re-checks the pool
        model under the dispatch lock before joining, so setting
        ``_model`` and snapshotting the list in ONE critical section
        guarantees every replica is either in this snapshot (promoted
        here) or promotes itself before dispatch."""
        with self._dispatch_lock:
            self._model = model
            replicas = list(self.replicas)
        for replica in replicas:
            done = replica.swap(model)
            if not done.wait(timeout):
                raise TimeoutError(
                    "replica %d did not finish the swap in %.0fs" %
                    (replica.index, timeout))
        self.info("pool promoted to %s v%d", model.name, model.version)

    def stats(self):
        with self._dispatch_lock:
            replicas = list(self.replicas)
        return [r.stats() for r in replicas]

    def stop(self):
        with self._dispatch_lock:
            replicas = list(self.replicas)
            self.replicas = []
        for replica in replicas:
            replica.stop()
