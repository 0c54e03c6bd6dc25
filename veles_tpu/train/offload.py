"""Out-of-core MODEL state: host-offloaded param/optimizer layer
groups streamed through a double-buffered staging ring (ISSUE 17).

PR 8 solved "dataset bigger than HBM" (:mod:`veles_tpu.loader.prefetch`
streams shards through a :class:`~veles_tpu.loader.prefetch.StagingRing`
with the loss bit-identical to the resident run); this module is the
same libhclooc overlap blueprint (PAPERS.md, arXiv:1808.05056) applied
to the OTHER big tenant of device memory — the parameters and optimizer
state themselves:

* :class:`OffloadPlan` partitions the forward chain into contiguous
  layer groups sized against the device budget
  (``VELES_DEVICE_BUDGET_MB`` via :func:`prefetch.device_budget_bytes`,
  the same budget logic ``plan_residency`` uses for the dataset;
  ``VELES_OFFLOAD_GROUP_MB`` overrides the per-group target directly).

* The MASTER copy of every group lives on host (``reshard``'s ``host``
  layout); per minibatch the engine walks the groups — forward through
  groups ``0..G-2`` saving boundary activations, then backward from the
  head group down, each group's forward REMATERIALIZED inside its
  ``jax.vjp`` so only one group's params + activations are ever
  device-resident.

* Transfers ride the generalized :class:`prefetch.StagingRing` driven
  by a :class:`prefetch.PrefetchPipeline`: group ``k+1`` uploads H2D
  while group ``k`` computes, and a writeback thread retires updated
  group ``k-1`` D2H into the host masters — steady-state wall time is
  ``max(compute, transfer)``, not their sum. ``VELES_OFFLOAD_DEPTH=0``
  reproduces the fully synchronous path (every transfer inline on the
  step thread) — the bench's "sync offload" leg.

Determinism: the grouped walk computes bit-identical gradients to the
fused joint ``value_and_grad`` — the chain rule across a group
boundary IS what the joint backward does internally, dropout keys fold
by ABSOLUTE layer index, and the host⇄device roundtrip through numpy
preserves bits. ``tests/test_offload.py`` pins the loss curve against
the in-core run; ``scripts/offload_bench.py`` + the perf gate pin the
overlap.

Telemetry (docs/OBSERVABILITY.md): ``veles_offload_h2d_ms`` /
``veles_offload_d2h_ms`` / ``veles_offload_wait_ms`` histograms,
``veles_offload_compute_overlap_fraction`` gauge, ``offload:*`` trace
spans, the ``offload_plan`` startup phase, per-group
``offload:h2d/g<k>`` / ``offload:d2h/g<k>`` cost-book rows (achieved
GB/s in ``/profile.json``), and every H2D leaf lands in
``veles_reshard_ms{src="host"}`` via :func:`reshard.host_placer`.

``VELES_OFFLOAD_THROTTLE_MS`` injects a per-transfer sleep — the
slow-interconnect simulation ``scripts/offload_bench.py`` and the perf
gate's overlap probe use, mirroring ``VELES_ETL_THROTTLE_MS``.
"""

import queue
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.envknob import env_knob
from veles_tpu.loader import prefetch
from veles_tpu.logger import Logger
from veles_tpu.telemetry import profiler, tracing
from veles_tpu.train.step import device_scope, unit_tag

#: live engines (weak): conftest session teardown closes any a crashed
#: test left running (same leak class as prefetch.shutdown_all)
_live_lock = threading.Lock()
_live = weakref.WeakSet()


def offload_depth():
    """``VELES_OFFLOAD_DEPTH`` staged groups ahead (default 2 =
    double-buffered; 0 = fully synchronous transfers)."""
    return max(0, env_knob("VELES_OFFLOAD_DEPTH", 2, parse=int,
                           on_error="default"))


def offload_workers():
    """``VELES_OFFLOAD_WORKERS`` H2D upload threads (default 2: the
    forward and backward phases of adjacent groups upload
    concurrently)."""
    return max(1, env_knob("VELES_OFFLOAD_WORKERS", 2, parse=int,
                           on_error="default"))


def transfer_throttle_s():
    """Injected per-transfer sleep (``VELES_OFFLOAD_THROTTLE_MS``) —
    the slow-interconnect simulation for benches/tests; 0 in
    production."""
    return max(0.0, env_knob("VELES_OFFLOAD_THROTTLE_MS", 0.0,
                             parse=float, on_error="default")) / 1e3


def group_budget_bytes(device=None, depth=None):
    """Target bytes per offloaded layer group.

    ``VELES_OFFLOAD_GROUP_MB`` wins when set; else the device budget
    (:func:`prefetch.device_budget_bytes`) divided by the ring's
    ``depth + 2`` resident groups; else 256 MB (unknown budget)."""
    mb = env_knob("VELES_OFFLOAD_GROUP_MB", parse=float,
                  on_error="default")
    if mb is not None and mb > 0:
        return mb * 1e6
    depth = offload_depth() if depth is None else depth
    budget = prefetch.device_budget_bytes(device)
    if budget:
        return budget / (max(1, depth) + 2)
    return 256e6


def plan_offload(model_bytes, device=None, force=None):
    """``"offloaded"`` or ``"resident"`` for model state of
    ``model_bytes`` (params + estimated optimizer state).

    ``force`` (or ``VELES_OFFLOAD``: ``1``/``force``/``on`` offload
    always, ``0``/``off``/``no`` never; anything else ignored)
    overrides the budget comparison — same contract as
    :func:`prefetch.plan_residency`."""
    if force is None:
        env = env_knob("VELES_OFFLOAD")
        if env in ("1", "force", "on", "yes", "true"):
            force = True
        elif env in ("0", "off", "no", "false"):
            force = False
    if force is not None:
        return "offloaded" if force else "resident"
    budget = prefetch.device_budget_bytes(device)
    if budget is not None and model_bytes > budget:
        return "offloaded"
    return "resident"


#: optimizer-state bytes per param byte, by solver (planning estimate:
#: sgd carries velocity, adadelta/adam carry two accumulators)
_STATE_FACTORS = {"sgd": 1.0, "adagrad": 1.0, "adadelta": 2.0,
                  "adam": 2.0}


def model_layer_bytes(forwards, solvers):
    """Per-layer host-master bytes (params + estimated opt state)."""
    out = []
    for fwd, solver in zip(forwards, solvers):
        nbytes = sum(arr.nbytes for arr in fwd.param_arrays().values())
        if nbytes and solver is not None:
            factor = _STATE_FACTORS.get(getattr(solver, "name", None),
                                        1.0)
            nbytes = int(nbytes * (1.0 + factor))
        out.append(nbytes)
    return out


def _registry():
    from veles_tpu.telemetry.registry import get_registry
    return get_registry()


def h2d_histogram():
    return _registry().histogram(
        "veles_offload_h2d_ms",
        "Host->device upload time per offloaded layer group")


def d2h_histogram():
    return _registry().histogram(
        "veles_offload_d2h_ms",
        "Device->host writeback time per offloaded layer group")


def wait_histogram():
    return _registry().histogram(
        "veles_offload_wait_ms",
        "Step-thread wait for the next staged layer group")


def overlap_gauge():
    return _registry().gauge(
        "veles_offload_compute_overlap_fraction",
        "1 - transfer wait / wall of the last offloaded segment",
        labels=("phase",))


class OffloadPlan(object):
    """Contiguous layer groups ``[(lo, hi)]`` packed greedily so each
    group's host-master bytes stay under the per-group budget (a group
    always holds at least one layer — a single layer larger than the
    budget becomes its own group)."""

    def __init__(self, groups, group_bytes):
        self.groups = list(groups)
        self.group_bytes = list(group_bytes)

    @property
    def n_groups(self):
        return len(self.groups)

    @property
    def total_bytes(self):
        return sum(self.group_bytes)

    @classmethod
    def build(cls, layer_bytes, budget):
        groups, sizes = [], []
        lo, acc = 0, 0
        for i, nbytes in enumerate(layer_bytes):
            if i > lo and acc + nbytes > budget:
                groups.append((lo, i))
                sizes.append(acc)
                lo, acc = i, 0
            acc += nbytes
        groups.append((lo, len(layer_bytes)))
        sizes.append(acc)
        return cls(groups, sizes)


class OffloadEngine(Logger):
    """Drives one trainer's offloaded execution: host masters, the
    per-group jit programs, and the transfer machinery.

    The engine is stateless between segments (masters are the
    ``(params, states)`` pytrees the caller threads through, exactly
    like the in-core scan carry) — only the jit caches, the staging
    ring and the metric handles persist."""

    def __init__(self, trainer, plan, depth=None, workers=None):
        super(OffloadEngine, self).__init__()
        self.trainer = trainer
        self.plan = plan
        self.depth = offload_depth() if depth is None else max(0, depth)
        self.workers = (offload_workers() if workers is None
                        else max(1, workers))
        #: cumulative step-thread transfer wait (uploads + any inline
        #: writeback); the runner/benches read deltas of this
        self.wait_s = 0.0
        device = getattr(trainer.loader.original_data, "device", None)
        from veles_tpu.parallel import reshard
        self._gather_to_host = reshard.gather_to_host
        self._ring = prefetch.StagingRing(
            max(1, self.depth) + 2, reshard.host_placer(device))
        self._h2d = h2d_histogram()
        self._d2h = d2h_histogram()
        self._wait_hist = wait_histogram()
        self._overlap = overlap_gauge()
        self._book = profiler.get_cost_book()
        for g, nbytes in enumerate(plan.group_bytes):
            # transfer rows in the roofline table: bytes + observed ms
            # give achieved GB/s per group in /profile.json (flops stay
            # 0 — these ops move data, they don't compute)
            self._book.note_cost("offload:h2d/g%d" % g, 0.0,
                                 float(nbytes))
            self._book.note_cost("offload:d2h/g%d" % g, 0.0,
                                 float(nbytes))
        self._jit_gather = jax.jit(trainer._gather)
        self._jits = {}
        self._active_pipe = None
        self._active_stop = None
        with _live_lock:
            _live.add(self)

    # -- per-group jit programs ---------------------------------------------

    def _jit(self, kind, g):
        fn = self._jits.get((kind, g))
        if fn is None:
            lo, hi = self.plan.groups[g]
            build = getattr(self, "_build_" + kind)
            fn = self._jits[(kind, g)] = jax.jit(build(lo, hi))
        return fn

    def _build_fwd_train(self, lo, hi):
        trainer = self.trainer

        def fwd_train(params_g, x, key):
            return trainer._forward_range(params_g, x, key, True, lo, hi)
        return fwd_train

    def _build_fwd_eval(self, lo, hi):
        trainer = self.trainer

        def fwd_eval(params_g, x):
            return trainer._forward_range(params_g, x, None, False, lo,
                                          hi)
        return fwd_eval

    def _apply_group_updates(self, lo, hi, params_g, grads_g, opt_g):
        trainer = self.trainer
        new_params, new_states = [], []
        for j, i in enumerate(range(lo, hi)):
            if trainer.solvers[i] is None or not params_g[j]:
                new_params.append(params_g[j])
                new_states.append(opt_g[j])
                continue
            with device_scope("update", unit_tag(i, trainer.forwards[i])):
                p, s = trainer.solvers[i].update(
                    params_g[j], grads_g[j], opt_g[j], trainer.hypers[i])
            new_params.append(p)
            new_states.append(s)
        gsq = None
        if trainer.track_grad_norms:
            gsq = jnp.asarray(0.0, jnp.float32)
            for g in jax.tree_util.tree_leaves(grads_g):
                gsq = gsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
        return tuple(new_params), tuple(new_states), gsq

    def _build_bwd_head(self, lo, hi):
        """Head group: loss + joint grads over (group params, boundary
        activation); the boundary cotangent seeds the upstream groups'
        vjp chain — exactly the contribution the fused backward passes
        through the same point."""
        trainer = self.trainer
        track = trainer.track_grad_norms

        def bwd_head(params_g, opt_g, x_in, truth, idx, key):
            valid = idx >= 0

            def loss_fn(plist, x):
                aux = []
                out = trainer._forward_range(plist, x, key, True, lo,
                                             hi, aux=aux, valid=valid)
                grad_loss, report, metric = trainer._loss_and_metrics(
                    out, truth, valid)
                for term in aux:
                    grad_loss = grad_loss + term
                return grad_loss, (report, metric)

            (_, (loss, metric)), (grads, cot) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params_g, x_in)
            new_p, new_s, gsq = self._apply_group_updates(
                lo, hi, params_g, grads, opt_g)
            if track:
                return new_p, new_s, loss, metric, cot, gsq
            return new_p, new_s, loss, metric, cot
        return bwd_head

    def _build_bwd(self, lo, hi):
        """Inner group: rematerialize the group's forward from the
        saved boundary activation inside ``jax.vjp``, pull the
        downstream cotangent (plus 1.0 for the group's own aux-loss
        terms) back through it, and apply the per-layer solver
        updates."""
        trainer = self.trainer
        track = trainer.track_grad_norms

        def bwd(params_g, opt_g, x_in, cot, idx, key):
            valid = idx >= 0

            def f(plist, x):
                aux = []
                out = trainer._forward_range(plist, x, key, True, lo,
                                             hi, aux=aux, valid=valid)
                aux_sum = jnp.asarray(0.0, jnp.float32)
                for term in aux:
                    aux_sum = aux_sum + term
                return out, aux_sum

            _, vjp_fn = jax.vjp(f, params_g, x_in)
            grads, cot_in = vjp_fn((cot, jnp.asarray(1.0, jnp.float32)))
            new_p, new_s, gsq = self._apply_group_updates(
                lo, hi, params_g, grads, opt_g)
            if track:
                return new_p, new_s, cot_in, gsq
            return new_p, new_s, cot_in
        return bwd

    def _build_eval_head(self, lo, hi):
        trainer = self.trainer
        wants_conf = trainer.wants_confusion

        def eval_head(params_g, x_in, truth, idx):
            valid = idx >= 0
            out = trainer._forward_range(params_g, x_in, None, False,
                                         lo, hi)
            _, report, metric = trainer._loss_and_metrics(out, truth,
                                                          valid)
            if wants_conf:
                return report, metric, trainer._batch_confusion(
                    out, truth, valid)
            return report, metric
        return eval_head

    def _build_conf_head(self, lo, hi):
        trainer = self.trainer

        def conf_head(params_g, x_in, truth, idx):
            valid = idx >= 0
            out = trainer._forward_range(params_g, x_in, None, False,
                                         lo, hi)
            return trainer._batch_confusion(out, truth, valid)
        return conf_head

    # -- transfer machinery -------------------------------------------------

    def _upload_pipeline(self, schedule, masters_p, masters_s, cond,
                         versions, abort, name, readonly=False):
        """The H2D side: a PrefetchPipeline over the static transfer
        schedule. ``produce(i)`` waits (version counters) until the
        group's host master carries every writeback the task's
        minibatch depends on, then stages it through the ring.
        ``readonly`` (eval: masters never change) skips the wait."""
        ring = self._ring
        throttle = transfer_throttle_s()
        groups = self.plan.groups

        def produce(i):
            kind, b, g = schedule[i]
            lo, hi = groups[g]
            with cond:
                while not readonly and versions[g] < b and not abort[0]:
                    cond.wait(0.1)
                if abort[0]:
                    raise RuntimeError(
                        "offload upload aborted at task %d" % i)
                p_host = tuple(masters_p[lo:hi])
                s_host = (tuple(masters_s[lo:hi]) if kind == "B"
                          else None)
            t0 = time.perf_counter()
            if throttle:
                time.sleep(throttle)
            tree = (p_host,) if s_host is None else (p_host, s_host)
            placed = ring.place(tree)
            elapsed = time.perf_counter() - t0
            self._h2d.observe(elapsed * 1e3)
            self._book.observe_ms("offload:h2d/g%d" % g, elapsed)
            tracing.add_complete("offload:h2d", t0, elapsed, group=g,
                                 batch=b, phase=kind)
            return placed

        return prefetch.PrefetchPipeline(
            produce, len(schedule), depth=self.depth,
            workers=self.workers, name=name,
            wait_hist=self._wait_hist, fill_phase=None)

    def _retire_group(self, b, g, dev_tree, masters_p, masters_s, cond,
                      versions):
        """D2H: gather the updated group back into the host masters and
        bump its version (unblocking the next minibatch's uploads)."""
        lo, hi = self.plan.groups[g]
        throttle = transfer_throttle_s()
        t0 = time.perf_counter()
        if throttle:
            time.sleep(throttle)
        host_p, host_s = jax.tree_util.tree_map(self._gather_to_host,
                                                dev_tree)
        elapsed = time.perf_counter() - t0
        self._d2h.observe(elapsed * 1e3)
        self._book.observe_ms("offload:d2h/g%d" % g, elapsed)
        tracing.add_complete("offload:d2h", t0, elapsed, group=g,
                             batch=b)
        with cond:
            for j, i in enumerate(range(lo, hi)):
                masters_p[i] = host_p[j]
                masters_s[i] = host_s[j]
            versions[g] = b + 1
            cond.notify_all()
        return elapsed

    # -- segment drivers ----------------------------------------------------

    def train_segment(self, params, states, idx_matrix, keys):
        """One training sweep, group-walked. Returns ``(params, states,
        losses, metrics, norms_or_None)`` with host-master pytrees."""
        trainer = self.trainer
        groups = self.plan.groups
        n_groups = len(groups)
        track = trainer.track_grad_norms
        idx_np = numpy.asarray(idx_matrix, numpy.int32)
        n_batches = idx_np.shape[0]
        masters_p = list(params)
        masters_s = list(states)
        cond = threading.Condition()
        versions = {g: 0 for g in range(n_groups)}
        abort = [False]
        schedule = []
        for b in range(n_batches):
            for g in range(n_groups - 1):
                schedule.append(("F", b, g))
            for g in range(n_groups - 1, -1, -1):
                schedule.append(("B", b, g))
        pipe = self._upload_pipeline(schedule, masters_p, masters_s,
                                     cond, versions, abort,
                                     "offload-train")
        wb_queue = queue.Queue() if self.depth else None
        wb_error = []
        inline_wb_s = [0.0]

        def submit(b, g, dev_tree):
            if wb_queue is None:
                inline_wb_s[0] += self._retire_group(
                    b, g, dev_tree, masters_p, masters_s, cond,
                    versions)
            else:
                wb_queue.put((b, g, dev_tree))

        def wb_loop():
            while True:
                item = wb_queue.get()
                if item is None:
                    return
                try:
                    self._retire_group(*item, masters_p=masters_p,
                                       masters_s=masters_s, cond=cond,
                                       versions=versions)
                except BaseException as e:
                    wb_error.append(e)
                    with cond:
                        abort[0] = True
                        cond.notify_all()
                    return

        wb_thread = None
        data_args = trainer._data_args
        losses, metrics, norms = [], [], []
        start = time.perf_counter()
        self._active_pipe = pipe
        self._active_stop = lambda: (wb_queue.put(None)
                                     if wb_queue is not None else None)
        try:
            self._ring.reopen()
            pipe.start()
            if wb_queue is not None:
                wb_thread = threading.Thread(
                    target=wb_loop, daemon=True,
                    name="veles-offload-writeback")
                wb_thread.start()
            for b in range(n_batches):
                if wb_error:
                    raise wb_error[0]
                idx_dev = jnp.asarray(idx_np[b])
                x, truth = self._jit_gather(data_args, idx_dev)
                key = keys[b]
                x_bound = [None] * n_groups
                x_bound[0] = x
                for g in range(n_groups - 1):
                    (placed_p,), _ = pipe.get()
                    x_bound[g + 1] = self._jit("fwd_train", g)(
                        placed_p, x_bound[g], key)
                cot = None
                gsq_parts = [None] * n_groups
                for g in range(n_groups - 1, -1, -1):
                    placed_p, placed_s = pipe.get()[0]
                    if g == n_groups - 1:
                        out = self._jit("bwd_head", g)(
                            placed_p, placed_s, x_bound[g], truth,
                            idx_dev, key)
                        if track:
                            (new_p, new_s, loss, metric, cot,
                             gsq_parts[g]) = out
                        else:
                            new_p, new_s, loss, metric, cot = out
                    else:
                        out = self._jit("bwd", g)(
                            placed_p, placed_s, x_bound[g], cot,
                            idx_dev, key)
                        if track:
                            new_p, new_s, cot, gsq_parts[g] = out
                        else:
                            new_p, new_s, cot = out
                    submit(b, g, (new_p, new_s))
                losses.append(loss)
                metrics.append(metric)
                if track:
                    gsq = gsq_parts[0]
                    for part in gsq_parts[1:]:
                        gsq = gsq + part
                    norms.append(jnp.sqrt(gsq))
            if wb_queue is not None:
                wb_queue.put(None)
                wb_thread.join()
                wb_thread = None
                if wb_error:
                    raise wb_error[0]
        finally:
            with cond:
                abort[0] = True
                cond.notify_all()
            pipe.close()
            if wb_thread is not None:
                wb_queue.put(None)
                wb_thread.join(10.0)
            self._active_pipe = None
            self._active_stop = None
            seg_wait = pipe.wait_s + inline_wb_s[0]
            self.wait_s += seg_wait
            self._publish_overlap("train", seg_wait, start)
        return (tuple(masters_p), tuple(masters_s), jnp.stack(losses),
                jnp.stack(metrics),
                jnp.stack(norms) if track else None)

    def _publish_overlap(self, phase, seg_wait, start):
        wall = time.perf_counter() - start
        if wall > 0:
            fraction = max(0.0, 1.0 - seg_wait / wall)
            self._overlap.labels(phase=phase).set(fraction)

    def _eval_walk(self, params, idx_matrix, head_kind):
        """Shared eval-shaped driver: forward through every group,
        ``head_kind`` ("eval_head"/"conf_head") finishing the chain."""
        trainer = self.trainer
        groups = self.plan.groups
        n_groups = len(groups)
        idx_np = numpy.asarray(idx_matrix, numpy.int32)
        n_batches = idx_np.shape[0]
        masters_p = list(params)
        cond = threading.Condition()
        versions = {g: 0 for g in range(n_groups)}
        abort = [False]
        schedule = [("F", b, g) for b in range(n_batches)
                    for g in range(n_groups)]
        pipe = self._upload_pipeline(schedule, masters_p, [], cond,
                                     versions, abort, "offload-eval",
                                     readonly=True)
        data_args = trainer._data_args
        outs = []
        start = time.perf_counter()
        self._active_pipe = pipe
        try:
            self._ring.reopen()
            pipe.start()
            for b in range(n_batches):
                idx_dev = jnp.asarray(idx_np[b])
                x, truth = self._jit_gather(data_args, idx_dev)
                for g in range(n_groups - 1):
                    (placed_p,), _ = pipe.get()
                    x = self._jit("fwd_eval", g)(placed_p, x)
                (placed_p,), _ = pipe.get()
                outs.append(self._jit(head_kind, n_groups - 1)(
                    placed_p, x, truth, idx_dev))
        finally:
            with cond:
                abort[0] = True
                cond.notify_all()
            pipe.close()
            self._active_pipe = None
            self.wait_s += pipe.wait_s
            self._publish_overlap("eval", pipe.wait_s, start)
        return outs

    def eval_segment(self, params, idx_matrix):
        outs = self._eval_walk(params, idx_matrix, "eval_head")
        losses = jnp.stack([o[0] for o in outs])
        metrics = jnp.stack([o[1] for o in outs])
        if len(outs[0]) == 3:
            conf = outs[0][2]
            for o in outs[1:]:
                conf = conf + o[2]
            return losses, metrics, conf
        return losses, metrics

    def confusion_segment(self, params, idx_matrix):
        outs = self._eval_walk(params, idx_matrix, "conf_head")
        conf = outs[0]
        for o in outs[1:]:
            conf = conf + o
        return conf

    # -- lifecycle -----------------------------------------------------------

    def close(self):
        """Join any live upload pipeline / writeback thread and drop
        staged groups. Idempotent — the segment drivers already tear
        down per segment; this is the crash/Ctrl-C backstop
        ``FusedTrainer.shutdown()`` (and the conftest session teardown)
        call."""
        pipe = self._active_pipe
        if pipe is not None:
            pipe.close()
            self._active_pipe = None
        stop = self._active_stop
        if stop is not None:
            try:
                stop()
            except Exception:
                pass
            self._active_stop = None
        self._ring.clear()


def shutdown_all():
    """Close every live engine (conftest session teardown: offload
    threads must not outlive pytest into interpreter shutdown)."""
    with _live_lock:
        engines = list(_live)
    for engine in engines:
        engine.close()
