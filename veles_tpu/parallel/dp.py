"""Data-parallel fused training.

The TPU lowering of the reference's master↔slave data parallelism
(SURVEY.md §2.4): instead of pickled per-unit deltas over ZeroMQ with a
compute-free master, the minibatch axis is sharded over the mesh's
``data`` axis and parameters are replicated; XLA's SPMD partitioner
inserts the gradient all-reduce (``lax.psum`` over ICI) inside the
compiled step. A single controller drives every chip — the "master" has
collapsed into the jit.

Optionally combines with tensor parallelism: pass ``param_shardings``
(see :mod:`veles_tpu.parallel.tp`) to shard layer weights over the
``model`` axis; XLA then inserts the activation collectives too.

**The minibatch fetch.** The resident data set is row-sharded, host
row ``g`` on shard ``g % N`` (``sharded_placer(interleave=True)``). A
sweep's index matrix is known on the host before the sweep starts, so
the host plans the fetch
(:func:`plan_fetch`): which of its rows every shard sends to every
other a step, and where each slot of a shard's ``mb / N`` finds its
sample among the rows received. In the step a shard gathers what it
owes from its own rows, ONE ``all_to_all`` over the data axis moves
them, and a second local gather puts them in slot order: no sum, and
the batch is bit for bit ``jnp.take(dataset, idx)``'s part, so the
serving order and the math are one device's. A pair of shards
exchanges at most :func:`exchange_capacity` rows a step, a number
taken from the shapes; a sweep that asks a pair for more (a strided
order may; a shuffled one would once in 1e8 steps) runs through the
program over a plain index matrix instead, in which XLA's SPMD
partitioner gathers the whole padded global minibatch on every shard
and all-reduces it.
"""

import collections

import jax
import jax.numpy as jnp
import numpy
from jax.sharding import PartitionSpec

from veles_tpu.loader import prefetch
from veles_tpu.parallel.mesh import (build_mesh, named_sharding,
                                     put_global)
from veles_tpu.telemetry import profiler
from veles_tpu.telemetry.registry import get_registry
from veles_tpu.train.step import FusedTrainer, device_scope


@jax.custom_vjp
def fenced(x):
    """``x``, unchanged; in the backward pass its cotangent goes
    through an ``optimization_barrier``: a boundary the compiler fuses
    nothing across. It separates; it computes nothing and overlaps
    nothing."""
    return x


fenced.defvjp(lambda x: (x, None),
              lambda _, g: (jax.lax.optimization_barrier(g),))


#: a sweep's planned fetch, the index operand of a segment's scan:
#: ``send[k, s, d]`` the local rows shard ``s`` sends shard ``d`` in
#: step ``k`` (``cap`` of them, the unused ones row 0); ``place[k, i]``
#: where slot ``i`` finds its sample among the ``N x cap`` rows its
#: shard receives, owner-major (-1: an empty slot, which reads zero)
FetchPlan = collections.namedtuple("FetchPlan", ("send", "place"))


def exchange_capacity(mb, n_shards):
    """Rows a pair of shards may exchange a step: six standard
    deviations above what a shuffled order asks of a pair (each of a
    shard's ``mb / N`` slots falls to an owner with probability
    ``1 / N``), rounded up to 8 rows, and never more than the slots a
    shard has. 64 rows at 512 over 4: twice the mean."""
    slots = mb // n_shards
    mean = slots / n_shards
    spread = (mean * (1 - 1 / n_shards)) ** 0.5
    return min(slots, -int(-(mean + 6 * spread) // 8) * 8)


def plan_fetch(idx_matrix, n_shards, cap):
    """``(plan, pair_rows)`` of a sweep's ``(n_batches, mb)`` matrix
    of sample ids (-1 padded) over a data set placed with
    ``interleave`` (:func:`~veles_tpu.loader.prefetch.interleaved_home`
    says where a sample lies): the :class:`FetchPlan` in numpy, or
    None where a pair of shards owes more than ``cap`` rows in some
    step; and the rows each ``[step, owner, destination]`` owes. Slot
    ``i`` belongs to shard ``i // (mb / N)``, and a pair's rows travel
    in slot order."""
    idx = numpy.asarray(idx_matrix, numpy.int32)
    n_batches, mb = idx.shape
    valid = idx >= 0
    owner, local = prefetch.interleaved_home(idx, n_shards)
    slot_shard = numpy.arange(mb) // (mb // n_shards)
    pair = ((numpy.arange(n_batches)[:, None] * n_shards + owner)
            * n_shards + slot_shard)[valid]
    pair_rows = numpy.bincount(pair, minlength=n_batches * n_shards ** 2)
    if pair_rows.max() > cap:
        plan = None
    else:
        # a slot's rank among its pair's slots of the same step
        order = numpy.argsort(pair, kind="stable")
        rank = numpy.empty_like(order)
        rank[order] = numpy.arange(order.size) - (
            numpy.cumsum(pair_rows) - pair_rows)[pair[order]]
        send = numpy.zeros((pair_rows.size, cap), numpy.int32)
        send[pair, rank] = local[valid]
        place = numpy.full(idx.shape, -1, numpy.int32)
        place[valid] = owner[valid] * cap + rank
        plan = FetchPlan(
            send.reshape(n_batches, n_shards, n_shards, cap), place)
    return plan, pair_rows.reshape(n_batches, n_shards, n_shards)


class DataParallelTrainer(FusedTrainer):
    """FusedTrainer whose compiled segments shard the batch over a mesh.

    ``mesh`` must contain the ``axis`` (default "data") axis; the
    minibatch size must divide by its size. Parameters/optimizer state
    are replicated unless ``param_shardings`` overrides per-layer specs.
    """

    @profiler.phased("trainer_build")
    def __init__(self, workflow, mesh=None, axis="data",
                 param_shardings=None, **kwargs):
        self.mesh = mesh if mesh is not None else build_mesh()
        self.axis = axis
        self._param_shardings = param_shardings
        n_shards = self.mesh.shape[axis]
        mb = workflow.loader.max_minibatch_size
        if mb % n_shards:
            # fail HERE with the constraint spelled out instead of an
            # opaque sharding error out of jit — this is the check an
            # elastic restart at a NEW world size hits first (ISSUE 13:
            # the re-formed mesh must still divide the minibatch, or
            # the deterministic re-partition of the index matrix
            # cannot keep every minibatch training exactly once)
            raise ValueError(
                "minibatch size %d does not divide over the %r mesh "
                "axis (%d shards); pick a minibatch the pod's every "
                "reachable world size divides, or a smaller mesh"
                % (mb, axis, n_shards))
        # set before super().__init__: _build() compiles the segments,
        # whose in_shardings read this spec
        self._data_spec = named_sharding(self.mesh, axis)
        registry = get_registry()
        self._exchange_rows = registry.gauge(
            "veles_input_exchange_rows",
            "Rows a shard lays out for the planned minibatch exchange "
            "a step (shards x capacity, its own share among them)",
            labels=("segment",))
        self._exchange_needed = registry.gauge(
            "veles_input_exchange_needed_rows",
            "Rows a shard's slots asked of OTHER shards a step, mean "
            "over the shards and steps of the last planned sweep",
            labels=("segment",))
        self._exchange_sweeps = registry.counter(
            "veles_input_exchange_sweeps_total",
            "Sweeps over the resident data set by how their "
            "minibatches were fetched: the planned exchange, or the "
            "partitioner's gather of the whole global minibatch",
            labels=("segment", "path"))
        super(DataParallelTrainer, self).__init__(workflow, **kwargs)
        if self.streaming:
            # out-of-core: shards flow through the prefetch staging
            # ring, placed per-device by _shard_placer — there is no
            # resident dataset to row-shard
            return
        # the loader uploaded the dataset committed to ONE device
        # (memory.py device_put). SHARD it over the data axis — a
        # replicated dataset multiplies HBM by mesh size and cannot fit
        # ImageNet-shaped fullbatch loaders (VERDICT r2 weak #5).
        # Sample g is local row g // N of shard g % N, so that a
        # sequential sweep asks every pair of shards for the same
        # number of rows; the fetch is planned on the host from the
        # sweep's sample ids (the module docstring), and serving order
        # (and therefore the math) is identical to a single device.
        # The sample dim is padded to divide the axis — no index
        # reaches a pad row.
        self._n_samples = self._data_args[0].shape[0]
        # stage through HOST memory, a chunk at a time: padding
        # on-device would hold a second full-size copy on the loader's
        # device — exactly the 2x HBM peak this sharding exists to
        # avoid. sharded_placer is the ONE pad-and-place implementation
        # (streamed shards use it per shard; here it places the whole
        # dataset once).
        place = prefetch.sharded_placer(self._data_spec, n_shards,
                                        interleave=True)
        with profiler.phase("dataset_shard", shards=n_shards, bytes=sum(
                a.nbytes for a in self._data_args)):
            self._data_args = tuple(place(a) for a in self._data_args)
            # the loader's Arrays still hold the FULL dataset committed
            # to one device (FusedTrainer.__init__ forced .devmem to
            # build _data_args) — release those buffers so that device
            # holds only its 1/N shard, not full + 1/N
            for arr in (self.loader.original_data,
                        self.loader.original_labels
                        if self.loss_kind == "softmax"
                        else self.loader.original_targets):
                arr.release_devmem()

    def _dataset_device_bytes(self, total_bytes):
        # row-sharded residency: each device holds 1/N of the dataset,
        # so the stream-vs-resident decision compares the SHARD size
        # against one device's budget
        return total_bytes / self.mesh.shape[self.axis]

    def _shard_placer(self):
        """Streamed shards land directly as addressable per-device
        shards of the data-axis ``NamedSharding`` — each device
        receives its row slice of the host shard straight from host
        memory (``put_global``: plain sharded ``device_put``
        single-process, ``make_array_from_callback`` multi-controller).
        No device ever sees the full shard, and there is no
        gather-then-scatter hop. The pad-and-place implementation is
        :func:`veles_tpu.loader.prefetch.sharded_placer` (local shard
        indices never reach the pad rows), routed through the measured
        reshard primitive (ISSUE 15)."""
        return prefetch.sharded_placer(self._data_spec,
                                       self.mesh.shape[self.axis])

    # -- the minibatch fetch (the module docstring) -------------------------

    def _dataset_rows(self, idx_matrix):
        return prefetch.interleaved_rows(
            idx_matrix, self._n_samples, self.mesh.shape[self.axis])

    def _index_operand(self, kind, idx_matrix):
        """The planned fetch of a sweep over the resident data set,
        where every pair of shards owes at most the capacity in every
        step; else, and for a streamed shard (whose row numbers are
        its own), the plain matrix of rows for the partitioner's
        gather. What the sweep's index matrix holds picks the program;
        the second one is built when a sweep first takes it."""
        n_shards = self.mesh.shape[self.axis]
        spec = named_sharding(self.mesh, None, self.axis)
        if self.streaming or n_shards == 1:  # nothing to exchange
            return put_global(numpy.asarray(idx_matrix), spec)
        idx = numpy.asarray(idx_matrix, numpy.int32)
        cap = exchange_capacity(idx.shape[1], n_shards)
        plan, pair_rows = plan_fetch(idx, n_shards, cap)
        self._exchange_sweeps.labels(
            segment=kind,
            path="partitioner" if plan is None else "planned").inc()
        if plan is None:
            return put_global(self._dataset_rows(idx), spec)
        self._exchange_rows.labels(segment=kind).set(n_shards * cap)
        self._exchange_needed.labels(segment=kind).set(
            (pair_rows.sum() - pair_rows.trace(axis1=1, axis2=2).sum())
            / pair_rows[..., 0].size)
        return FetchPlan(*(put_global(a, spec) for a in plan))

    def _keys_operand(self, keys):
        # host-built keys must be placed explicitly under
        # multi-controller SPMD (implicit device_put would reject the
        # cross-process sharding)
        if jax.process_count() == 1:
            return keys
        return put_global(keys, named_sharding(self.mesh))

    def _fetch(self, data_args, step):
        if not isinstance(step, FetchPlan):
            return super(DataParallelTrainer, self)._fetch(data_args, step)
        axis, n_shards = self.axis, self.mesh.shape[self.axis]

        def exchange(send, place, *sources):
            def fetch(rows):
                # (the plan's row numbers are in bounds: "clip" spares
                # the pass that fills what is out of them)
                owed = jnp.take(rows, send.reshape(-1), axis=0,
                                mode="clip")
                got = jax.lax.all_to_all(
                    owed.reshape((n_shards, -1) + rows.shape[1:]),
                    axis, 0, 0)
                return jnp.take(got.reshape(owed.shape), place, axis=0,
                                mode="clip")
            data, truth = (fetch(rows) for rows in sources)
            return data * (place >= 0).reshape(
                (-1,) + (1,) * (data.ndim - 1)).astype(data.dtype), truth

        with device_scope("in"):
            # every operand and result is split over the data axis
            # alone; a model axis stays the partitioner's
            rows = PartitionSpec(axis)
            data, truth = jax.shard_map(
                exchange, mesh=self.mesh, in_specs=rows, out_specs=rows,
                axis_names={axis})(step.send, step.place, *data_args)
        return data, truth, step.place >= 0

    def _params_spec(self):
        if self._param_shardings is not None:
            return self._param_shardings
        return named_sharding(self.mesh)  # replicated (prefix pytree)

    def _forward_range(self, params_list, x, key, train, lo, hi,
                       aux=None, valid=None, ctx=None):
        """The forward chain; in a train step over the whole chain the
        entry unit's output is :func:`fenced`. That unit's backward is
        a weights gradient and a bias sum and nothing more, and left
        alone the TPU compiler takes what made their operand (in
        AlexNet the LRN's backward) into both as a producer: it is
        computed twice, the second time inside a convolution fusion
        that it slows to a quarter (PR 34, ``PERF.md`` section 6; the
        module docstring of :mod:`~veles_tpu.parallel.gspmd`). A part
        of the chain (the offload engine's group walk hands in that
        part's parameters only) and a forward-only pass go straight
        through."""
        forward = super(DataParallelTrainer, self)._forward_range
        if not train or lo or hi != len(self.forwards) or hi < 2:
            return forward(params_list, x, key, train, lo, hi, aux=aux,
                           valid=valid, ctx=ctx)
        x = fenced(forward(params_list[:1], x, key, train, 0, 1, aux=aux,
                           valid=valid, ctx=ctx))
        return forward(params_list[1:], x, key, train, 1, hi, aux=aux,
                       valid=valid, ctx=ctx)

    def _compile_train(self, fn):
        repl = named_sharding(self.mesh)
        params_spec = self._params_spec()
        # dataset/truth are row-sharded args; the index operand comes
        # placed (_index_operand: its arrays split over the per-step
        # batch dim), a plan or a plain matrix, and the one jitted
        # function is a program for either
        data_spec = (self._data_spec, self._data_spec)
        # outputs: params, states, losses, metrics (+ grad norms when
        # the flight recorder's tracking is on) — everything after the
        # params stays replicated
        n_extra = 3 + (1 if self.track_grad_norms else 0)
        return jax.jit(
            fn,
            in_shardings=(data_spec, params_spec, repl, None, repl),
            out_shardings=(params_spec,) + (repl,) * n_extra,
            donate_argnums=(1, 2) if self.donate else ())

    def _compile_eval(self, fn):
        # out_shardings as a single spec: the eval returns 2 leaves
        # (losses, metrics) or 3 when confusion rides the scan
        return jax.jit(
            fn,
            in_shardings=((self._data_spec, self._data_spec),
                          self._params_spec(), None),
            out_shardings=named_sharding(self.mesh))

    @profiler.phased("params_place")
    def pull_params(self):
        """Re-place host-committed params onto the mesh per the declared
        shardings (a committed single-device array would otherwise clash
        with the jit's in_shardings) — through the measured reshard
        primitive (ISSUE 15), so an elastic restore at a NEW mesh shape
        shows its re-placement cost as ``veles_reshard_ms``."""
        from veles_tpu.parallel import reshard
        params, states = super(DataParallelTrainer, self).pull_params()
        spec = self._params_spec()
        if not isinstance(spec, (tuple, list)):
            spec = tuple(spec for _ in params)
        params = tuple(
            {k: reshard.reshard(v, spec[i][k]
                                if isinstance(spec[i], dict)
                                else spec[i])
             for k, v in layer.items()}
            for i, layer in enumerate(params))
        repl = named_sharding(self.mesh)
        states = jax.tree_util.tree_map(
            lambda v: reshard.reshard(v, repl), states)
        return params, states
