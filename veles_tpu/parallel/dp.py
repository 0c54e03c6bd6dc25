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
"""

import jax
import jax.numpy as jnp

from veles_tpu.parallel.mesh import (build_mesh, named_sharding,
                                     put_global)
from veles_tpu.telemetry import profiler
from veles_tpu.train.step import FusedTrainer


@jax.custom_vjp
def fenced(x):
    """``x``, unchanged; in the backward pass its cotangent goes
    through an ``optimization_barrier``: a boundary the compiler fuses
    nothing across. It separates; it computes nothing and overlaps
    nothing."""
    return x


fenced.defvjp(lambda x: (x, None),
              lambda _, g: (jax.lax.optimization_barrier(g),))


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
        super(DataParallelTrainer, self).__init__(workflow, **kwargs)
        if self.streaming:
            # out-of-core: shards flow through the prefetch staging
            # ring, placed per-device by _shard_placer — there is no
            # resident dataset to row-shard
            return
        # the loader uploaded the dataset committed to ONE device
        # (memory.py device_put). SHARD it over the data axis — a
        # replicated dataset multiplies HBM by mesh size and cannot fit
        # ImageNet-shaped fullbatch loaders (VERDICT r2 weak #5). The
        # index gather stays on GLOBAL sample ids, so XLA's SPMD
        # partitioner inserts the cross-shard gather collective over
        # ICI; serving order (and therefore the math) is identical to a
        # single device. The sample dim is padded to divide the axis —
        # indices never reach the pad rows.
        import numpy
        # stage through HOST memory: padding on-device would hold a
        # second full-size copy on the loader's device — exactly the
        # 2x HBM peak this sharding exists to avoid. _shard_placer is
        # the ONE pad-and-place implementation (streamed shards use it
        # per shard; here it places the whole dataset once).
        place = self._shard_placer()
        with profiler.phase("dataset_shard", shards=n_shards, bytes=sum(
                a.nbytes for a in self._data_args)):
            self._data_args = tuple(place(numpy.asarray(a))
                                    for a in self._data_args)
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
        from veles_tpu.loader import prefetch
        return prefetch.sharded_placer(self._data_spec,
                                       self.mesh.shape[self.axis])

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
        # dataset/truth are row-sharded args; the per-minibatch index
        # gather crosses shards via XLA's SPMD collectives
        data_spec = (self._data_spec, self._data_spec)
        # idx_matrix: (n_batches, mb) — shard the per-step batch dim
        idx_spec = named_sharding(self.mesh, None, self.axis)
        # outputs: params, states, losses, metrics (+ grad norms when
        # the flight recorder's tracking is on) — everything after the
        # params stays replicated
        n_extra = 3 + (1 if self.track_grad_norms else 0)
        jitted = jax.jit(
            fn,
            in_shardings=(data_spec, params_spec, repl, idx_spec, repl),
            out_shardings=(params_spec,) + (repl,) * n_extra,
            donate_argnums=(1, 2) if self.donate else ())
        if jax.process_count() == 1:
            return jitted

        def multihost_call(data_args, params, states, idx, keys):
            # host-built idx/keys must be placed explicitly under
            # multi-controller SPMD (implicit device_put would reject
            # the cross-process sharding)
            return jitted(data_args, params, states,
                          put_global(idx, idx_spec),
                          put_global(keys, repl))
        return multihost_call

    def _compile_eval(self, fn):
        repl = named_sharding(self.mesh)
        idx_spec = named_sharding(self.mesh, None, self.axis)
        # out_shardings as a single spec: the eval returns 2 leaves
        # (losses, metrics) or 3 when confusion rides the scan
        jitted = jax.jit(
            fn,
            in_shardings=((self._data_spec, self._data_spec),
                          self._params_spec(), idx_spec),
            out_shardings=repl)
        if jax.process_count() == 1:
            return jitted

        def multihost_call(data_args, params, idx):
            return jitted(data_args, params, put_global(idx, idx_spec))
        return multihost_call

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
