"""GSPMD pod-scale training path (ISSUE 15, ROADMAP item 1).

One launcher, one ``jit``: the whole train step — forward, backward,
optimizer — compiles with in/out ``NamedSharding``s over a named
``Mesh(('batch', 'model'))``, so the reference's master↔slave gradient
merge lowers to a compiler-inserted ``lax.psum`` over ICI (the
PAPER.md target) instead of the host-mediated pickle/shm exchange.
The pieces already existed as fragments; this module unifies them
into sharding *specs* consumed by the one jitted step:

* :mod:`veles_tpu.parallel.dp` supplies the batch-axis placement
  (dataset row-sharded and interleaved, a step's minibatch fetched by
  a host-planned all-to-all of the rows each shard lacks, the
  prefetch staging ring landing streamed shards directly as
  addressable per-device shards of the global batch);
* :mod:`veles_tpu.parallel.tp` supplies the model-axis rules
  (:func:`~veles_tpu.parallel.tp.tp_param_shardings`'s Megatron
  column/row alternation for dense AND conv);
* :mod:`veles_tpu.parallel.reshard` supplies the measured
  layout-change primitive for checkpoint restore at a different mesh
  shape and for train→serve moves.

Axis naming: ``batch`` × ``model`` (the ISSUE 15 convention for the
launcher-SPMD tier; the coordinator remains the cross-pod /
heterogeneous tier and the older ``data`` axis name keeps working for
direct :class:`~veles_tpu.parallel.dp.DataParallelTrainer` users).

**Parity with the other paths, as far as it holds.** On the CPU with
fixed seeds (tests/test_gspmd.py):

* the weight trajectory is bit-identical to the single-device fused
  step and to the coordinator path — on every backend this repo
  meets, the partitioner's gradient psum merges shard partials into
  exactly the floats the single-device contraction produces (weights
  compared bit-for-bit);
* the *reported* loss/metric scalars agree to a few float32 ULP, not
  bit for bit. A reduction over a batch-sharded per-sample vector
  lowers to local-sum + psum, so
  :meth:`GSPMDTrainer._loss_and_metrics` gathers the per-sample values
  to a REPLICATED layout (one all-gather of ``mb`` rows — noise next
  to the step) before any cross-sample reduction. That keeps the
  partitioning out of the sum, but each compiled program still picks
  its own order for it: against the coordinator path the curve comes
  out bit-identical on this jaxlib, against the one-device fused
  program one validation loss reads 2.3427908 for 2.3427906. The
  tests and ``scripts/perf_gate.py`` hold the curve to rtol 4e-7.

**The schedule, and what the fence in the backward pass is for.**
What the TPU compiler returns is a *scheduled* module
(``is_scheduled=true`` in ``compiled.as_text()``): inside a computation
the order of the instructions is the order one core issues them in, so
the text says where a collective sits, what the compiler fused with
what, and what it sank where. Left to itself it combines every
gradient into two synchronous tuple all-reduces at the very tail of
the step. PR 34 made the large ones asynchronous pairs of their own
with compute between their halves (three compiler options, on a v5e)
and measured that this hides next to nothing: there the all-reduce is
work of the core (it streams the float32 partial products through HBM
and adds them, at ~58 GB/s a chip), so a ``done`` half still holds the
core for most of the exchange and what runs "beside" it runs slower by
as much. The options went again; the exchange is the compiler's own
and unchanged (float32 partial products, summed in float32, the sum
rounded once to the compute dtype), and it can be shortened (fewer
bytes, or an exchange the core does not run), not hidden. What stayed
is :func:`~veles_tpu.parallel.dp.fenced`: an ``optimization_barrier``
in the backward pass, put behind the entry unit by
``DataParallelTrainer._forward_range`` on every platform, that changes
what the compiler FUSES and overlaps nothing (the weights come out bit
for bit on a CPU mesh). It keeps the backward of what follows the entry
unit (in AlexNet the LRN) from being taken, as a producer, into both of
the entry conv's own backward fusions and computed twice.
``scripts/partitioned_schedule.py`` compiles a cell's train segment
for a described topology and prints its collectives, and with
``--backward`` its backward pass, in schedule order at no chip cost;
``scripts/step_timeline.py`` prints one traced step's operations in
time order on the chip.

Telemetry: ``veles_gspmd_step_ms{phase}`` (compute + compiler-inserted
exchange, per class sweep), ``veles_reshard_ms{src,dst}`` via
:mod:`~veles_tpu.parallel.reshard`, and the per-step collective-bytes
estimate harvested from the compiled step into the PR 7 CostBook
(``veles_op_collective_bytes{op="gspmd_train_segment"}``).
"""

import time

import jax

from veles_tpu.parallel.dp import DataParallelTrainer
from veles_tpu.parallel.mesh import build_mesh, named_sharding
from veles_tpu.telemetry import profiler

#: the launcher-SPMD tier's axis names (ISSUE 15)
BATCH_AXIS = "batch"
MODEL_AXIS = "model"


def gspmd_mesh(batch=-1, model=1, devices=None):
    """The named ``batch`` × ``model`` mesh. ``batch=-1`` infers the
    batch extent from the device count (all devices on the batch axis
    when ``model=1``). The model axis exists even at size 1, so the
    same specs compile whether tensor parallelism is on or off."""
    return build_mesh({BATCH_AXIS: batch, MODEL_AXIS: model},
                      devices=devices)


def parse_mesh_spec(spec, devices=None):
    """``--gspmd`` argument -> mesh.

    Accepts ``"auto"``/``""`` (all devices on ``batch``),
    ``"batch=4,model=2"`` (any order, ``-1`` infers), or the shorthand
    ``"4x2"`` (batch x model)."""
    spec = (spec or "auto").strip().lower()
    if spec in ("auto", "1", "true", "on"):
        return gspmd_mesh(devices=devices)
    axes = {BATCH_AXIS: -1, MODEL_AXIS: 1}
    if "=" in spec:
        for part in spec.split(","):
            name, _, value = part.partition("=")
            name = name.strip()
            if name not in axes:
                raise ValueError(
                    "unknown GSPMD mesh axis %r (have batch, model)"
                    % name)
            axes[name] = int(value)
    else:
        sizes = spec.split("x")
        axes[BATCH_AXIS] = int(sizes[0])
        if len(sizes) > 1:
            axes[MODEL_AXIS] = int(sizes[1])
        if len(sizes) > 2:
            raise ValueError("GSPMD mesh shorthand is BATCHxMODEL, "
                             "got %r" % spec)
    return gspmd_mesh(batch=axes[BATCH_AXIS], model=axes[MODEL_AXIS],
                      devices=devices)


def gspmd_param_specs(forwards, mesh, model_axis=MODEL_AXIS):
    """The unified parameter-sharding plan: tp.py's column/row rules
    over the ``model`` axis when it is wider than 1, else fully
    replicated (pure data parallelism — the gradient psum is the only
    parameter collective)."""
    if model_axis in mesh.shape and mesh.shape[model_axis] > 1:
        from veles_tpu.parallel.tp import tp_param_shardings
        return tp_param_shardings(forwards, mesh, axis=model_axis)
    return None  # DataParallelTrainer default: replicated prefix tree


class GSPMDTrainer(DataParallelTrainer):
    """The single-launcher SPMD training path over ``batch``×``model``.

    ``mesh=None`` builds the default mesh (all devices on ``batch``);
    ``shard_model=True`` (default) consumes tp.py's model-axis specs
    whenever the mesh's model axis is wider than 1 — pass
    ``param_shardings`` to override per-layer, or ``shard_model=False``
    to keep parameters replicated on a wide model axis.

    Everything else — dataset row-sharding with release of the
    single-device copy, streamed shards placed as addressable
    per-device shards through the staging ring, the minibatch
    divisibility check an elastic restart hits first — is inherited
    from :class:`~veles_tpu.parallel.dp.DataParallelTrainer`, now
    driven through the ``batch`` axis.
    """

    _op_prefix = "gspmd_"

    @profiler.phased("trainer_build")
    def __init__(self, workflow, mesh=None, batch_axis=BATCH_AXIS,
                 model_axis=MODEL_AXIS, param_shardings=None,
                 shard_model=True, **kwargs):
        if mesh is None:
            mesh = gspmd_mesh()
        if batch_axis not in mesh.shape:
            raise ValueError(
                "GSPMD mesh %r has no %r axis (gspmd_mesh/"
                "parse_mesh_spec build the right one)"
                % (dict(mesh.shape), batch_axis))
        self.model_axis = model_axis
        if param_shardings is None and shard_model:
            param_shardings = gspmd_param_specs(
                workflow.forwards, mesh, model_axis=model_axis)
        from veles_tpu.telemetry.registry import get_registry
        self._gspmd_ms = get_registry().histogram(
            "veles_gspmd_step_ms",
            "GSPMD class sweep: compute + compiler-inserted exchange, "
            "blocked on results", labels=("phase",))
        super(GSPMDTrainer, self).__init__(
            workflow, mesh=mesh, axis=batch_axis,
            param_shardings=param_shardings, **kwargs)

    # -- shard-invariant loss reductions ------------------------------------

    def _loss_and_metrics(self, out, labels_or_targets, valid):
        """Gather per-sample values to the replicated layout before any
        cross-sample reduction (see the module docstring): the loss and
        metric scalars then reduce over one replicated vector, whatever
        the mesh, and the reported curve stays within a few float32 ULP
        of the single-device and coordinator paths'. The
        gradient seed is computed from the same replicated logits; its
        transpose reshards the cotangent back to the batch axis with
        values untouched."""
        repl = named_sharding(self.mesh)
        out = jax.lax.with_sharding_constraint(out, repl)
        labels_or_targets = jax.lax.with_sharding_constraint(
            labels_or_targets, repl)
        valid = jax.lax.with_sharding_constraint(valid, repl)
        return super(GSPMDTrainer, self)._loss_and_metrics(
            out, labels_or_targets, valid)

    # -- measured sweeps (veles_gspmd_step_ms) ------------------------------

    def train_class(self, params, states, skip=0):
        t0 = time.perf_counter()
        out = super(GSPMDTrainer, self).train_class(params, states,
                                                    skip=skip)
        # block: the honest exchange+compute cycle, not the async
        # dispatch (the runner blocks on these results right after
        # anyway, so this moves the wait, it does not add one)
        jax.block_until_ready(out)
        self._gspmd_ms.labels(phase="train").observe(
            (time.perf_counter() - t0) * 1e3)
        return out

    def eval_class(self, params, klass, skip=0):
        t0 = time.perf_counter()
        out = super(GSPMDTrainer, self).eval_class(params, klass,
                                                   skip=skip)
        jax.block_until_ready([o for o in out if o is not None])
        self._gspmd_ms.labels(phase="eval").observe(
            (time.perf_counter() - t0) * 1e3)
        return out

    # -- train→serve layout moves ------------------------------------------

    @profiler.phased("params_place")
    def push_params(self, params, states):
        """Device pytrees -> unit Arrays, via the measured train→serve
        reshard: model-axis-sharded leaves move to the fully replicated
        layout (the all-gather decomposition) before landing in the
        unit Arrays, so snapshots and the serving model store read full
        arrays without a hidden gather on their own path."""
        from veles_tpu.parallel import reshard
        repl = named_sharding(self.mesh)

        def to_replicated(v):
            try:
                return reshard.reshard(v, repl)
            except ValueError:
                # a jaxlib that cannot device_put across processes:
                # keep the source layout (the pre-ISSUE-15 behavior —
                # readers gather on their own path)
                return v

        params = tuple(
            {k: to_replicated(v) for k, v in layer.items()}
            for layer in params)
        states = jax.tree_util.tree_map(to_replicated, states)
        return super(GSPMDTrainer, self).push_params(params, states)
