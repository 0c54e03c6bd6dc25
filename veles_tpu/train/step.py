"""The step compiler: unit chain -> one XLA computation.

Contract with model workflows (MnistWorkflow et al. follow it):

* ``wf.loader``     — FullBatchLoader-like: device-resident
  ``original_data``/``original_labels``(/``original_targets``),
  ``shuffled_indices``, ``class_lengths``, ``max_minibatch_size``;
* ``wf.forwards``   — ordered ForwardBase list (pure ``apply``);
* ``wf.evaluator``  — EvaluatorSoftmax or EvaluatorMSE (selects loss);
* ``wf.gds``        — GD units (reverse order), giving each layer's
  solver + hyper-parameters;
* ``wf.decision``   — stop criterion (max_epochs / fail_iterations).

The compiled functions:

* ``train_segment(params, states, idx_matrix)`` — ``lax.scan`` over
  minibatches: gather → forward → loss → grad → per-layer solver
  update. On accelerators params/opt-states are donated, so weights
  stay in HBM across the whole segment with zero host traffic; on the
  CPU backend donation is OFF by default (``VELES_DONATE`` overrides)
  because this jaxlib's CPU client corrupts the heap under it — see
  :meth:`FusedTrainer._resolve_donate`;
* ``eval_segment(params, idx_matrix)`` — forward-only scan.

Epoch order mirrors the eager path (validation before train), so loss
curves are comparable run-to-run.

Training math parity: gradients here are d(mean CE)/dθ with padded rows
masked — identical to EvaluatorSoftmax's ``(p - onehot)/batch`` seed
through the GD chain.
"""

import contextlib
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy

from veles_tpu import prng, remat
from veles_tpu.envknob import env_flag, env_knob
from veles_tpu.loader import prefetch
from veles_tpu.loader.base import TEST, TRAIN, VALIDATION, CLASS_NAMES
from veles_tpu.logger import Logger
from veles_tpu.nn.dropout import DropoutForward
from veles_tpu.nn.evaluator import EvaluatorMSE, EvaluatorSoftmax
from veles_tpu.nn.optim import get_solver
from veles_tpu.telemetry import profiler, tracing
from veles_tpu.telemetry.registry import get_registry


def device_scope(*parts):
    """The name every traced operation of a fused step carries on the
    device, as a ``jax.named_scope``; this docstring is the grammar's
    one definition (the benchmark's ``readers/trace_scopes.py`` parses
    it with an expression of its own):

    * ``veles.in``: the minibatch fetch (:meth:`FusedTrainer._fetch`:
      a gather, or a partitioned trainer's exchange of rows);
    * ``veles.u<ii>.<unit name>``: forward unit ``<ii>``, its ABSOLUTE
      two-digit index in ``trainer.forwards``, whichever ``apply*``
      branch it takes, with its ``aux_loss``
      (:meth:`FusedTrainer._forward_range`: ``veles.u00.conv_str0``);
    * ``veles.loss``: ``_loss_and_metrics`` (at its callers: the GSPMD
      override's resharding stays outside) and ``_batch_confusion``;
    * ``veles.update.u<ii>.<unit name>``: that unit's solver update,
      and its :meth:`update_state` where it has one;
    * ``veles.gradnorm``: the global gradient norm's reduction.

    A scope may have plain sub-scopes BEHIND it, further path elements
    without the ``veles.`` prefix, which a reader that does not know
    them skips: a unit names its own parts (``veles.u04.moe4/route``,
    ``/experts``, ``/shared``; ``veles.u03.latent_attention3/proj``,
    ``/core``; ``veles.u01.grouped_attention1/proj``, ``/core``,
    ``/gate``, and under a learned selection of keys ``/index``,
    ``/select``, ``/index_loss`` beside them); a per-token objective
    names the stream a pass of the head and its loss belong to,
    ``main`` or the side branch's name
    (``veles.u16.vocabulary_head16/mtp``, ``veles.loss/main``,
    ``veles.loss/mtp``). A side branch's units carry their own indices
    like any other unit.

    The pass needs no scope: JAX wraps the name itself, so an
    operation of the backward pass reads
    ``transpose(jvp(veles.u03.fc))`` and one of the forward pass
    ``jvp(veles.u03.fc)`` (train) or ``veles.u03.fc`` (eval). A scope
    is HLO metadata only: it costs nothing per step and leaves the
    compiled program as it was (tests/test_unit_scopes.py), so there
    is no switch. Never a ``tracing.span`` here: traced code must not
    read the clock."""
    return jax.named_scope(".".join(("veles",) + parts))


def unit_tag(i, fwd):
    """``u<ii>.<unit name>`` of forward unit ``i``; what a unit's name
    holds of an op name's own syntax (``/``, brackets, blanks) is
    replaced."""
    return "u%02d.%s" % (i, re.sub(r"[^\w.\-]", "_", fwd.name))


def staged_row_shape(n_elements, dtype):
    """``(rows, cols)`` under which one staged sample of
    ``n_elements`` is stored: the fewest whole device tiles that hold
    it, side by side in one band of rows.

    A TPU array's two minor dims are tiled, rows by 128 lanes (the
    v5e: ``T(8,128)`` for ``float32``, ``T(8,128)(2,1)`` for
    ``bfloat16``, two rows to a 32-bit sublane). The band is one full
    register of rows, 8 sublanes of 32 bits: 8 rows of ``float32``,
    16 of ``bfloat16``, 32 of a one-byte type, which every such tile
    divides. A shape whose trailing dims are whole tiles keeps the
    order it is written in, sample-major, as its default layout: a
    sample is one contiguous run of tiles and the minibatch gather
    reads it in place. One that is not is stored in whichever dim
    order pads least, and every program that wants it sample-major
    copies it first (:meth:`FusedTrainer._maybe_stage_s2d`). The
    sample's tail, under one tile, is zero padding."""
    rows = 8 * max(1, 4 // numpy.dtype(dtype).itemsize)
    return rows, -(-n_elements // (rows * 128)) * 128


@jax.jit
def _fold_keys(base, steps):
    """One key a step, folded from the epoch's ``base``. Jitted, so
    that a sweep after the first runs it from JAX's cache of
    executables: mapped eagerly, ``fold_in`` was traced anew every
    sweep."""
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(steps)


def _committed(*trees):
    """Whether every array of ``trees`` is committed to its devices,
    as a program's outputs are and a value made on the host side is
    not. A ``segment_first_call`` row says which the segment's
    executable was built from; the held executable takes either
    (:func:`_signature` does not tell them apart)."""
    return all(getattr(leaf, "committed", False)
               for leaf in jax.tree_util.tree_leaves(trees))


def _signature(args):
    """What tells one executable of a segment from another: the
    operands' tree and every leaf's shape, dtype and sharding (a
    one-step sweep from a 16-step one, a plan of rows from a plain
    matrix, a mesh's placement from one chip's). Not whether a leaf is
    committed: an executable compiled for a device takes both. A
    host value (numpy: a restored checkpoint's parameters) has no
    sharding, which tells it from the arrays a call hands back."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    return tree, tuple(
        (leaf.shape, leaf.dtype, getattr(leaf, "sharding", None))
        for leaf in leaves)


class StepContext(object):
    """What a unit of a fused step may read beyond ``x`` and its own
    parameters, and what it may hand back beside its output (the seam
    ROADMAP D7 asked for). A unit that defines ``apply_step(params, x,
    ctx) -> (y, stats)`` is called with it:

    * ``tokens``: the minibatch as gathered, before any unit touched it
      (a token model's ids, lookahead included);
    * ``params_of(name)``: the parameters of the unit of that name, as
      this step differentiates them (a tied embedding or head: the
      gradient reaches them through every reader);
    * ``train``: whether the step trains;
    * ``stats``: ``{unit tag: {name: array}}`` of what units handed
      back (a router's per-expert token counts). They leave the train
      scan beside the gradient norms
      (:attr:`FusedTrainer.last_step_stats`) and reach the unit's own
      ``update_state(params, stats)`` after the solver's update;
    * ``sides``: ``{branch: state}``, the side branches' streams
      (:meth:`FusedTrainer._forward_range`);
    * ``valid``: which rows of the padded batch are samples, or None.

    A unit whose class names one of its stats ``OBJECTIVE_STAT`` hands
    the step a scalar TERM OF THE OBJECTIVE that way, computed from
    its own intermediate values in the pass that made them (an
    index's KL against the attention core's probabilities, PR 33): it
    leaves the unit's rematerialization as every stat does, an output
    of the checkpointed function, and a per-token objective adds it to
    the loss the step differentiates."""

    def __init__(self, forwards, params_list, tokens, train, valid=None):
        self.tokens, self.train, self.valid = tokens, train, valid
        self._params = {fwd.name: p
                        for fwd, p in zip(forwards, params_list)}
        self.stats = {}
        self.sides = {}

    def params_of(self, name):
        return self._params[name]


class FusedTrainer(Logger):
    """Compiles and drives the fused train/eval loop of a workflow.

    Dataset residency generalizes the old all-or-nothing staging:
    *staged-resident* when the dataset fits the device budget (the
    pre-existing path, including the space-to-depth staging pack),
    *streamed* when it doesn't — fixed-size shards are host-gathered
    and transferred through :mod:`veles_tpu.loader.prefetch`'s
    double-buffered staging ring while the previous shard computes,
    so datasets larger than HBM train out-of-core instead of OOMing.
    ``stream=None`` auto-decides (``VELES_STREAM`` /
    ``VELES_DEVICE_BUDGET_MB`` override); True/False force.

    MODEL state gets the same treatment (ISSUE 17,
    :mod:`veles_tpu.train.offload`): when the params + optimizer state
    exceed the device budget (or ``VELES_OFFLOAD``/``offload=True``
    force it), the master copies stay on host and the step walks layer
    groups through a double-buffered staging ring — H2D prefetch of
    group k+1 overlaps group k's compute, updated groups retire D2H on
    a writeback thread. The loss curve is bit-identical to the in-core
    run (pinned by tests/test_offload.py). Offload composes with a
    RESIDENT dataset only; a streamed dataset wins the ring.
    """

    #: cost-book op namespace: parallel trainers that compile a
    #: DIFFERENT program for the same sweep (the GSPMD path's
    #: partitioned step, ISSUE 15) prefix their op names so their
    #: cost/collective-bytes rows never mix with the single-device
    #: program's — the runner reads this too
    _op_prefix = ""

    @profiler.phased("trainer_build")
    def __init__(self, workflow, donate=None, stage_s2d=True,
                 grad_norms=None, stream=None, prefetch_depth=None,
                 prefetch_workers=None, offload=None,
                 offload_depth=None, offload_workers=None):
        super(FusedTrainer, self).__init__()
        self.workflow = workflow
        self.loader = workflow.loader
        self.forwards = list(workflow.forwards)
        self.evaluator = workflow.evaluator
        self.decision = workflow.decision
        self.donate = self._resolve_donate(donate)
        self.stage_s2d = stage_s2d
        self.stream = stream
        self.prefetch_depth = prefetch_depth
        self.prefetch_workers = prefetch_workers
        #: model-state residency (ISSUE 17): ``None`` auto-decides
        #: (``VELES_OFFLOAD`` / device budget), True/False force
        self.offload = offload
        self.offload_depth = offload_depth
        self.offload_workers = offload_workers
        self.offloaded = False
        self._offload_engine = None
        #: cumulative step-thread input wait (streamed mode); the
        #: runner reads deltas of this per epoch
        self.input_wait_s = 0.0
        self._active_pipeline = None
        #: ``{(op, _signature(operands)): jax.stages.Compiled}``: every
        #: segment executable this trainer built (:meth:`_call_segment`)
        self._executables = {}
        #: optional ``fn(trainer, params, states)`` fired after EVERY
        #: closed epoch (both the standalone :meth:`train` loop and the
        #: production FusedRunner honor it) — the elastic checkpoint
        #: seam (ISSUE 13): veles_tpu.parallel.elastic hangs its
        #: per-epoch sharded snapshot here. Observational only: it
        #: must not mutate params/states.
        self.epoch_callback = None
        # per-batch global gradient norms ride the train scan (the
        # flight recorder's divergence detector input); the norm is a
        # pure observation over grads the solver reads anyway, so the
        # update math is untouched
        self.track_grad_norms = (
            grad_norms if grad_norms is not None
            else env_flag("VELES_GRAD_NORMS", True))
        #: (n_batches,) f32 norms of the most recent train segment,
        #: None until one ran (or when tracking is off)
        self.last_grad_norms = None
        #: per-token objectives only: what left the most recent train
        #: segment beside the losses, each with a leading axis of
        #: batches: ``{"losses": {branch: (n_batches,)}, "stats":
        #: {unit tag: {name: (n_batches, ...)}}}``
        self.last_step_stats = None
        self._staged_s2d = False
        # map each forward to its GD unit (for solver + hyper)
        self.gd_for = {}
        for gd in getattr(workflow, "gds", []):
            self.gd_for[id(gd.forward)] = gd
        profiler.watch_builds()
        self._build()

    def _op(self, name):
        """Cost-book op name under this trainer's namespace."""
        return self._op_prefix + name

    @staticmethod
    def _resolve_donate(donate):
        """Donation policy: explicit arg > ``VELES_DONATE`` env > off
        on CPU, on elsewhere.

        Donation is an HBM-residency optimization — on TPU it keeps
        weights device-resident across segments without a spare copy.
        On the CPU backend it buys nothing (host RAM, no transfer) and
        this jaxlib's CPU client intermittently corrupts the glibc
        heap when scan-carried tuple params are donated: depending on
        allocator layout the run dies with ``free(): invalid next
        size`` / ``munmap_chunk(): invalid pointer`` aborts, segfaults
        materializing segment outputs, or silently-garbled weights —
        the long-standing "order-dependent eager-vs-fused flake"
        (reproduced standalone: tests/test_fused_runner.py fails or
        aborts ~5/6 runs with donation on CPU, 0/6 with it off)."""
        if donate is not None:
            return donate
        env = env_flag("VELES_DONATE", None)
        if env is not None:
            return env
        import jax
        return jax.default_backend() != "cpu"

    # -- pure functions ----------------------------------------------------

    def _forward(self, params_list, x, key, train, aux=None,
                 valid=None):
        """Run the forward chain; the head uses apply_for_grad (logits).

        ``aux`` (train path): a list that collects units' auxiliary
        loss terms (e.g. MoE load balancing) for the grad loss;
        ``valid`` is the padded-row mask those terms must respect."""
        return self._forward_range(params_list, x, key, train, 0,
                                   len(self.forwards), aux=aux,
                                   valid=valid)

    def _forward_range(self, params_list, x, key, train, lo, hi,
                       aux=None, valid=None, ctx=None):
        """Forward through layers ``[lo, hi)`` only — the group-walk
        primitive of offloaded execution (ISSUE 17); ``_forward`` is
        the full range. ``params_list`` holds ONLY the range's layers,
        but dropout keys fold by the ABSOLUTE layer index, so a
        grouped walk reproduces the fused chain bit-for-bit.

        A unit of a side branch (``fwd.branch``) takes the main path's
        state where the branch leaves it, or the branch's own, and
        leaves its output in ``ctx.sides``; the main path goes on past
        it. A branch is part of the objective: a step that does not
        train (or has no ``ctx``) skips it."""
        for j, fwd in enumerate(self.forwards[lo:hi]):
            branch = getattr(fwd, "branch", None)
            if branch is not None and (ctx is None or not ctx.train):
                continue
            with device_scope(unit_tag(lo + j, fwd)):
                out = self._apply_unit(
                    lo + j, fwd, params_list[j],
                    x if branch is None else ctx.sides.get(branch, x),
                    key, train, aux, valid, ctx)
            if branch is None:
                x = out
            else:
                ctx.sides[branch] = out
        return x

    def _apply_unit(self, i, fwd, params, x, key, train, aux, valid,
                    ctx=None):
        """Forward unit ``i`` (absolute index) on ``x``."""
        if aux is not None:
            aux_fn = getattr(fwd, "aux_loss", None)
            if aux_fn is not None and \
                    getattr(fwd, "aux_loss_weight", 0.0):
                aux.append(aux_fn(params, x, valid=valid))
        is_head = i == len(self.forwards) - 1
        if isinstance(fwd, DropoutForward):
            if train:
                x = fwd.apply_with_key(params, x,
                                       jax.random.fold_in(key, i))
        elif i == 0 and self._staged_s2d:
            # dataset was packed to patch-channel layout at staging;
            # the entry conv consumes it directly — no per-step
            # rearrange. Numerics identical to fwd.apply on raw.
            x = fwd.apply_staged(params, self._unstage(x))
        elif is_head:
            x = fwd.apply_for_grad(params, x)
        elif ctx is not None:
            x = self._apply_in_context(i, fwd, params, x, ctx)
        else:
            x = fwd.apply(params, x)
        return x

    @staticmethod
    def _apply_in_context(i, fwd, params, x, ctx):
        """A unit of a step that has a :class:`StepContext`: through
        ``apply_step`` where the unit has one, its stats kept under
        the unit's tag, and rematerialized in the backward pass where
        the unit's descriptor asked (``remat``), but for the values
        the unit's own code named as worth keeping
        (:mod:`veles_tpu.remat`; the gauge
        ``veles_remat_kept_bytes{unit}`` says how many bytes)."""
        step_fn = getattr(fwd, "apply_step", None)
        if step_fn is None:
            def fn(p, v):
                return fwd.apply(p, v), {}
        else:
            def fn(p, v):
                return step_fn(p, v, ctx)
        if ctx.train and getattr(fwd, "remat", False):
            (x, stats), kept = remat.checkpoint(fn)(params, x)
        else:
            (x, stats), kept = fn(params, x), 0
        if ctx.train:
            get_registry().gauge(
                "veles_remat_kept_bytes", "bytes a unit of the fused "
                "train step keeps across its rematerialization in the "
                "backward pass; 0 without remat", labels=("unit",)
            ).labels(unit=fwd.name).set(kept)
        if stats:
            ctx.stats[unit_tag(i, fwd)] = stats
        return x

    def _token_objective(self, params_list, x, truth, key, valid, train):
        """The per-token objective of a chain that ends in a head with
        ``token_losses``: ``(grad_loss, (report, metric, extras))``.

        ``truth[:, t]`` is the target of position ``t`` on the main
        path; a side branch of shift ``s`` is scored, through the same
        head, against ``truth[:, t + s]``, and its mean loss enters
        the objective with the branch's weight. ``report`` is the MAIN
        path's mean loss over the valid rows' tokens and ``metric`` its
        count of missed tokens; ``extras`` is ``{"losses": {branch:
        mean loss}, "stats": ctx.stats}``. A unit's own term, the stat
        its class names ``OBJECTIVE_STAT``, enters the objective as it
        is. The gradient's scale is the
        softmax branch's of :meth:`_loss_and_metrics`: a mean over the
        whole padded batch."""
        n = len(self.forwards)
        ctx = StepContext(self.forwards, params_list, x, train, valid)
        state = self._forward_range(params_list[:n - 1], x, key, train,
                                    0, n - 1, valid=valid, ctx=ctx)
        head, tag = self.forwards[-1], unit_tag(n - 1, self.forwards[-1])
        # a tied head reads another unit's parameters out of ``ctx``
        head_params = head.step_params(params_list[-1], ctx) \
            if hasattr(head, "step_params") else params_list[-1]
        batch, seq = state.shape[:2]
        rows = valid.astype(jnp.float32)[:, None]
        n_valid = jnp.maximum(jnp.sum(valid), 1)

        def stream_loss(name, stream, shift):
            def loss_scope():
                stack = contextlib.ExitStack()
                stack.enter_context(device_scope("loss"))
                stack.enter_context(jax.named_scope(name))
                return stack
            with device_scope(tag), jax.named_scope(name):
                loss, wrong = head.token_losses(
                    head_params, stream,
                    truth[:, shift:shift + seq], loss_scope)
            with loss_scope():
                total = jnp.sum(loss * rows)
                return (total / (batch * seq), total / (n_valid * seq),
                        jnp.sum(wrong & valid[:, None]))

        grad_loss, report, metric = stream_loss("main", state, 0)
        extras = {"losses": {}, "stats": ctx.stats}
        for branch, (shift, weight) in self._branches.items():
            if branch not in ctx.sides:
                continue
            term, extras["losses"][branch], _ = stream_loss(
                branch, ctx.sides[branch], shift)
            grad_loss = grad_loss + weight * term
        for i, fwd in enumerate(self.forwards):
            term = ctx.stats.get(unit_tag(i, fwd), {}).get(
                getattr(fwd, "OBJECTIVE_STAT", None))
            if term is not None:
                grad_loss = grad_loss + term
        return grad_loss, (report, metric, extras)

    def _loss_and_metrics(self, out, labels_or_targets, valid):
        """Returns (grad_loss, report_loss, metric).

        ``grad_loss`` reproduces the eager evaluator's gradient seed
        EXACTLY: softmax err is (p - onehot)/batch (full padded batch,
        evaluator.py _softmax_eval), MSE err is diff/n_valid. The
        human-facing ``report_loss`` normalizes by valid rows."""
        # loss math always reduces in f32, whatever the compute policy
        # left the head output in
        out = out.astype(jnp.float32)
        batch = out.shape[0]
        if self.loss_kind == "softmax":
            labels = labels_or_targets
            safe = jnp.where(valid, labels, 0)
            logp = jax.nn.log_softmax(out.reshape(batch, -1))
            picked = jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
            n_valid = jnp.maximum(jnp.sum(valid), 1)
            grad_loss = -jnp.sum(picked * valid) / batch
            report_loss = -jnp.sum(picked * valid) / n_valid
            pred = jnp.argmax(logp, axis=1)
            n_err = jnp.sum((pred != safe) & valid)
            return grad_loss, report_loss, n_err
        # mse: eager err_output = diff/n_valid -> loss 0.5*sum(d^2)/n_valid
        target = labels_or_targets
        diff = (out.reshape(batch, -1) -
                target.reshape(target.shape[0], -1))
        diff = diff * valid[:, None]
        n_valid = jnp.maximum(jnp.sum(valid), 1)
        grad_loss = 0.5 * jnp.sum(jnp.square(diff)) / n_valid
        # metric matches DecisionMSE: summed per-sample mean-sq-error
        metric = jnp.sum(jnp.mean(jnp.square(diff), axis=1))
        return grad_loss, metric / n_valid, metric

    def _maybe_stage_s2d(self):
        """Pack the dataset to patch-channel layout ONCE, if the entry
        layer is a space-to-depth conv.

        The per-step ``s2d_pack_input`` on the gathered batch costs
        ~1.5 ms/step on the AlexNet flagship (docs/PERF.md); packing is
        row-wise and linear, so doing it at staging commutes with the
        index gather and the invalid-row zero mask — float math is
        unchanged. Upload happens chunked host->device into a donated
        buffer, so peak HBM is packed + one chunk (the raw full copy is
        never resident).

        Each packed sample is stored flat, zero-padded to whole device
        tiles and folded to ``(rows, cols)`` by :func:`staged_row_shape`
        (the flagship's 58*58*48 bf16 elements: 79 tiles, +0.2%). What
        the device does with a shape, not the rearrange, decides the
        step; five shapes were measured (r4 and PR 25 on v5e; tables
        in docs/PERF.md and PERF.md §6):

        * (n, rows_y, rows_x, 48) 4D: XLA relayouts the WHOLE dataset
          in-program to lane-pad the 48-channel minor dim (2.9x =
          14.6 GB copy -> compile OOM);
        * (n, F) flat 2D: the row gather lowers to a one-hot matmul —
          O(n * mb * F) per step, +16 ms/step at n=16k (the whole
          dataset re-read every step);
        * (n, F/128, 128) with F/128 not a whole number of tile rows:
          generic scalar-core gather of many tiny slices, +23 ms/step;
        * (n, rows_y, rows_x*48), what shipped until PR 25: the step
          gathers per-row DMA slices, but neither trailing dim is whole
          tiles (58 rows, 2,784 lanes), so the TPU runtime's default
          layout for the array puts the SAMPLES minor-most, the order
          that pads least, and every call of the train and the eval
          program began by copying the whole data set back to
          sample-major: 18 ms a call and a second, padded data set of
          temporaries (PR 24's trace; a 2k-sample probe had hidden it);
        * (n, rows, cols) of whole tiles: the default layout is the
          written, sample-major order, a sample is one contiguous run
          of tiles, the gather is the same DMA and no program touches
          the data set outside its scan.

        ``veles_dataset_relayout_bytes{op}`` (telemetry/profiler.py)
        reads what each compiled segment does to the data set before
        its scan; 0 is in place. The zero tail is written once here
        and sliced off the gathered minibatch (:meth:`_unstage`), so
        it never reaches ``apply_staged``. Returns the packed
        ``jax.Array`` or None; the CONV's per-sample packed shape, not
        the stored one, lands in ``self._staged_sample_shape``.
        """
        from veles_tpu.nn.conv import Conv
        fwd0 = self.forwards[0] if self.forwards else None
        if (not self.stage_s2d or len(self.forwards) < 2 or
                not isinstance(fwd0, Conv) or
                not getattr(fwd0, "space_to_depth", False)):
            return None
        raw = self.loader.original_data.map_read()
        n = raw.shape[0]
        packed_sample = fwd0.s2d_packed_shape(raw.shape[1:])
        self._staged_sample_shape = packed_sample
        flat = int(numpy.prod(packed_sample))
        rows, cols = staged_row_shape(flat, raw.dtype)
        pad = rows * cols - flat

        def pack_rows(chunk):
            packed = fwd0.s2d_pack_input(chunk).reshape(
                chunk.shape[0], flat)
            return jnp.pad(packed, ((0, 0), (0, pad))).reshape(
                chunk.shape[0], rows, cols)

        update = jax.jit(
            lambda buf, chunk, start: jax.lax.dynamic_update_slice(
                buf, pack_rows(chunk), (start, 0, 0)),
            donate_argnums=(0,) if self.donate else ())
        packed = jnp.zeros((n, rows, cols), dtype=raw.dtype)
        chunk = max(1, min(n, 512))
        for i, start in enumerate(range(0, n, chunk)):
            piece = jnp.asarray(raw[start:start + chunk])
            packed = update(packed, piece, start)
            if i % 8 == 7:
                # wait here, so that no more than 8 chunk uploads and
                # their updates are ever queued at once
                packed.block_until_ready()
        packed.block_until_ready()
        # the raw full copy must not ALSO sit on the device (some
        # eager path may have uploaded it before the fused build)
        self.loader.original_data.release_devmem()
        self.debug("staged space-to-depth dataset: %s -> %s, %.3f%% of "
                   "it zero padding, on the device as %s", raw.shape,
                   packed.shape, 100.0 * pad / (rows * cols),
                   getattr(packed, "format", None))
        return packed

    def _unstage(self, x):
        """Gathered rows of the staged data set -> the entry conv's
        packed samples, ``(mb,) + _staged_sample_shape``: the zero
        tail of each stored sample is sliced off and the rest
        reshaped. It touches only the minibatch (~40 MB on the
        flagship)."""
        packed = self._staged_sample_shape
        flat = int(numpy.prod(packed))
        return x.reshape(x.shape[0], -1)[:, :flat].reshape(
            (x.shape[0],) + packed)

    # -- dataset residency: staged-resident OR streamed --------------------

    def _dataset_device_bytes(self, total_bytes):
        """Bytes of the dataset ONE device would hold resident (the
        data-parallel trainer divides by its shard count)."""
        return total_bytes

    def _shard_placer(self):
        """host ndarray -> device shard array; the data-parallel
        trainer overrides this with a mesh-sharded placement."""
        return prefetch.default_placer(
            getattr(self.loader.original_data, "device", None))

    def _setup_data_residency(self):
        """The generalization of the old all-or-nothing staging:
        *staged-resident* (s2d-packed where applicable) when the
        dataset fits the device budget, *streamed* out-of-core through
        the prefetch staging ring when it doesn't."""
        loader = self.loader
        truth_arr = (loader.original_labels
                     if self.loss_kind == "softmax"
                     else loader.original_targets)
        total_bytes = loader.original_data.nbytes + truth_arr.nbytes
        #: bytes of the data set and its truth on the host
        self.dataset_bytes = total_bytes
        device = getattr(loader.original_data, "device", None)
        self.streaming = prefetch.plan_residency(
            self._dataset_device_bytes(total_bytes), device=device,
            force=self.stream) == "streamed"
        if self.streaming and not hasattr(loader, "host_backing"):
            self.warning("loader %s has no host backing store — "
                         "cannot stream; forcing the dataset resident",
                         loader.name)
            self.streaming = False
        if not self.streaming:
            staged = self._maybe_stage_s2d()
            self._staged_s2d = staged is not None
            self._data_args = (
                staged if staged is not None
                else loader.original_data.devmem,
                truth_arr.devmem)
            return
        # streamed: the dataset NEVER becomes fully device-resident.
        # Space-to-depth staging is skipped — apply() packs per step,
        # trading ~1.5 ms/step (flagship) for fitting at all.
        self._staged_s2d = False
        self._data_args = None
        self._truth_kind = ("labels" if self.loss_kind == "softmax"
                            else "targets")
        data, truth = loader.host_backing(self._truth_kind)
        # an eager init may already have uploaded the full copy — a
        # streamed run must not keep it resident alongside the ring
        loader.original_data.release_devmem()
        truth_arr.release_devmem()
        mb = loader.max_minibatch_size
        batch_bytes = mb * (
            int(numpy.prod(data.shape[1:], dtype=numpy.int64)) *
            data.dtype.itemsize +
            int(numpy.prod(truth.shape[1:], dtype=numpy.int64)) *
            truth.dtype.itemsize)
        depth = (prefetch.default_depth() if self.prefetch_depth is None
                 else self.prefetch_depth)
        # shard sizing is per-DEVICE, like the budget: a data-parallel
        # mesh holds 1/N of every shard per device, so its shards carry
        # N times the minibatches for the same footprint
        self._batches_per_shard = prefetch.shard_batches(
            self._dataset_device_bytes(batch_bytes), depth=depth,
            budget_bytes=prefetch.device_budget_bytes(device))
        self._staging_ring = prefetch.StagingRing(
            max(1, depth) + 2, self._shard_placer())
        from veles_tpu.telemetry.registry import get_registry
        registry = get_registry()
        self._etl_ms = registry.histogram(
            "veles_prefetch_etl_ms", "Host ETL time per streamed shard")
        self._h2d_ms = registry.histogram(
            "veles_prefetch_h2d_ms",
            "Host->device transfer dispatch time per streamed shard")
        self.info(
            "dataset streams out-of-core: %.0f MB exceeds the device "
            "budget; shards of %d minibatches (%.0f MB), prefetch "
            "depth %d", total_bytes / 1e6, self._batches_per_shard,
            self._batches_per_shard * batch_bytes / 1e6, depth)

    # -- model residency: in-core OR host-offloaded (ISSUE 17) --------------

    def _setup_model_residency(self):
        """The model-state analogue of :meth:`_setup_data_residency`:
        params/opt-state stay device-resident across the segment scan
        when they fit the budget, or offload to host masters walked
        group-by-group through :mod:`veles_tpu.train.offload`'s
        double-buffered staging ring when they don't (``offload=`` /
        ``VELES_OFFLOAD`` force)."""
        from veles_tpu.train import offload
        device = getattr(self.loader.original_data, "device", None)
        layer_bytes = offload.model_layer_bytes(self.forwards,
                                                self.solvers)
        decision = offload.plan_offload(sum(layer_bytes), device=device,
                                        force=self.offload)
        if decision != "offloaded":
            return
        if self.per_token:
            # the group walk (train/offload.py) knows no step context,
            # side branch or per-token head
            self.warning(
                "a per-token objective keeps its model state in-core: "
                "the offload engine walks a plain chain only")
            return
        if self.streaming:
            self.warning(
                "offloaded model state requires a resident dataset — "
                "the streamed input pipeline already owns the staging "
                "budget; keeping params in-core")
            return
        depth = (offload.offload_depth() if self.offload_depth is None
                 else max(0, self.offload_depth))
        with profiler.phase("offload_plan"):
            with tracing.span("offload:plan"):
                plan = offload.OffloadPlan.build(
                    layer_bytes,
                    offload.group_budget_bytes(device, depth))
                self._offload_engine = offload.OffloadEngine(
                    self, plan, depth=depth,
                    workers=self.offload_workers)
        self.offloaded = True
        self.info(
            "model state offloads out-of-core: %.1f MB in %d layer "
            "groups (%s), staging depth %d",
            plan.total_bytes / 1e6, plan.n_groups,
            "/".join("%d-%d" % g for g in plan.groups), depth)

    @property
    def offload_wait_s(self):
        """Cumulative step-thread transfer wait of offloaded segments
        (the runner and benches read deltas — mirrors
        :attr:`input_wait_s`)."""
        engine = self._offload_engine
        return engine.wait_s if engine is not None else 0.0

    def _shard_bounds(self, n_rows):
        """[(row0, row1)] index-matrix row ranges, one per shard."""
        rows = max(1, min(self._batches_per_shard, n_rows))
        return [(r, min(r + rows, n_rows))
                for r in range(0, n_rows, rows)]

    def _stream_segment(self, kind, run_shard, idx_matrix):
        """Drive one class sweep shard-by-shard through the prefetch
        pipeline: worker threads fill+transfer shard N+k while
        ``run_shard(data_args, local_idx, row0, row1)`` computes shard
        N. Returns the list of per-shard outputs; publishes the step
        thread's input-wait histogram + starvation gauge."""
        idx_np = numpy.asarray(idx_matrix, numpy.int32)
        bounds = self._shard_bounds(idx_np.shape[0])
        ring = self._staging_ring
        loader = self.loader
        truth_kind = self._truth_kind

        def produce(i):
            row0, row1 = bounds[i]
            rows_idx = idx_np[row0:row1]
            t0 = time.perf_counter()
            data_rows, truth_rows = loader.fill_indices(
                rows_idx, kind=truth_kind)
            etl = time.perf_counter() - t0
            self._etl_ms.observe(etl * 1e3)
            tracing.add_complete("prefetch:etl", t0, etl, shard=i)
            t1 = time.perf_counter()
            placed = ring.place((data_rows, truth_rows))
            local = self._index_operand(
                kind, prefetch.local_indices(rows_idx))
            h2d = time.perf_counter() - t1
            self._h2d_ms.observe(h2d * 1e3)
            tracing.add_complete("prefetch:h2d", t1, h2d, shard=i)
            return placed, local, row0, row1

        pipe = prefetch.PrefetchPipeline(
            produce, len(bounds), depth=self.prefetch_depth,
            workers=self.prefetch_workers, name=kind)
        self._active_pipeline = pipe
        outs = []
        start = time.perf_counter()
        try:
            ring.reopen()  # a prior shutdown() may have closed it
            pipe.start()
            for _ in range(len(bounds)):
                (placed, local, row0, row1), _ = pipe.get()
                outs.append(run_shard(placed, local, row0, row1))
        finally:
            pipe.close()
            self._active_pipeline = None
            self.input_wait_s += pipe.wait_s
            wall = time.perf_counter() - start
            if wall > 0:
                prefetch.starvation_gauge().labels(phase=kind).set(
                    min(1.0, pipe.wait_s / wall))
        return outs

    def _train_segment_streamed(self, jit_train, params_list,
                                opt_states, idx_matrix, keys):
        state = [params_list, opt_states]

        def run_shard(data_args, local_idx, row0, row1):
            args = (data_args, state[0], state[1], local_idx,
                    self._keys_operand(keys[row0:row1]))
            out = self._call_segment("train_segment", jit_train, args,
                                     state)
            state[0], state[1] = out[0], out[1]
            return out[2:]

        outs = self._stream_segment("train", run_shard, idx_matrix)
        merged = jax.tree_util.tree_map(
            lambda *parts: jnp.concatenate(parts), *outs)
        return self._keep_observations((state[0], state[1]) + merged)

    def _keep_observations(self, out):
        """A train segment's ``(params, states, losses, metrics)``;
        what else left its scan, the gradient norms and a per-token
        objective's extras, is kept on the trainer."""
        rest = list(out[4:])
        if self.track_grad_norms:
            self.last_grad_norms = rest.pop(0)
        if self.per_token:
            self.last_step_stats = rest.pop(0)
        return tuple(out[:4])

    def _eval_segment_streamed(self, jit_eval, params_list, idx_matrix):
        def run_shard(data_args, local_idx, row0, row1):
            args = (data_args, params_list, local_idx)
            return self._call_segment("eval_segment", jit_eval, args,
                                      params_list)

        outs = self._stream_segment("eval", run_shard, idx_matrix)
        losses = jnp.concatenate([o[0] for o in outs])
        metrics = jnp.concatenate([o[1] for o in outs])
        if len(outs[0]) == 3:
            conf = outs[0][2]
            for o in outs[1:]:
                conf = conf + o[2]
            return losses, metrics, conf
        return losses, metrics

    def shutdown(self):
        """Join any live prefetch pipeline and drop staged shards.

        Idempotent: the streamed drivers already close their pipeline
        per segment — this is the crash/Ctrl-C backstop the runner
        (and tests' session teardown) call so worker threads never
        outlive the run."""
        pipe = self._active_pipeline
        if pipe is not None:
            pipe.close()
            self._active_pipeline = None
        ring = getattr(self, "_staging_ring", None)
        if ring is not None:
            ring.clear()
        engine = self._offload_engine
        if engine is not None:
            engine.close()

    @staticmethod
    def _gather(data_args, idx):
        dataset, truth_src = data_args
        with device_scope("in"):
            data = jnp.take(dataset, jnp.maximum(idx, 0), axis=0)
            data = data * (idx >= 0).reshape(
                (-1,) + (1,) * (data.ndim - 1)).astype(data.dtype)
            truth = jnp.take(truth_src, jnp.maximum(idx, 0), axis=0)
        return data, truth

    def _fetch(self, data_args, step):
        """One step's minibatch inside a segment's scan, from that
        step's slice of the scan's index operand: ``(data, truth,
        valid)``. A partitioned trainer plans its fetch on the host and
        overrides the three methods from here on."""
        data, truth = self._gather(data_args, step)
        return data, truth, step >= 0

    def _dataset_rows(self, idx_matrix):
        """Host index matrix of sample ids -> the rows of the resident
        data set that hold them (-1 stays)."""
        return idx_matrix

    def _index_operand(self, kind, idx_matrix):
        """Host index matrix -> the index operand a ``kind`` (train,
        eval) segment scans over."""
        return jnp.asarray(idx_matrix)

    def _keys_operand(self, keys):
        """A train sweep's dropout keys -> the operand its segment
        scans over beside the index operand."""
        return keys

    def _build(self):
        if isinstance(self.evaluator, EvaluatorSoftmax):
            self.loss_kind = "softmax"
        elif isinstance(self.evaluator, EvaluatorMSE):
            self.loss_kind = "mse"
        else:
            raise TypeError("unsupported evaluator %r" % self.evaluator)
        solvers = []
        hypers = []
        for fwd in self.forwards:
            gd = self.gd_for.get(id(fwd))
            solvers.append(get_solver(gd.solver_name) if gd else None)
            hypers.append(gd.hyper if gd else None)
        self.solvers = solvers
        self.hypers = hypers
        #: a head that scores every token (``token_losses``) makes the
        #: objective per-token: :meth:`_token_objective`
        self.per_token = self.loss_kind == "softmax" and bool(
            self.forwards) and hasattr(self.forwards[-1], "token_losses")
        #: ``{branch: (target shift, objective weight)}``, read off
        #: the unit that opens the branch
        self._branches = {}
        for fwd in self.forwards:
            branch = getattr(fwd, "branch", None)
            if branch is not None and branch not in self._branches:
                self._branches[branch] = (fwd.shift, fwd.objective_weight)
        if self._branches and not self.per_token:
            raise TypeError("side branches need a per-token head")

        # resolve the dataset's residency OUTSIDE any trace: calling
        # .devmem under jit would cache a tracer inside the Array.
        # CRITICAL: device arrays are passed to the compiled functions
        # as ARGUMENTS, never closed over — a closure-captured array is
        # baked into the HLO as a constant, which (a) bloats the
        # program by the whole dataset (hundreds of MB for ImageNet
        # shapes — enough to kill remote-compile services) and (b)
        # defeats donation/sharding of the dataset buffer.
        with profiler.phase("dataset_stage") as staged:
            self._setup_data_residency()
            staged.attrs.update(bytes=self.dataset_bytes,
                                streaming=self.streaming,
                                s2d=self._staged_s2d)

        #: fold confusion accumulation into the eval scan (one forward
        #: sweep serves losses+metrics+confusion) whenever the evaluator
        #: asks for it — eager fills confusion_matrix per minibatch
        #: under the same flag (evaluator.py:153-154)
        self.wants_confusion = self.loss_kind == "softmax" and \
            bool(getattr(self.evaluator, "compute_confusion", False)) \
            and not self.per_token  # a vocabulary squared is no table

        # model residency rides AFTER data residency: offload needs to
        # know whether the dataset streams (the two rings don't compose)
        with profiler.phase("model_residency"):
            self._setup_model_residency()

        fetch = self._fetch

        def train_batch(data_args, carry, batch_in):
            params_list, opt_states = carry
            step, key = batch_in
            x, truth, valid = fetch(data_args, step)

            def loss_fn(plist):
                if per_token:
                    return self._token_objective(plist, x, truth, key,
                                                 valid, train=True)
                aux = []
                out = self._forward(plist, x, key, train=True, aux=aux,
                                    valid=valid)
                with device_scope("loss"):
                    grad_loss, report, metric = self._loss_and_metrics(
                        out, truth, valid)
                # auxiliary terms (MoE load balancing) shape gradients
                # only; the human-facing report stays the task loss
                for term in aux:
                    grad_loss = grad_loss + term
                return grad_loss, (report, metric)

            (_, (loss, metric, *extras)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params_list)
            # what the units handed out of the forward pass; a chain
            # without a step context hands out nothing
            step_stats = extras[0]["stats"] if extras else {}
            new_params, new_states = [], []
            for i in range(len(params_list)):
                if self.solvers[i] is None or not params_list[i]:
                    new_params.append(params_list[i])
                    new_states.append(opt_states[i])
                    continue
                fwd = self.forwards[i]
                with device_scope("update", unit_tag(i, fwd)):
                    # the solver moves what has a gradient; the unit
                    # what has none, from the step's stats
                    p, s = self.solvers[i].update(
                        fwd.gradient_params(params_list[i]),
                        fwd.gradient_params(grads[i]), opt_states[i],
                        self.hypers[i])
                    if fwd.non_gradient:
                        p = dict(params_list[i], **p)
                        p.update(fwd.update_state(
                            params_list[i],
                            step_stats.get(unit_tag(i, fwd), {})))
                new_params.append(p)
                new_states.append(s)
            outs = (loss, metric)
            if track_norms:
                # global grad norm in f32 — observation only, and the
                # grads are being read by the solvers anyway so XLA
                # fuses the reduction into traffic already paid for
                with device_scope("gradnorm"):
                    gsq = jnp.asarray(0.0, jnp.float32)
                    for g in jax.tree_util.tree_leaves(grads):
                        gsq = gsq + jnp.sum(jnp.square(
                            g.astype(jnp.float32)))
                    outs = (loss, metric, jnp.sqrt(gsq))
            return (tuple(new_params), tuple(new_states)), \
                outs + tuple(extras)

        track_norms = self.track_grad_norms
        per_token = self.per_token

        def train_segment(data_args, params_list, opt_states, idx_matrix,
                          keys):
            (params_list, opt_states), outs = jax.lax.scan(
                lambda carry, batch_in: train_batch(data_args, carry,
                                                    batch_in),
                (params_list, opt_states), (idx_matrix, keys))
            return (params_list, opt_states) + tuple(outs)

        jit_train = self._compile_train(train_segment)

        def _train_segment_call(params_list, opt_states, idx_matrix, keys):
            if self.offloaded:
                with profiler.first_call(self._op("train_segment"),
                                         committed=False):
                    params_list, opt_states, losses, metrics, norms = \
                        self._offload_engine.train_segment(
                            params_list, opt_states,
                            self._dataset_rows(idx_matrix), keys)
                if track_norms:
                    self.last_grad_norms = norms
                return params_list, opt_states, losses, metrics
            if self.streaming:
                return self._train_segment_streamed(
                    jit_train, params_list, opt_states, idx_matrix,
                    keys)
            args = (self._data_args, params_list, opt_states,
                    self._index_operand("train", idx_matrix),
                    self._keys_operand(keys))
            return self._keep_observations(self._call_segment(
                "train_segment", jit_train, args,
                (params_list, opt_states)))

        self._train_segment = _train_segment_call

        wants_confusion = self.wants_confusion

        def eval_segment_pure(data_args, params_list, idx_matrix):
            def body(_, step):
                x, truth, valid = fetch(data_args, step)
                if per_token:
                    _, (report, metric, _) = self._token_objective(
                        params_list, x, truth, None, valid, train=False)
                    return None, (report, metric)
                out = self._forward(params_list, x, None, train=False)
                with device_scope("loss"):
                    _, report, metric = self._loss_and_metrics(
                        out, truth, valid)
                if wants_confusion:
                    conf = self._batch_confusion(out, truth, valid)
                    return None, (report, metric, conf)
                return None, (report, metric)
            _, outs = jax.lax.scan(body, None, idx_matrix)
            if wants_confusion:
                losses, metrics, confs = outs
                return losses, metrics, jnp.sum(confs, axis=0)
            return outs

        jit_eval = self._compile_eval(eval_segment_pure)

        def _eval_segment_call(params_list, idx_matrix):
            if self.offloaded:
                with profiler.first_call(self._op("eval_segment"),
                                         committed=False):
                    return self._offload_engine.eval_segment(
                        params_list, self._dataset_rows(idx_matrix))
            if self.streaming:
                return self._eval_segment_streamed(
                    jit_eval, params_list, idx_matrix)
            args = (self._data_args, params_list,
                    self._index_operand("eval", idx_matrix))
            return self._call_segment("eval_segment", jit_eval, args,
                                      params_list)

        self._eval_segment = _eval_segment_call

    def _call_segment(self, name, jit_fn, args, state):
        """One call of a segment: of the executable held for these
        operands' :func:`_signature`, which is built here where there
        is none yet, ahead of its first call, by
        ``jit_fn.lower(*args).compile()`` on the operands themselves
        (the placement is the call's own; the donation travels with
        the lowering). That build and first call are one
        ``segment_first_call`` start-up row with JAX's stages inside
        it, which says whether ``state`` (the parameters, and the
        optimizer's with them) came in committed; the segment's costs
        are then read off the executable (:meth:`_harvest_costs`)
        while the call runs. A later call costs the signature and a
        dictionary lookup: nothing is traced, lowered or built again,
        whatever became committed meanwhile. Measured times are
        observed by the callers that BLOCK on the results (dispatch
        here is async: timing it would be a lie)."""
        op = self._op(name)
        key = op, _signature(args)
        compiled = self._executables.get(key)
        if compiled is not None:
            return compiled(*args)
        with profiler.first_call(op, committed=_committed(state)):
            compiled = self._executables[key] = \
                jit_fn.lower(*args).compile()
            get_registry().gauge(
                "veles_segment_executables", "Executables a trainer "
                "holds for a segment, one a signature of operands",
                labels=("op",)).labels(op=op).set(
                sum(held == op for held, _ in self._executables))
            out = compiled(*args)
        self._harvest_costs(op, compiled, args[0][0])
        return out

    @staticmethod
    def _harvest_costs(op, compiled, data):
        """Once an op: the cost analysis, collective bytes and data-set
        relayout of the executable a call just built, into the
        CostBook (``veles_op_flops``, ``veles_op_bytes``; the
        ``cost_harvest`` start-up row, which times the reading of the
        analysis and of the compiled text: nothing is lowered or
        built for it). ``data`` is the segment's data set operand."""
        book = profiler.get_cost_book()
        if not book.needs_harvest(op):
            return
        with profiler.phase("cost_harvest", op=op):
            # what ONE device holds of the data set (a data-parallel
            # trainer's is row-sharded): the shape the compiled text
            # spells, for veles_dataset_relayout_bytes
            book.harvest(op, compiled, dataset_shape=(
                data.sharding.shard_shape(data.shape)))

    @staticmethod
    def _batch_confusion(out, truth, valid):
        """One minibatch's confusion counts (eager: evaluator.py:39-42)."""
        with device_scope("loss"):
            probs = out.reshape(out.shape[0], -1)
            n_classes = probs.shape[-1]
            pred = jnp.argmax(probs, axis=1)
            safe = jnp.where(valid, truth, 0)
            flat = safe * n_classes + pred
            return jnp.zeros((n_classes * n_classes,), jnp.int32).at[
                flat].add(valid.astype(jnp.int32)).reshape(
                n_classes, n_classes)

    def confusion_segment(self, params_list, idx_matrix):
        """Summed confusion matrix of a forward pass over a segment.

        Lazily compiled, and only needed for the TRAIN class when no
        validation set exists — eval segments already return confusion
        alongside losses when ``wants_confusion``. Whole-segment
        accumulation supersedes the eager evaluator's last-minibatch
        snapshot of ``confusion_matrix``."""
        if self.loss_kind != "softmax":
            raise TypeError("confusion requires a softmax evaluator")
        fn = getattr(self, "_conf_fn", None)
        if fn is None:
            def conf_pure(data_args, params_list, idx_matrix):
                def body(_, step):
                    x, truth, valid = self._fetch(data_args, step)
                    out = self._forward(params_list, x, None, train=False)
                    return None, self._batch_confusion(out, truth, valid)
                _, confs = jax.lax.scan(body, None, idx_matrix)
                return jnp.sum(confs, axis=0)
            fn = self._conf_fn = jax.jit(conf_pure)
        if self.offloaded:
            return self._offload_engine.confusion_segment(
                params_list, self._dataset_rows(numpy.asarray(idx_matrix)))
        if self.streaming:
            def run_shard(data_args, local_idx, row0, row1):
                return fn(data_args, params_list, local_idx)
            outs = self._stream_segment("eval", run_shard,
                                        numpy.asarray(idx_matrix))
            conf = outs[0]
            for o in outs[1:]:
                conf = conf + o
            return conf
        return fn(self._data_args, params_list,
                  self._index_operand("eval", idx_matrix))

    def _dropout_base_key(self):
        """Per-epoch dropout key, drawn from the DROPOUT unit's stream
        (eager: DropoutForward._draw_mask uses prng.get(self.rand_name),
        nn/base.py:39) — never from the loader's, whose shuffle sequence
        must stay bit-identical to an eager run of the same seed."""
        for fwd in self.forwards:
            if isinstance(fwd, DropoutForward):
                return prng.get(fwd.rand_name).jax_key()
        # keys are dead in the trace without dropout; a constant keeps
        # every stream untouched
        return jax.random.PRNGKey(0)

    # -- class-level driving (shared by run_epoch and FusedRunner) ---------

    def eval_class(self, params, klass, skip=0):
        """Forward-only sweep of one class (from sample ``skip`` on).

        Returns ``(losses, metrics, confusion)`` where ``confusion`` is
        None unless it rides the eval scan (``wants_confusion``)."""
        # the HOST's index matrix: a resident segment makes its operand
        # of it (_index_operand), a streamed or offloaded one slices it
        out = self._eval_segment(
            params, self._segment_indices(klass, skip=skip))
        return out[0], out[1], out[2] if len(out) == 3 else None

    def train_class(self, params, states, skip=0):
        """One training sweep of the TRAIN class with per-batch dropout
        keys folded from the epoch's base key.

        On a mid-epoch resume (``skip`` > 0) the fold indices continue
        from the batch position within the epoch, so the key sequence
        matches an uninterrupted fused run of the same stream state."""
        idx = self._segment_indices(TRAIN, skip=skip)
        base = self._dropout_base_key()
        first = skip // self.loader.max_minibatch_size
        keys = _fold_keys(base, jnp.arange(first, first + idx.shape[0]))
        out = self._train_segment(params, states, idx, keys)
        if self.per_token:
            self.publish_step_stats(out[0])
        return out

    # -- compilation hooks (overridden by parallel trainers) ---------------
    # signatures: train fn(data_args, params, states, idx, keys),
    #             eval fn(data_args, params, idx); idx is what
    #             _index_operand made: an array, or a trainer's pytree

    def _compile_train(self, fn):
        return jax.jit(fn, donate_argnums=(1, 2) if self.donate else ())

    def _compile_eval(self, fn):
        return jax.jit(fn)

    # -- parameter plumbing ------------------------------------------------

    @profiler.phased("params_place")
    def pull_params(self):
        """Unit Arrays -> device pytrees (one-time HBM residency).

        In offloaded mode the returned pytrees are HOST numpy masters
        instead (the pinned out-of-core copy); the staging ring uploads
        layer groups from them per step."""
        if self.offloaded:
            return self._pull_params_host()
        params = tuple(fwd.param_values() for fwd in self.forwards)
        states = []
        for i, fwd in enumerate(self.forwards):
            gd = self.gd_for.get(id(fwd))
            if gd is not None and params[i]:
                if gd.opt_state is None:
                    gd.opt_state = get_solver(gd.solver_name).init(
                        fwd.gradient_params(params[i]))
                states.append(gd.opt_state)
            else:
                states.append({})
        return params, tuple(states)

    def _pull_params_host(self):
        """Unit Arrays -> HOST numpy masters (out-of-core residency).

        Params stay off the device entirely — ``map_read`` copies give
        the engine mutable masters and ``release_devmem`` drops any
        stale device mirror so the ring owns all HBM traffic. Restored
        opt states (which a snapshot may hand back as jax arrays) are
        normalized to numpy so a later upload sees uniform leaves."""
        t0 = time.perf_counter()
        params = []
        for fwd in self.forwards:
            layer = {}
            for k, arr in fwd.param_arrays().items():
                layer[k] = numpy.array(arr.map_read())
                arr.release_devmem()
            params.append(layer)
        states = []
        for i, fwd in enumerate(self.forwards):
            gd = self.gd_for.get(id(fwd))
            if gd is not None and params[i]:
                if gd.opt_state is None:
                    gd.opt_state = get_solver(gd.solver_name).init(
                        fwd.gradient_params(params[i]))
                gd.opt_state = jax.tree_util.tree_map(
                    numpy.asarray, gd.opt_state)
                states.append(gd.opt_state)
            else:
                states.append({})
        tracing.add_complete("offload:pin", t0,
                             time.perf_counter() - t0)
        return tuple(params), tuple(states)

    def checkpoint_records(self, params, states):
        """``[(spec, leaf)]`` for a sharded checkpoint of the live
        training state (``snapshotter.save_snapshot_sharded``).

        Deterministic order (forward index, sorted keys/paths) so every
        SPMD process emits the SAME record list and per-process part
        files line up shard-for-shard. Specs are the layout
        ``snapshotter._apply_record`` installs back into a restored
        workflow's unit Arrays / GD opt states."""
        records = []
        for i, layer in enumerate(params):
            for name in sorted(layer):
                records.append(({"kind": "param", "forward": i,
                                 "name": name}, layer[name]))

        def walk(i, node, path):
            if isinstance(node, dict):
                for key in sorted(node):
                    walk(i, node[key], path + [key])
                return
            records.append(({"kind": "opt", "forward": i,
                             "path": path}, node))

        for i, state in enumerate(states):
            if state:
                walk(i, state, [])
        return records

    @profiler.phased("params_place")
    def push_params(self, params, states):
        """Device pytrees -> unit Arrays (after training).

        Offloaded runs hand back HOST masters: those go through
        ``Array.reset`` (replacing the host buffer, no device mirror)
        instead of ``assign_devmem``."""
        for fwd, p, s in zip(self.forwards, params, states):
            for k, arr in fwd.param_arrays().items():
                if self.offloaded:
                    arr.reset(numpy.array(p[k]))
                else:
                    arr.assign_devmem(p[k])
            gd = self.gd_for.get(id(fwd))
            if gd is not None:
                gd.opt_state = s

    # -- index plumbing ----------------------------------------------------

    def _segment_indices(self, klass, skip=0):
        """(n_batches, mb) int32 index matrix for a class, padded -1.

        ``skip`` drops the class's first samples — a mid-epoch snapshot
        resume serves only the REMAINING minibatches through the same
        scan (``veles/snapshotter.py:387-409`` resume semantics;
        minibatch boundaries are class-aligned, so ``skip`` is a
        multiple of the minibatch size)."""
        loader = self.loader
        ends = loader.class_end_offsets
        start = ends[klass] - loader.class_lengths[klass] + skip
        seg = numpy.asarray(
            loader.shuffled_indices.map_read()[start:ends[klass]],
            numpy.int32)
        mb = loader.max_minibatch_size
        n_batches = (len(seg) + mb - 1) // mb
        mat = numpy.full((max(n_batches, 1), mb), -1, numpy.int32)
        flat = mat.reshape(-1)
        flat[:len(seg)] = seg
        return mat

    # -- driving -----------------------------------------------------------

    def run_epoch(self, params, states, epoch):
        """One epoch: eval classes in reference order, then train."""
        with profiler.epoch_phase(epoch):
            stats = {}
            for klass in (TEST, VALIDATION):
                if not self.loader.class_lengths[klass]:
                    continue
                losses, metrics, conf = self.eval_class(params, klass)
                if conf is not None:
                    self.evaluator.confusion_matrix = numpy.asarray(conf)
                stats[CLASS_NAMES[klass]] = self._summarize(
                    losses, metrics, klass)
            if self.loader.class_lengths[TRAIN]:
                t0 = time.perf_counter()
                params, states, losses, metrics = self.train_class(
                    params, states)
                stats[CLASS_NAMES[TRAIN]] = self._summarize(
                    losses, metrics, TRAIN)
                # _summarize forced the sync, so this elapsed covers the
                # whole sweep — the live-view gauges + MFU ride on it
                self._publish_live(stats[CLASS_NAMES[TRAIN]],
                                   time.perf_counter() - t0)
                self.loader.epoch_number = epoch + 1
                if self.loader.epoch_number <= self.loader.shuffle_limit:
                    self.loader.shuffle()
            return params, states, stats

    def _publish_live(self, train_stats, elapsed_s):
        """The live job view (ISSUE 19) for the class-level loop:
        FusedRunner publishes the same families on the launcher path;
        this keeps runs driving :meth:`run_epoch` directly (elastic
        workers, scheduled gangs) feeding the federation plane too."""
        from veles_tpu.telemetry import profiler
        from veles_tpu.telemetry.registry import get_registry
        registry = get_registry()
        registry.gauge(
            "veles_train_loss",
            "Last training batch loss").set(train_stats["loss"])
        if elapsed_s > 0:
            registry.gauge(
                "veles_train_samples_per_s",
                "Samples served per second over the last epoch").set(
                train_stats["samples"] / elapsed_s)
        profiler.get_cost_book().record_step_mfu(
            getattr(self, "_op_prefix", "") + "train_segment",
            elapsed_s)

    def publish_step_stats(self, params):
        """Registry gauges of what the last train sweep's steps handed
        out of the scan (:attr:`last_step_stats`): each side branch's
        loss, and whatever a unit publishes of its own stats
        (``fwd.publish_stats``; the trainer knows no unit's)."""
        observed = self.last_step_stats
        if not observed:
            return
        from veles_tpu.telemetry.registry import get_registry
        registry = get_registry()
        branch_loss = registry.gauge(
            "veles_branch_loss", "Mean loss of a side branch of the "
            "objective over the last train sweep", labels=("branch",))
        for branch, losses in observed["losses"].items():
            branch_loss.labels(branch=branch).set(float(jnp.mean(losses)))
        for i, fwd in enumerate(self.forwards):
            stats = observed["stats"].get(unit_tag(i, fwd))
            if stats:
                fwd.publish_stats(registry, unit_tag(i, fwd), stats,
                                  params[i])

    def _summarize(self, losses, metrics, klass):
        n = self.loader.class_lengths[klass]
        metric_sum = float(jnp.sum(metrics))
        return {"samples": n, "metric": metric_sum,
                "normalized": metric_sum / max(n, 1),
                "loss": float(jnp.mean(losses))}

    def train(self, max_epochs=None, epoch_callback=None,
              initial_state=None):
        """Full training loop with the decision unit's stop criterion.

        ``epoch_callback`` (or the :attr:`epoch_callback` attribute)
        fires after each epoch's bookkeeping closes — with the live
        ``(trainer, params, states)`` — which is exactly the complete
        step boundary an elastic checkpoint must be cut at. A restored
        workflow resumes transparently: the loop starts from the
        loader's ``epoch_number`` and the decision's restored history/
        best-state carry the stop criterion forward.
        ``initial_state`` accepts an already-pulled ``(params,
        states)`` so a caller that needed them before the loop (the
        elastic generation-initial checkpoint) does not pay the
        host→device placement twice."""
        decision = self.decision
        max_epochs = max_epochs if max_epochs is not None \
            else decision.max_epochs
        callback = (epoch_callback if epoch_callback is not None
                    else self.epoch_callback)
        params, states = (initial_state if initial_state is not None
                          else self.pull_params())
        epoch = self.loader.epoch_number
        start = time.perf_counter()
        while True:
            params, states, stats = self.run_epoch(params, states, epoch)
            stats["epoch"] = epoch
            decision.epoch_history.append(stats)
            key = ("validation" if self.loader.class_lengths[VALIDATION]
                   else "train")
            metric = stats[key]["normalized"]
            if metric < decision.best_metric:
                decision.best_metric = metric
                decision.best_epoch = epoch
                decision.improved <<= True
            else:
                decision.improved <<= False
            self.info("epoch %d: %s", epoch, "  ".join(
                "%s=%.4f" % (k, v["normalized"])
                for k, v in stats.items() if isinstance(v, dict)))
            if callback is not None:
                callback(self, params, states)
            epoch += 1
            if max_epochs is not None and epoch >= max_epochs:
                break
            # same inequality as DecisionBase._on_epoch_finished, where
            # epoch_number is the epoch just completed (= epoch - 1 here)
            if (epoch - 1) - decision.best_epoch > decision.fail_iterations:
                break
        elapsed = time.perf_counter() - start
        decision.complete <<= True
        self.workflow.stopped <<= True
        self.push_params(params, states)
        self.shutdown()
        n_train = self.loader.class_lengths[TRAIN]
        epochs_done = len(decision.epoch_history)
        self.info("fused training: %d epochs in %.2fs (%.0f samples/s)",
                  epochs_done, elapsed,
                  epochs_done * n_train / max(elapsed, 1e-9))
        return decision.epoch_history
