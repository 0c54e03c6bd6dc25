"""Mixture-of-experts FFN unit (``{"type": "moe"}`` layer).

One unit, two dispatches. ``capacity_factor`` a number: the Switch
top-1 layer with a fixed capacity and dropped overflow, as below.
``capacity_factor=None``: the DROPLESS layer of the DeepSeek-V3 line:
pre-norm, ``top_k`` experts a token by softmax or sigmoid scores, a
selection-only bias that no gradient reaches, normalised and scaled
weights, SiLU-gated experts, shared experts that see every token, and
``experts_held=(first, count)``: the router scores ALL ``n_experts``
while this chip holds, and computes, only ``count`` of them.

Wraps :func:`veles_tpu.parallel.ep.moe_ffn` the way the attention unit
wraps ring attention: a plain ForwardBase whose ``apply`` is pure, so
the fused step compiler, the eager scheduler, and the generic vjp GD
unit all drive it unchanged. Without a mesh it computes the dense
single-device math; ``use_experts(mesh)`` switches to the
expert-parallel all_to_all schedule (transient state — reattach after
snapshot resume, like ``MultiHeadAttentionForward.use_ring``).

The 2015 reference predates MoE; this extends the Znicz layer family
per the task brief's first-class-parallelism requirement.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Array
from veles_tpu.nn.base import ForwardBase
from veles_tpu.nn.mlp import gated_mlp
from veles_tpu.nn.normalization import rms_norm
from veles_tpu.nn.precision import get_policy
from veles_tpu.telemetry.registry import get_registry


class MoEForward(ForwardBase):
    """Switch-style top-1 MoE FFN over (batch, seq, dim) or (n, dim).

    Parameters: ``weights`` is the ROUTER (dim, n_experts) — reusing
    the base class's allocation/filling — plus per-expert ``up``
    (E, dim, hidden) and ``down`` (E, hidden, dim) stacks.

    Dropless (``capacity_factor=None``): ``x + Shared(h) + sum_k w_k
    E_k(h)``, ``h = rms_norm(x)``. ``s = score(h weights)`` in float32
    (``scoring``: ``softmax`` or ``sigmoid``); the ``top_k`` largest
    ``s + select_bias`` are chosen; ``w`` is ``s`` at the chosen,
    divided by its sum + ``normalize_eps`` (1e-20) if ``normalize``,
    times ``scale``. The
    sum runs over the chosen experts that are HELD, ``experts_held =
    (first, count)`` (default: all). Further parameters: ``norm``,
    ``select_bias`` (n_experts; :attr:`non_gradient`:
    :meth:`update_state` moves it by ``bias_rate * sign(mean(c) - c)``
    after a train step, ``c`` the step's tokens to each expert),
    ``gate`` beside ``up``/``down`` (held, ...), and
    ``shared_gate/up/down`` (``shared_experts``, ...).

    No token is dropped, for any routing. Held assignments are sorted
    by expert and the rows go through ONE grouped product a matrix
    (``lax.ragged_dot``). The combine stays in that sorted domain:
    row ``r`` is added, under its weight and in the accumulation
    dtype, into the token it came from, ONE scatter-add of ``rows``
    rows whose transpose is one gather; an assignment whose expert is
    not held has no row, and no ``(tokens, top_k, dim)`` array exists
    in either pass. ``dispatch_rows`` bounds the rows; a step whose
    routing sends more rows here takes, through ``lax.cond``, the same
    code at the bound that cannot be passed, ``tokens * min(top_k,
    count)``; the gauge ``veles_moe_combine_rows{unit}`` is the bound
    in force. On the device the routing (scores, top-k, sort, the
    dispatch gather and the combine) runs under the sub-scope
    ``route``, the grouped products under ``experts``, the shared
    experts under ``shared``.
    """

    def __init__(self, workflow, n_experts=8, hidden=None,
                 capacity_factor=1.25, residual=True,
                 aux_loss_weight=0.0, top_k=1, scoring="softmax",
                 normalize=False, scale=1.0, shared_experts=0,
                 experts_held=None, bias_rate=0.0, dispatch_rows=None,
                 eps=1e-5, normalize_eps=1e-20, **kwargs):
        kwargs.setdefault("include_bias", False)
        super(MoEForward, self).__init__(workflow, **kwargs)
        self.n_experts = int(n_experts)
        self.hidden = hidden  # default: 4 * dim, set at initialize
        self.dropless = capacity_factor is None
        if self.dropless:
            self.non_gradient = ("select_bias",)
        self.capacity_factor = None if self.dropless \
            else float(capacity_factor)
        if not self.dropless and (top_k != 1 or shared_experts
                                  or experts_held):
            raise ValueError(
                "top_k, shared_experts and experts_held belong to the "
                "dropless layer: pass capacity_factor=None")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError("unknown scoring %r" % (scoring,))
        self.top_k, self.scoring = int(top_k), scoring
        self.normalize, self.scale = bool(normalize), float(scale)
        self.normalize_eps = float(normalize_eps)
        self.shared_experts = int(shared_experts)
        first, count = experts_held or (0, self.n_experts)
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError("experts_held %r of %d experts"
                             % (experts_held, self.n_experts))
        self.experts_held = (int(first), int(count))
        self.bias_rate = float(bias_rate)
        self.dispatch_rows = dispatch_rows
        self.eps = float(eps)
        self.residual = residual
        #: Switch load-balancing aux-loss weight, added to the FUSED
        #: training loss (opt-in: 0.0 keeps fused == eager numerics)
        self.aux_loss_weight = float(aux_loss_weight)
        self.up = Array()
        self.down = Array()
        #: the dropless layer's further parameters, by name
        self.extra = ()
        if self.dropless:
            self.extra = ("norm", "select_bias", "gate")
            if self.shared_experts:
                self.extra += ("shared_gate", "shared_up", "shared_down")
        for name in self.extra:
            setattr(self, name, Array())
        self._ep_mesh_ = None
        self._ep_axis_ = "expert"

    def use_experts(self, mesh, axis="expert"):
        """Attach an expert mesh: apply() switches to the all_to_all
        expert-parallel schedule (per-shard capacity semantics)."""
        if self.dropless:
            raise ValueError(
                "the dropless layer computes its held experts on its "
                "own chip: there is no exchange to attach")
        if mesh.shape[axis] != self.n_experts:
            raise ValueError(
                "%d experts cannot shard over a %d-wide %r axis" %
                (self.n_experts, mesh.shape[axis], axis))
        self._ep_mesh_ = mesh
        self._ep_axis_ = axis
        return self

    def init_unpickled(self):
        super(MoEForward, self).init_unpickled()
        self._ep_mesh_ = None
        self._ep_axis_ = "expert"

    def _placement_mesh(self):
        # base place_for_grad/param_values/_input_devmem re-place every
        # committed buffer onto the expert mesh (the all_to_all
        # shard_map rejects device-set mismatches otherwise)
        return self._ep_mesh_

    def weights_shape_for(self, input_shape):
        return (input_shape[-1], self.n_experts)

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def initialize(self, device=None, **kwargs):
        super(MoEForward, self).initialize(device=device, **kwargs)
        dim = self.input_shape[-1]
        if self.hidden is None:
            self.hidden = 4 * dim
        held, shared = self.experts_held[1], self.shared_experts
        stacks = {"up": (held, dim, self.hidden),
                  "down": (held, self.hidden, dim),
                  "gate": (held, dim, self.hidden),
                  "shared_gate": (shared, dim, self.hidden),
                  "shared_up": (shared, dim, self.hidden),
                  "shared_down": (shared, self.hidden, dim)}
        for name in ("up", "down") + self.extra:
            arr = getattr(self, name)
            if arr.mem is not None:
                continue
            if name == "norm":
                arr.reset(numpy.ones((dim,), numpy.float32))
            elif name == "select_bias":
                arr.reset(numpy.zeros((self.n_experts,), numpy.float32))
            else:
                arr.reset(numpy.zeros(stacks[name], numpy.float32))
                self.fill_matrices(arr.mem)
        self.init_vectors(self.up, self.down,
                          *(getattr(self, n) for n in self.extra))

    def param_arrays(self):
        out = super(MoEForward, self).param_arrays()
        for name in ("up", "down") + self.extra:
            out[name] = getattr(self, name)
        return out

    def param_values(self):
        out = super(MoEForward, self).param_values()
        out.update(self.place_for_grad(
            {name: getattr(self, name).devmem
             for name in ("up", "down") + self.extra}))
        return out

    # -- the dropless layer ------------------------------------------------

    def route(self, params, h):
        """``(chosen, weights, scores)`` of tokens ``h`` (tokens, dim)
        over ALL experts, in float32 at full precision: the ``top_k``
        expert ids a token, their combine weights, and every expert's
        score. ``lax.top_k`` takes the lower id on a tie. The
        selection bias picks and does not weigh."""
        logits = jnp.dot(h.astype(jnp.float32),
                         params["weights"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if self.scoring == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(params["select_bias"]),
            self.top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.normalize:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True)
                + self.normalize_eps)
        return chosen, weights * self.scale, scores

    def _held_experts(self, pol, params, h, order, sizes, weights, rows):
        """The held experts' part of the result for the first ``rows``
        sorted assignments: gather, three grouped products, and the
        combine in the rows' own domain: row ``r`` belongs to token
        ``order[r] // k`` and is added there under its weight, one
        scatter-add of ``rows`` rows (its transpose: one gather of as
        many). Exact when ``sum(sizes) <= rows``."""
        tokens, k = weights.shape
        with jax.named_scope("route"):
            live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
            picked = order[:rows]
            token = picked // k
            xs = jnp.where(live, pol.cast_in(h)[token], 0)
        with jax.named_scope("experts"):
            def grouped(lhs, name):
                # rows past the last group are whatever the kernel
                # left there: zeroed BEFORE any nonlinearity, so that
                # neither pass multiplies by them
                return jnp.where(live, jax.lax.ragged_dot(
                    lhs, pol.cast_in(params[name]), sizes,
                    preferred_element_type=pol.accum_dtype), 0)
            hidden = jax.nn.silu(grouped(xs, "gate")) * grouped(xs, "up")
            out = grouped(pol.cast_in(hidden), "down")
        with jax.named_scope("route"):
            # a row past the live ones weighs nothing; an assignment
            # whose expert is not held has no row and adds nothing
            weight = jnp.where(live, weights.reshape(-1)[picked][:, None],
                               0).astype(pol.accum_dtype)
            # the product under a checkpoint of its own, so that the
            # weights' gradient reads the rows as they are (compute
            # dtype) and not a copy widened to ``weight``'s
            weighted = jax.checkpoint(jnp.multiply)(weight, pol.cast_in(out))
            return jnp.zeros((tokens, out.shape[1]),
                             pol.accum_dtype).at[token].add(weighted)

    def _dropless(self, params, x):
        """``(y, counts)``: the layer's output and the tokens routed
        to each of ALL experts by this batch, int32."""
        pol = get_policy()
        first, count = self.experts_held
        with jax.named_scope("route"):
            h = rms_norm(x, params["norm"], self.eps).reshape(
                -1, x.shape[-1])
            chosen, weights, _ = self.route(params, h)
            tokens, k = chosen.shape
            flat = chosen.reshape(-1)
            counts = jnp.sum(jax.nn.one_hot(
                flat, self.n_experts, dtype=jnp.int32), axis=0)
            local = flat - first
            held = (local >= 0) & (local < count)
            # held assignments first, by expert; the rest behind them
            order = jnp.argsort(jnp.where(held, local, count),
                                stable=True)
            sizes = jax.lax.dynamic_slice(counts, (first,), (count,))
        full = tokens * min(k, count)
        bound = min(int(self.dispatch_rows or full), full)
        get_registry().gauge(
            "veles_moe_combine_rows", "Rows a step that the sparse "
            "unit's combine adds into its tokens on the bounded path: "
            "one a sorted assignment, not one a (token, slot)",
            labels=("unit",)).labels(unit=self.name).set(float(bound))
        run = functools.partial(self._held_experts, pol, params, h, order,
                                sizes, weights)
        if bound < full:
            y = jax.lax.cond(jnp.sum(sizes) <= bound,
                             lambda: run(bound), lambda: run(full))
        else:
            y = run(full)
        if self.shared_experts:
            with jax.named_scope("shared"):
                for i in range(self.shared_experts):
                    y = y + gated_mlp(
                        pol, h, params["shared_gate"][i],
                        params["shared_up"][i], params["shared_down"][i])
        y = y.reshape(x.shape)
        if self.residual:
            y = y + x.astype(y.dtype)
        return pol.cast_out(y), counts

    def apply_step(self, params, x, ctx):
        """What a fused step calls: ``(y, stats)``, ``stats`` the
        step's ``expert_counts`` over all experts."""
        if not self.dropless:
            return self.apply(params, x), {}
        y, counts = self._dropless(params, x)
        return y, {"expert_counts": counts}

    def update_state(self, params, stats):
        """The selection bias after a train step: up for an expert
        that got fewer tokens than the mean, down for one that got
        more (DeepSeek-V3's auxiliary-loss-free balancing). In a
        deployment the counts are summed over the expert-parallel
        group first; one chip has its own tokens' counts."""
        if not self.bias_rate or "expert_counts" not in stats:
            return {}
        counts = stats["expert_counts"].astype(jnp.float32)
        return {"select_bias": params["select_bias"] + self.bias_rate
                * jnp.sign(jnp.mean(counts) - counts)}

    def publish_stats(self, registry, tag, stats, params):
        """Of a train sweep's ``expert_counts``: the tokens a step
        routed to each expert HELD here (mean over the steps), the
        largest of those over their mean, the tokens a step routed over
        ALL the experts (tokens x top_k exactly: nothing is dropped),
        and the largest selection bias."""
        if "expert_counts" not in stats:
            return
        counts = numpy.asarray(stats["expert_counts"], numpy.float64)
        first, count = self.experts_held
        held = counts[:, first:first + count].mean(axis=0)
        tokens = registry.gauge(
            "veles_moe_expert_tokens", "Tokens a step routed to an "
            "expert held here, mean over the last train sweep",
            labels=("unit", "expert"))
        for local, value in enumerate(held):
            tokens.labels(unit=tag, expert=str(first + local)).set(
                float(value))
        registry.gauge(
            "veles_moe_load_max_over_mean", "Largest over mean of the "
            "held experts' tokens a step", labels=("unit",)).labels(
            unit=tag).set(float(held.max() / max(held.mean(), 1e-9)))
        registry.gauge(
            "veles_moe_routed_per_step", "Tokens x top_k a step routed "
            "over all experts, held or not", labels=("unit",)).labels(
            unit=tag).set(float(counts.sum(axis=1).mean()))
        registry.gauge(
            "veles_moe_select_bias_max", "Largest |selection bias|",
            labels=("unit",)).labels(unit=tag).set(float(jnp.max(
                jnp.abs(params["select_bias"]))))

    def apply(self, params, x):
        from veles_tpu.parallel.ep import moe_ffn, moe_ffn_reference

        if self.dropless:
            return self._dropless(params, x)[0]
        if self._ep_mesh_ is not None:
            tokens = x.reshape(-1, x.shape[-1])
            y = moe_ffn(tokens, params["weights"], params["up"],
                        params["down"], self._ep_mesh_, self._ep_axis_,
                        capacity_factor=self.capacity_factor
                        ).reshape(x.shape)
        else:
            # dense path: capacity pools PER SAMPLE, so inference is
            # batch-composition-independent (the same sample routes
            # identically whatever it shares a batch with) — matching
            # the native runtime exactly. Consequence: on 2D (n, dim)
            # inputs every sample is a single token and capacity
            # (>= 1) never drops anything — deliberate; capacity is a
            # sequence-length concept. The expert-parallel path above
            # pools per device shard instead (the Switch training
            # contract).
            per_sample = x.reshape(x.shape[0], -1, x.shape[-1])
            y = jax.vmap(lambda s: moe_ffn_reference(
                s, params["weights"], params["up"], params["down"],
                self.n_experts, capacity_factor=self.capacity_factor,
                n_shards=1))(per_sample).reshape(x.shape)
        if self.residual:
            y = y + x
        return y.astype(x.dtype)

    def aux_loss(self, params, x, valid=None):
        """weight * Switch load-balance loss over this batch's router
        probabilities — the FusedTrainer adds it to the training loss
        when ``aux_loss_weight`` > 0. Router math identical to the
        dispatch path, so the nudged distribution is the served one;
        ``valid`` (per-SAMPLE mask) keeps a tail batch's zero padding
        rows out of the balance statistics."""
        from veles_tpu.parallel.ep import load_balance_loss
        tokens = x.reshape(-1, x.shape[-1])
        probs = jax.nn.softmax(tokens @ params["weights"], axis=-1)
        weights = None
        if valid is not None:
            per_sample = tokens.shape[0] // x.shape[0]
            weights = jnp.repeat(valid.astype(probs.dtype), per_sample)
        return self.aux_loss_weight * load_balance_loss(probs, weights)
