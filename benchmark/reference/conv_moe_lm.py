"""Plain float32 reference of the gated short-convolution and
grouped-query attention, sparse-expert language-model family with one
table for embedding and head (the block LFM2-8B-A1B's ``lfm2_moe``
configuration describes), from a configuration's layer list.

Straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; no unit of the program is
imported: the layer descriptors and the parameter arrays are all it
takes from the system under test. The forward pass, the loss and,
through ``jax.grad`` of that, the gradients; one Adam step and the
selection bias's rule. What it has in common with
``reference/moe_lm.py`` (the gated MLP, the head's chunked
cross-entropy, Adam's first step, the bias rule, the layout of a step
and its comparison) and with ``reference/window_moe_lm.py`` (RMSNorm,
the rotary table) is imported from there.

The equations (all matrices without bias; ``n = norm(x)``):

* RMSNorm: ``x * rsqrt(mean(x^2) + eps) * g``, ``eps`` 1e-5 published.
* A block, pre-norm with the residual inside each half: ``h = x +
  Mix(norm(x))``, ``y = h + FFN(norm(h))``; ``Mix`` by the block's
  entry in the published ``layer_types``.
* Short convolution: ``[B, C, X] = n Win`` (``dim -> 3 dim``, split in
  that order), ``u = B * X``, ``v_t = sum_{j=0..L-1} w[:, j] u_{t - (L
  - 1) + j}`` (depthwise over the sequence, ``L`` = ``taps`` 3, one
  filter a channel, ``u`` before position 0 is 0, no bias), ``Mix =
  (C * v) Wout``. Computed lag by lag on copies of ``u`` moved down
  the sequence behind as many zeros.
* Attention with ``H`` query heads on ``KV`` key/value heads of ``d``:
  ``q = n Wq``, ``k = n Wk``, ``v = n Wv``; an RMSNorm over every
  query head and one over every key head (one gain of ``d`` each);
  rotary embedding over the whole head; query head ``j`` reads
  key/value head ``j // (H / KV)`` (``jnp.repeat``); scores over
  ``sqrt(d)``; query ``i`` sees key ``j`` iff ``j <= i`` (an explicit
  mask); softmax; heads concatenated, ``Wo``. No window, no gate.
* Gated MLP and every expert: ``Wdown(silu(Wgate x) * Wup x)``.
* Router: ``s = sigmoid(n Wr)`` over all the experts; the ``top_k``
  largest ``s + b`` (``b`` the selection bias: in the choice only);
  weights ``s`` at the chosen, over their sum + ``normalize_eps``
  (1e-6), times ``scale`` (1); the sum over the chosen experts that
  are among the HELD. No shared expert.
* The head: ``logits = x table^T``, ``table`` the embedding's, passed
  ONCE: ``jax.grad`` sums its two readers' gradients.
* Objective: the mean cross-entropy over all positions.
* One train step (:func:`train_step`): the objective's gradient,
  Adam's first step from zero moments, the bias moved by ``bias_rate *
  sign(mean(c) - c)``. This is what :func:`agreement` holds the timed
  program to.

Departures from the published description, each stated where it is
computed: the chip's share (held experts, the vocabulary slice) as in
``reference/moe_lm.py``; the bias rule's counts are this chip's
tokens' (a deployment sums them over the group). ASSUMED, because the
configuration names none of them (the configuration file's
``assumed`` has each with its reason): the tied table, the split order
``B, C, X``, zero history before position 0, rotate-half pairing, the
q/k RMSNorm, sigmoid scores and the ``1e-6``, the bias's rule and
rate. Attention runs a block of queries at a time over an explicit
mask of the whole row of keys, one block after the other
(``lax.map``), and the head a chunk of tokens at a time, so that a
sequence of 8,192 positions fits.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from benchmark.reference import moe_lm
from benchmark.reference.moe_lm import (  # noqa: F401 (the driver's)
    gated, head_losses, stepped)
from benchmark.reference.window_moe_lm import rms_norm, rope

CONV, ATTENTION = "short_conv", "grouped_attention"

#: What decides ``correct`` is ONE TRAIN STEP of the timed program from
#: the initial weights and a fresh optimizer state, on one batch of the
#: train set, against :func:`train_step` here, as in
#: ``reference/moe_lm.py``. The program computes in bfloat16
#: (activations rounded to 8 bits of mantissa between and inside the
#: units; parameters, accumulation, the gates, the taps' sum and the
#: routers float32).
#:
#: The v5e's readings (PERF.md section 6, PR 37, my chip runs). The
#: limits were set from the first three of the program's (call 2:
#: seeds 2147483777, 3000000037, 77770037) and the int8 reference's on
#: seed 2147483777 (both operands of every product of the forward and
#: the backward pass but the routers' rounded to 8 bits,
#: ``scripts/lm_tolerance_probe.py --cell
#: lfm2-8b-a1b-ep4share.pretrain8k-1seq``); the six after them are the
#: final tree's (call 3), seeds not used while the change was written.
READINGS = {
    "program": {
        "gradient_error": [0.041431, 0.041936, 0.039355, 0.041531,
                           0.040164, 0.037010, 0.039163, 0.039401,
                           0.042411],
        "table_gradient_error": [0.040905, 0.041510, 0.039055, 0.041086,
                                 0.039762, 0.036686, 0.038795, 0.038996,
                                 0.042017],
        "taps_gradient_error": [0.044971, 0.044403, 0.039429, 0.043358,
                                0.042738, 0.038674, 0.041592, 0.040572,
                                0.042343],
        "update_error": [0.314059, 0.313974, 0.304279, 0.316734, 0.303233,
                         0.304870, 0.304147, 0.297334, 0.320200],
        "update_scale_error": [7.9e-6, 5.7e-7, 1.4e-6, 1.1e-5, 2.1e-6,
                               1.3e-5, 1.5e-5, 9.4e-6, 1.6e-6]},
    "int8": {"gradient_error": 0.097650, "table_gradient_error": 0.096487,
             "taps_gradient_error": 0.104043, "update_error": 0.542190,
             "update_scale_error": 2.7e-5},
}

#: ``GRADIENT_TOLERANCE`` bounds ``gradient_error``, the L2 distance
#: between the program's and the reference's gradient over ALL the
#: parameters, over the reference's norm (read off Adam's first
#: moment, ``m = (1 - beta1) g`` after one step from zero). THE
#: PRECISION LIMIT: the geometric mean of the program's largest
#: reading then (0.041936) and the int8 reference's (0.097650): 1.53
#: times of room above the program, 1.53 below int8 (the same seed's
#: two readings are 2.36 times apart, as in the other token cells;
#: the largest of nine seeds is 0.042411: 1.51 times of room).
#: Both readings are three to eight times the other token cells' (the
#: Keye cell: 0.0049 and 0.0174) in the same ratio: it is this model
#: at its initial weights that passes more of a product's rounding on
#: to its gradient, not this program's lowering (PERF.md sections 6,
#: 7: which part of the model, is open). Both carry the routing's own
#: noise: 0.16-0.20% of a step's token-to-expert assignments fall the
#: other way in bfloat16 (0.47% in int8), and the routers' weights
#: read 21-23% on their own.
GRADIENT_TOLERANCE = 0.064

#: ``TABLE_GRADIENT_TOLERANCE`` bounds ``table_gradient_error``, the
#: same distance over the ONE table alone: the embedding's rows and
#: the head's columns are one array with two readers, and a reader
#: whose gradient is dropped reads near 1 there (the head's is the
#: dense part, the embedding's the rows the batch holds) while an L2
#: over 0.5 G numbers would not notice. The geometric mean of the
#: program's largest reading (0.041510) and int8's (0.096487).
TABLE_GRADIENT_TOLERANCE = 0.063

#: ``TAPS_GRADIENT_TOLERANCE`` bounds ``taps_gradient_error``, the same
#: distance over the ``dim x taps`` filter weights of every
#: short-convolution block alone (6,144 numbers a block): a tap
#: shifted the wrong way or a wrong history before position 0 reads
#: near 1 there. The geometric mean of the program's largest reading
#: (0.044971) and int8's (0.104043).
TAPS_GRADIENT_TOLERANCE = 0.068

#: ``UPDATE_TOLERANCE`` bounds ``update_error``, the L2 distance of the
#: two parameter changes over the reference's norm; a state left
#: unchanged reads 1. Adam's first step moves a weight by the rate
#: times its gradient's SIGN, so the reading is ``2 sqrt(share of
#: signs that differ)``, 2.3-2.5% of the signs here (the weights whose
#: gradient is within the gradient's own error of zero), and tells a
#: wrong direction, not a precision (int8 reads 0.54, inside it;
#: ``reference/moe_lm.py`` has the argument). Between the largest
#: reading (0.314; 0.320 over nine seeds) and 1, the more room above
#: the reading.
UPDATE_TOLERANCE = 0.70

#: ``UPDATE_SCALE_TOLERANCE`` bounds ``update_scale_error``, |the norm
#: of the program's changes over the norm of the reference's - 1|: the
#: RATE, which a sign cannot move (a rate twice too large reads 1).
#: What is left at 6e-7 to 1.5e-5 is the weights whose gradient is
#: of epsilon's size.
UPDATE_SCALE_TOLERANCE = 0.01

#: queries to a block of attention
Q_BLOCK = 512


def short_conv(descr, p, x):
    """``x + (C * v) Wout``. ASSUMED: the split order ``B, C, X`` and
    zeros before position 0 (a causal padding of ``taps - 1``)."""
    taps, seq = descr.get("taps", 3), x.shape[1]
    n = rms_norm(x, p["norm"], descr["eps"])
    b, c, xs = jnp.split(n @ p["in"], 3, axis=-1)
    u = b * xs
    v = jnp.zeros_like(u)
    for lag in range(taps):  # tap ``taps - 1 - lag`` reads ``lag`` back
        behind = jnp.concatenate(
            [jnp.zeros_like(u[:, :lag]), u[:, :seq - lag]], 1)
        v = v + p["taps"][:, taps - 1 - lag] * behind
    if descr.get("bias"):
        v = v + p["taps_bias"]
    return x + (c * v) @ p["out"]


def attention_core(q, k, v, scale):
    """Softmax attention under the explicit causal mask, (batch, seq,
    heads, dim) operands of as many heads, a block of ``Q_BLOCK``
    queries at a time over ALL the keys of the row, one block after
    the other. ``jax.checkpoint`` changes no value: it says what the
    gradient keeps."""
    batch, seq = q.shape[:2]
    rows = min(Q_BLOCK, seq)
    if seq % rows:
        raise ValueError("%d positions are no whole blocks of %d"
                         % (seq, rows))

    @jax.checkpoint
    def block(operands):
        q_blk, start = operands
        seen = (start + jnp.arange(rows))[:, None] >= jnp.arange(seq)[None]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", prob, v)

    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape((batch, seq // rows, rows) + q.shape[2:]),
                     1, 0), jnp.arange(0, seq, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def grouped_attention(descr, p, x):
    """ASSUMED: an RMSNorm on every query and key head before the
    rotary embedding; rotate-half pairing over the whole head."""
    heads, head = descr["heads"], descr["head_dim"]
    kv_heads = descr.get("kv_heads") or heads
    batch, seq, _ = x.shape
    theta = descr.get("rope_theta", 1e4)
    n = rms_norm(x, p["norm"], descr["eps"])
    q = (n @ p["q"]).reshape(batch, seq, heads, head)
    k = (n @ p["k"]).reshape(batch, seq, kv_heads, head)
    if descr.get("qk_norm"):
        q = rms_norm(q, p["q_norm"], descr["eps"])
        k = rms_norm(k, p["k_norm"], descr["eps"])
    q, k = rope(q, theta), rope(k, theta)
    v = (n @ p["v"]).reshape(batch, seq, kv_heads, head)
    # query head j reads key/value head j // group
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    ctx = attention_core(q, k, v, 1.0 / math.sqrt(head))
    return x + ctx.reshape(batch, seq, heads * head) @ p["o"]


def gated_mlp(descr, p, x):
    return x + gated(rms_norm(x, p["norm"], descr["eps"]), p["gate"],
                     p["up"], p["down"])


def route(descr, p, h):
    """``(chosen (tokens, k), weights (tokens, k))`` over ALL the
    router's experts; ``lax.top_k`` takes the lower index on a tie.
    ASSUMED: sigmoid scores, the bias in the choice only, the sum's
    ``normalize_eps``."""
    scores = jax.nn.sigmoid(h @ p["weights"])
    _, chosen = jax.lax.top_k(scores + p["select_bias"], descr["top_k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    if descr.get("normalize"):
        picked = picked / (jnp.sum(picked, -1, keepdims=True)
                           + descr.get("normalize_eps", 1e-20))
    return chosen, picked * descr.get("scale", 1.0)


def moe(descr, p, x, residual=True):
    """The block's sparse half with the experts HELD here (DEPARTURE:
    the chip's share; what the absent experts would add is left out).
    ``residual=False`` leaves the residual out, for the test that adds
    the shares up."""
    first, count = descr.get("experts_held") or (0, descr["n_experts"])
    h = rms_norm(x, p["norm"], descr["eps"]).reshape(-1, x.shape[-1])
    chosen, weights = route(descr, p, h)
    # (tokens, held): a held expert's weight for the token, 0 where
    # the token did not choose it; every held expert sees every token
    w = jnp.sum(jnp.where(
        chosen[..., None] == first + jnp.arange(count), weights[..., None],
        0.0), 1)
    y = jnp.zeros_like(h)
    for e in range(count):  # one expert at a time
        y = y + w[:, e:e + 1] * gated(h, p["gate"][e], p["up"][e],
                                      p["down"][e])
    y = y.reshape(x.shape)
    return x + y if residual else y


def expert_counts(descr, p, x):
    """Tokens routed to each of the router's experts (all of them)."""
    chosen, _ = route(descr, p, rms_norm(
        x, p["norm"], descr["eps"]).reshape(-1, x.shape[-1]))
    return jnp.zeros(descr["n_experts"], jnp.int32).at[
        chosen.reshape(-1)].add(1)


UNITS = {CONV: short_conv, ATTENTION: grouped_attention,
         "gated_mlp": gated_mlp, "moe": moe,
         "rms_norm": lambda descr, p, x: rms_norm(
             x, p["weights"], descr.get("eps", 1e-5))}


def states(layers, params, tokens, counts=None):
    """The state the head reads. ``tokens``: (batch, positions + 1)
    ids. ``counts``, a dict, is filled with every sparse layer's
    :func:`expert_counts`, keyed 0, 1, ... in the layers' order."""
    x = None
    for descr, p in zip(layers[:-1], params[:-1]):
        ltype = descr["type"]
        if ltype == "token_embedding":
            x = jnp.asarray(p["weights"])[tokens[:, :descr["positions"]]]
            continue
        if ltype == "moe" and counts is not None:
            counts[len(counts)] = expert_counts(descr, p, x)
        x = jax.checkpoint(
            lambda p, x, fn=UNITS[ltype], descr=descr: fn(descr, p, x))(
                p, x)
    return x


def head_matrix(layers, params):
    """What the head multiplies by, (dim, vocabulary held): its own
    weights, or under ``tied_to`` the transposed table of the
    embedding of that name (ASSUMED tied: the published 8.3 B total
    has room for one table)."""
    tied = layers[-1].get("tied_to")
    if not tied:
        return params[-1]["weights"]
    names = [d.get("name", "%s%d" % (d["type"], i))
             for i, d in enumerate(layers)]
    return jnp.asarray(params[names.index(tied)]["weights"]).T


def logits(layers, params, tokens):
    """(batch, positions, vocabulary held)."""
    return states(layers, params, tokens) @ head_matrix(layers, params)


def objective(layers, params, tokens, labels, counts=None,
              head_table=None):
    """``(total, {"main": CE})`` of a batch: what the train step
    differentiates. ``labels[:, t]`` is the id after position ``t``.
    ``head_table`` stands in for the table the head reads, so that a
    test can tell the two readers' gradients apart."""
    x = states(layers, params, tokens, counts=counts)
    head = head_matrix(layers, params) if head_table is None \
        else jnp.asarray(head_table).T
    main = jnp.mean(head_losses(head, x, labels[:, :x.shape[1]]))
    return main, {"main": main}


def step_function(layers):
    """``f(params, tokens, labels) -> (gradients, losses, counts)`` of
    one batch, to be jitted."""
    layers = [dict(d) for d in layers]

    def fn(params, tokens, labels):
        def loss(p):
            counts = {}
            total, terms = objective(layers, p, tokens, labels, counts)
            return total, (terms, counts)
        (_, (terms, counts)), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        return grads, terms, counts
    return fn


def gradients(layers, params, tokens, labels):
    """The objective's gradient to every parameter, by ``jax.grad``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(step_function(layers))(params, tokens, labels)[0]


def train_step(layers, params, tokens, labels, optimizer, fn=None):
    """ONE TRAIN STEP from ``params`` (host arrays) and a fresh
    optimizer state on one batch, everything back on the host, laid
    out by ``reference/moe_lm.py`` ``stepped``. ``fn`` stands in for
    the jitted :func:`step_function` (the probe's lower-precision
    control)."""
    with jax.default_matmul_precision("highest"):
        fn = fn or jax.jit(step_function(layers))
        grads, terms, counts = jax.device_get(fn(
            jax.device_put(params), numpy.asarray(tokens),
            numpy.asarray(labels)))
    return stepped(layers, params, grads, terms, counts, optimizer)


def sample_losses(layers, params, data, labels):
    """Mean cross-entropy of each sequence (host arrays), one sequence
    to a call, kept in float64 on the host."""
    layers = [dict(d) for d in layers]

    @jax.jit
    def one(params, tokens, targets):
        return objective(layers, params, tokens, targets)[0]

    out = numpy.empty(len(data), numpy.float64)
    with jax.default_matmul_precision("highest"):
        params = jax.device_put(params)
        for i in range(len(data)):
            out[i] = one(params, numpy.asarray(data[i:i + 1]),
                         numpy.asarray(labels[i:i + 1]))
    return out


def validation_batch_losses(layers, params, data, labels, batch):
    """What the program's validation sweep reports from these
    parameters: the mean loss of each batch of ``batch`` sequences, in
    the order served."""
    per_sample = sample_losses(layers, params, data, labels)
    return numpy.array([per_sample[i:i + batch].mean()
                        for i in range(0, len(per_sample), batch)])


def _error_over(layers, program, expected, wanted):
    """The L2 distance of Adam's first moments over the arrays
    ``wanted(index, descr)`` names alone, over the reference's norm
    there."""
    num = den = 0.0
    for i, descr in enumerate(layers):
        for name in wanted(i, descr):
            n, d, _ = moe_lm._distance(program["moments"][i][name],
                                       expected["moments"][i][name])
            num, den = num + n, den + d
    return math.sqrt(num / max(den, 1e-300))


def step_comparison(layers, program, expected):
    """``reference/moe_lm.py``'s numbers, and this family's own, each
    over a few arrays that an L2 over all the parameters would drown:

    * ``table_gradient_error``: the one table's alone (the embedding's
      ``weights``, which the head reads too);
    * ``taps_gradient_error``: the filter weights of every
      short-convolution block."""
    out = moe_lm.step_comparison(layers, program, expected)
    out["table_gradient_error"] = _error_over(
        layers, program, expected,
        lambda i, d: ["weights"] if d["type"] == "token_embedding" else [])
    out["taps_gradient_error"] = _error_over(
        layers, program, expected,
        lambda i, d: ["taps"] if d["type"] == CONV else [])
    return out


def agreement(program_losses, reference):
    """``(ok, report)``: the driver's comparison. ``correct`` needs the
    step inside every limit above (each number there: a step that
    lacks one is not correct), every selection bias moved by the rule,
    and every token routed ``top_k`` times (nothing dropped)."""
    program = numpy.asarray(program_losses, numpy.float64)
    losses = numpy.asarray(reference["losses"], numpy.float64)
    if program.shape != losses.shape:
        return False, {"error": "shapes %s vs %s"
                       % (program.shape, losses.shape)}
    step = reference["step"]
    limits = {"gradient_error": GRADIENT_TOLERANCE,
              "table_gradient_error": TABLE_GRADIENT_TOLERANCE,
              "taps_gradient_error": TAPS_GRADIENT_TOLERANCE,
              "update_error": UPDATE_TOLERANCE,
              "update_scale_error": UPDATE_SCALE_TOLERANCE}
    # a number that was never computed is not inside its limit
    ok = (all(name in step and step[name] <= limit
              for name, limit in limits.items())
          and step["bias_error"] == 0
          and all(abs(r - k) < 1e-9 for r, k in zip(
              step["routed_per_token"], step["top_k"])))
    return bool(ok), dict(
        step, **{name.replace("_error", "_tolerance"): limit
                 for name, limit in limits.items()},
        validation_loss_error=float(numpy.max(numpy.abs(
            program - losses))),
        batch_mean_spread=float(numpy.std(losses)),
        batches=int(losses.size))
