"""Plain float32 reference of the grouped-query attention under a
learned selection of keys, sparse-expert language-model family (the
language model Keye-VL-2.0-30B-A3B's ``KeyeVL2`` configuration
describes), from a configuration's layer list.

Straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; no unit of the program is
imported: the layer descriptors and the parameter arrays are all it
takes from the system under test. The forward pass, the loss and,
through ``jax.grad`` of that, the gradients; one Adam step. What it
has in common with ``reference/window_moe_lm.py`` (the norm, the
rotary table, the sparse layer and its router) and with
``reference/moe_lm.py`` (the head's chunked cross-entropy, Adam's
first step, the layout of a step and its comparison) is imported from
there.

The equations (all matrices without bias; every layer is ``x' = x +
SelAttn(x)``, ``y = x' + MoE(x')``; ``n = rms_norm(x)``; ``T``
positions; query head ``h`` reads key/value head ``h // (H / KV)``):

* ``q_h = rope(rms_norm((n Wq)_h; gq))``, ``k_j = rope(rms_norm((n
  Wk)_j; gk))``, ``v_j = (n Wv)_j``; ``gq``, ``gk`` of ``d`` numbers,
  shared by the heads; all ``d`` dims rotated, rotate-half pairing.
* The index, on ``m = stop_gradient(n)``: ``a_{t,i} = rope((m
  Wiq)_i)`` in ``Hi`` heads of ``Di``; ``b_s = rope(layer_norm(m_s
  Wik; gamma, beta))`` (ONE key head; LayerNorm: mean and variance
  over the ``Di`` dims, the descriptor's eps); ``c_{t,i} = (m_t Wiw)_i
  / sqrt(Hi Di)``; ``I_{t,s} = sum_i c_{t,i} relu(a_{t,i} . b_s)``.
* Selection: ``S_t`` = the ``min(t + 1, top_k)`` keys ``s <= t`` of
  largest ``I_{t,s}``, the lower ``s`` on a tie (``lax.top_k`` over the
  whole row of keys, those after ``t`` at ``-inf`` and dropped again;
  ``-0`` read as ``+0``, so that a tie is a tie). No gradient passes
  through it.
* Core: ``P_{t,h,s} = softmax over s in S_t of (q_{t,h} . k_{s,g(h)} /
  sqrt(d))`` under the explicit mask, ``jnp.repeat`` of K and V;
  ``o_{t,h} = sum_s P_{t,h,s} v_{s,g(h)}``; heads concatenated, ``Wo``.
* The index's term: ``p_{t,s} = stop_gradient(mean_h P_{t,h,s})``;
  ``L_I = mean_t sum_{s in S_t} p_{t,s} (log p_{t,s} - log softmax_{s
  in S_t}(I_{t,s}))`` (and a mean over the batch).
* The sparse layer: ``reference/window_moe_lm.py`` ``moe``, with
  ``scoring`` softmax, ``normalize``, scale 1, no shared expert.
* Objective: the mean cross-entropy over all positions + the sum over
  the layers of ``L_I``.
* One train step (:func:`train_step`): the objective's gradient and
  Adam's first step from zero moments. This is what :func:`agreement`
  holds the timed program to.

Departures from the published description, each stated where it is
computed: the chip's share (held experts, the vocabulary slice) as in
``reference/moe_lm.py``; ASSUMED, because the configuration names
none of them (the configuration file gives each reason): the q/k
RMSNorm, the index's input (the layer's normed state), LayerNorm on
the index key, rotary embedding on the whole index head, the ``1 /
sqrt(Hi Di)`` scale, no Hadamard rotation, ``top_k`` counting the
query's own key, text-only positions, ``L_I``'s weight and its mean
over the positions. Attention runs a block of ``Q_BLOCK`` queries at a
time over the whole row of keys under ``jax.checkpoint``, one block
after the other (``lax.map``), and the head a chunk of tokens at a
time, so that a sequence of 8,192 positions fits.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from benchmark.reference import moe_lm
from benchmark.reference.moe_lm import (  # noqa: F401 (the driver's)
    head_losses, stepped)
from benchmark.reference.window_moe_lm import (  # noqa: F401
    expert_counts, moe, rms_norm, rope)

ATTENTION = "grouped_attention"
#: the index's five arrays
INDEX_PARAMS = ("index_q", "index_k", "index_w", "index_norm_gain",
                "index_norm_bias")

#: What decides ``correct`` is ONE TRAIN STEP of the timed program from
#: the initial weights and a fresh optimizer state, on one batch of the
#: train set, against :func:`train_step` here, as in the two other
#: token cells. The program computes in bfloat16 (activations rounded
#: to 8 bits of mantissa between and inside the units; parameters,
#: accumulation, the routers, the index scores, the selection and
#: ``L_I`` float32).
#:
#: The v5e's readings (PERF.md section 6, PR 33, my chip runs; calls 1
#: and 2). The program's over seven seeds (2147483777, 3000000033,
#: 99991033 in the probe, 1234567891, 2000000011, 777000111, 31337)
#: and the int8 reference's on seed 99991033 (both operands of all 224
#: products of the forward and the backward pass but the routers'
#: rounded to 8 bits, the index's and the core's inside the
#: reference's loop of blocks among them:
#: ``scripts/lm_tolerance_probe.py --cell
#: keye-vl2-ep8share.pretrain8k-1seq``). The limits were set from
#: these; runs after them are in PERF.md.
READINGS = {
    "program": {
        "gradient_error": [0.004789, 0.004836, 0.004547, 0.004827,
                           0.004837, 0.004602, 0.004874],
        "index_gradient_error": [0.005632, 0.005225, 0.005169, 0.006010,
                                 0.005796, 0.005474, 0.005305],
        "index_loss_error": [6.05e-4, 3.70e-4, 2.81e-4, 3.15e-4, 2.48e-4,
                             9.83e-5, 9.47e-5],
        "selection_error": [0.004008, 0.003953, 0.003972, 0.003992,
                            0.003966, 0.003960, 0.004048],
        "update_error": [0.12358, 0.13569, 0.12401, 0.12785, 0.12637,
                         0.12322, 0.12043],
        "update_scale_error": [6.0e-5, 3.6e-5, 5.3e-5, 6.0e-5, 3.1e-5,
                               6.7e-5, 2.0e-5]},
    "int8": {"gradient_error": 0.017359, "index_gradient_error": 0.011346,
             "index_loss_error": 0.001866, "selection_error": 0.014207,
             "update_error": 0.17778, "update_scale_error": 9.1e-5},
}

#: ``GRADIENT_TOLERANCE`` bounds ``gradient_error``, the L2 distance
#: between the program's and the reference's gradient over ALL the
#: parameters, over the reference's norm (read off Adam's first
#: moment). THE PRECISION LIMIT: the geometric mean of the program's
#: largest reading (0.004874) and the int8 reference's (0.017359),
#: 1.89 times of room on either side. Both read lower than the other
#: two token cells' (0.011, 0.034); beside them 0.20-0.22% of a
#: step's token-to-expert assignments fall the other way in bfloat16
#: (0.51% in int8), where the Laguna cell's 0.51-0.56% do (1.36%).
GRADIENT_TOLERANCE = 0.0092
#: ``INDEX_GRADIENT_TOLERANCE`` bounds ``index_gradient_error``: the
#: same over the index's five arrays of every layer ALONE, which only
#: ``L_I`` moves: a missing, doubled or mis-scaled ``L_I`` reads 1 or
#: more here and would vanish in an L2 over 0.47 G numbers. The two
#: readings lie only 1.89 times apart (0.006010, 0.011346: the index's
#: score product is a sum of 16 relus, which rounding the operands to
#: 8 bits moves less than it moves a softmax's scores), so the limit
#: stands nearer the int8 one: 1.41 times of room above the program,
#: 1.33 below int8.
INDEX_GRADIENT_TOLERANCE = 0.0085
#: ``INDEX_LOSS_TOLERANCE`` bounds ``index_loss_error``, the largest
#: relative difference of a layer's ``L_I`` (0.05-0.08 at the initial
#: weights). The program's 28 readings, a layer each, are noise of
#: 1.5e-5 about 0 in absolute terms, the largest 4.6e-5 (6.05e-4 of
#: its ``L_I``); the int8 reference's largest is 1.4e-4 (1.866e-3):
#: the limit at 1.2e-3, twice the program's largest, 1.55 below int8.
INDEX_LOSS_TOLERANCE = 0.0012
#: ``SELECTION_TOLERANCE`` bounds ``selection_error``: query-key pairs
#: of the first attention layer (where both sides read the same input,
#: the embedding's rows) that one side selected and the other did not,
#: over the 14,681,088 the reference selected. What falls the other
#: way are the keys whose index scores lie within the products'
#: rounding of the 2,048th: 0.395-0.405% in bfloat16 on every seed,
#: 1.42% in int8; the limit at their geometric mean, 1.88 times of
#: room on either side.
SELECTION_TOLERANCE = 0.0076
#: ``UPDATE_TOLERANCE`` bounds ``update_error``, the L2 distance of the
#: two parameter changes over the reference's norm; a state left
#: unchanged reads 1. Adam's first step moves a weight by the rate
#: times its gradient's SIGN, so the reading is ``2 sqrt(share of
#: signs that differ)`` and tells a wrong direction, not a precision
#: (int8 reads 0.178, inside it; ``reference/moe_lm.py`` has the
#: argument). Between the largest reading (0.136) and 1, the more room
#: above the reading: the other token cells' 0.65.
UPDATE_TOLERANCE = 0.65
#: ``UPDATE_SCALE_TOLERANCE`` bounds ``update_scale_error``, |the norm
#: of the program's changes over the norm of the reference's - 1|: the
#: RATE, which a sign cannot move (a rate twice too large reads 1).
#: What is left at 2e-5 to 7e-5 is the weights whose gradient is of
#: epsilon's size.
UPDATE_SCALE_TOLERANCE = 0.01

#: ``TIMED_SELECTION_TOLERANCE`` bounds ``timed_selection_error``. No
#: precision's limit: both sides are the PROGRAM's arithmetic on the
#: same weights and ids, the timed step's and the one-unit program's
#: that makes the mask ``selection_error`` compares, so it reads 0
#: where XLA compiles the index scores alike in both (0 on every run
#: so far, PERF.md section 6) and the limit leaves a query in a hundred
#: for a last bit that falls otherwise at the 2,048th score; a step
#: that selected by anything else reads 0.75, every query past
#: ``top_k``.
TIMED_SELECTION_TOLERANCE = 0.01

#: queries to a block of attention
Q_BLOCK = 512


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(x - mean), -1, keepdims=True) + eps) \
        * gain + bias


def index_operands(descr, p, n):
    """``(a (batch, seq, Hi, Di), b (batch, seq, Di), c (batch, seq,
    Hi))`` from the normed state, read as a constant. ASSUMED: the
    index's input, LayerNorm on its key, rotary embedding on the whole
    index head, the scale."""
    hi, di = descr["index"]["heads"], descr["index"]["head_dim"]
    batch, seq, _ = n.shape
    theta = descr.get("rope_theta", 1e4)
    m = jax.lax.stop_gradient(n)
    a = rope((m @ p["index_q"]).reshape(batch, seq, hi, di), theta)
    b = rope(layer_norm(m @ p["index_k"], p["index_norm_gain"],
                        p["index_norm_bias"], descr["eps"])[:, :, None],
             theta)[:, :, 0]
    c = (m @ p["index_w"]) / math.sqrt(hi * di)
    return a, b, c


def select(index, start, top_k):
    """The explicit mask (batch, bq, seq) of a block of queries from
    position ``start`` on, from their index scores over the WHOLE row
    of keys: ``lax.top_k`` (the lower index on a tie) of the row with
    the keys after the query at ``-inf``, scattered into a mask, those
    keys dropped again. ASSUMED: ``top_k`` counts the query's own
    key."""
    batch, rows, seq = index.shape
    causal = (start + jnp.arange(rows))[:, None] >= jnp.arange(seq)[None]
    _, chosen = jax.lax.top_k(
        jnp.where(causal, jnp.where(index == 0, 0.0, index), -jnp.inf),
        min(top_k, seq))
    mask = jnp.zeros(index.shape, bool).at[
        jnp.arange(batch)[:, None, None], jnp.arange(rows)[None, :, None],
        chosen].set(True)
    return mask & causal


def selected_core(descr, q, k, v, a, b, c):
    """``(o (batch, seq, H, d), L_I summed over the queries (batch,),
    the mask (batch, seq, seq))``: (batch, seq, heads, dim) operands
    of as many heads, a block of ``Q_BLOCK`` queries at a time over ALL
    the keys of the row, one block after the other (``lax.map``: the
    blocks laid side by side take 23 GB at 8,192 positions).
    ``jax.checkpoint`` changes no value: it says what the gradient
    keeps."""
    batch, seq = q.shape[:2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    top_k = descr["index"]["top_k"]
    rows = min(Q_BLOCK, seq)
    if seq % rows:
        raise ValueError("%d positions are no whole blocks of %d"
                         % (seq, rows))

    @jax.checkpoint
    def block(operands):
        q, a, c, start = operands
        index = jnp.sum(c.transpose(0, 2, 1)[..., None] * jax.nn.relu(
            jnp.einsum("bqhd,bkd->bhqk", a, b)), 1)
        mask = select(jax.lax.stop_gradient(index), start, top_k)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        prob = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
        share = jax.lax.stop_gradient(jnp.mean(prob, 1))
        log_share = jax.nn.log_softmax(
            jnp.where(mask, index, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(
            mask & (share > 0), share * (
                jnp.log(jnp.where(share > 0, share, 1.0)) - log_share),
            0.0), (1, 2))
        return jnp.einsum("bhqk,bkhd->bqhd", prob, v), kl, mask

    def blocks(t):  # (batch, seq, ...) -> (blocks, batch, rows, ...)
        return jnp.moveaxis(
            t.reshape((batch, seq // rows, rows) + t.shape[2:]), 1, 0)

    o, kl, mask = jax.lax.map(block, (
        blocks(q), blocks(a), blocks(c), jnp.arange(0, seq, rows)))
    return (jnp.moveaxis(o, 0, 1).reshape((batch, seq) + o.shape[3:]),
            jnp.sum(kl, 0), jnp.moveaxis(mask, 0, 1).reshape(
                batch, seq, seq))


def attention_inputs(descr, p, x):
    """``(n, q, k, v)``: the normed state, queries and the REPEATED
    keys and values, (batch, seq, H, d). ASSUMED: the RMSNorm on every
    query and key head before the rotary embedding."""
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
    return n, q, k, v


def selected_attention(descr, p, x):
    """``(x + SelAttn(x), L_I (scalar), the mask (batch, seq, seq))``."""
    batch, seq, _ = x.shape
    n, q, k, v = attention_inputs(descr, p, x)
    o, loss, mask = selected_core(descr, q, k, v,
                                  *index_operands(descr, p, n))
    return x + o.reshape(batch, seq, -1) @ p["o"], \
        jnp.mean(loss) / seq, mask


def states(layers, params, tokens, counts=None, index=None):
    """The state the head reads. ``tokens``: (batch, positions + 1)
    ids. ``counts``, a dict, is filled with every sparse layer's
    :func:`expert_counts`, keyed 0, 1, ... in the layers' order;
    ``index``, a dict, with every attention layer's ``(L_I, mask)``,
    keyed likewise."""
    x = None
    for descr, p in zip(layers[:-1], params[:-1]):
        ltype = descr["type"]
        if ltype == "token_embedding":
            x = jnp.asarray(p["weights"])[tokens[:, :descr["positions"]]]
        elif ltype == ATTENTION:
            x, loss, chosen = jax.checkpoint(
                lambda p, x, descr=descr: selected_attention(descr, p, x))(
                    p, x)
            if index is not None:
                index[len(index)] = (loss, chosen)
        elif ltype == "moe":
            if counts is not None:
                counts[len(counts)] = expert_counts(descr, p, x)
            x = jax.checkpoint(
                lambda p, x, descr=descr: moe(descr, p, x))(p, x)
        elif ltype == "rms_norm":
            x = rms_norm(x, p["weights"], descr.get("eps", 1e-5))
        else:
            raise ValueError("no reference for layer type %r" % ltype)
    return x


def objective(layers, params, tokens, labels, counts=None, index=None):
    """``(total, {"main": CE, "index<i>": L_I of attention layer i})``
    of a batch: what the train step differentiates. ``labels[:, t]``
    is the id after position ``t``."""
    index = {} if index is None else index
    x = states(layers, params, tokens, counts=counts, index=index)
    main = jnp.mean(head_losses(params[-1]["weights"], x,
                                labels[:, :x.shape[1]]))
    terms = {"index%d" % i: index[i][0] for i in range(len(index))}
    return main + sum(terms.values()), dict(terms, main=main)


def places(mask):
    """The sum of the positions of the keys each query selected: what
    a step that holds no mask can say of WHICH keys those were."""
    return jnp.sum(jnp.where(mask, jnp.arange(mask.shape[-1]), 0), -1)


def step_function(layers):
    """``f(params, tokens, labels) -> (gradients, losses, counts,
    chosen)`` of one batch, to be jitted; ``chosen`` holds the keys
    every query of each attention layer selected and the sum of their
    positions (``"selected"``, ``"selected_places"``, a list each) and
    the FIRST attention layer's mask (``"selection"``: the one layer
    whose input, the embedding's rows, the program and the reference
    read alike)."""
    layers = [dict(d) for d in layers]

    def fn(params, tokens, labels):
        def loss(p):
            counts, index = {}, {}
            total, terms = objective(layers, p, tokens, labels, counts,
                                     index)
            masks = [index[i][1] for i in range(len(index))]
            chosen = {"selected": [jnp.sum(m, -1) for m in masks],
                      "selected_places": [places(m) for m in masks],
                      "selection": masks[0]}
            return total, (terms, counts, chosen)
        (_, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return (grads,) + aux
    return fn


def gradients(layers, params, tokens, labels):
    """The objective's gradient to every parameter, by ``jax.grad``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(step_function(layers))(params, tokens, labels)[0]


def train_step(layers, params, tokens, labels, optimizer, fn=None):
    """ONE TRAIN STEP from ``params`` (host arrays) and a fresh
    optimizer state on one batch, everything back on the host, laid
    out by ``reference/moe_lm.py`` ``stepped``, with ``"selected"``
    and ``"selected_places"`` (lists, an attention layer each: the
    keys every query selected, the sum of their positions) and
    ``"selection"`` beside it. ``fn`` stands in for the jitted
    :func:`step_function` (the probe's lower-precision control)."""
    with jax.default_matmul_precision("highest"):
        fn = fn or jax.jit(step_function(layers))
        grads, terms, counts, chosen = jax.device_get(fn(
            jax.device_put(params), numpy.asarray(tokens),
            numpy.asarray(labels)))
    return dict(stepped(layers, params, grads, terms, counts, optimizer),
                selected=[numpy.asarray(n) for n in chosen["selected"]],
                selected_places=[numpy.asarray(n) for n in
                                 chosen["selected_places"]],
                selection=numpy.asarray(chosen["selection"]))


def sample_losses(layers, params, data, labels):
    """Mean cross-entropy of each sequence (host arrays), one sequence
    to a call, kept in float64 on the host; the index selects, its
    term is not part of what a validation sweep reports."""
    layers = [dict(d) for d in layers]

    @jax.jit
    def one(params, tokens, targets):
        return objective(layers, params, tokens, targets)[1]["main"]

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


def step_comparison(layers, program, expected):
    """``reference/moe_lm.py``'s numbers, and this family's own:

    * ``index_gradient_error``: the L2 distance of Adam's first
      moments over the index's five arrays of every layer alone, over
      the reference's norm there;
    * ``index_loss_error``: the largest relative difference of a
      layer's ``L_I`` (``loss_errors`` has each term's absolute one);
    * ``selection_error``: where both sides carry ``"selection"`` (the
      first attention layer's mask): pairs one side selected and the
      other did not, over the pairs the reference selected;
    * ``timed_selection_error``: the program's ``"selection"`` is made
      after the step, by a program of its own; the TIMED step says of
      its selection what it can without a mask, the keys each query
      selected and the sum of their positions. The share of the first
      attention layer's queries for which either differs from the
      mask's;
    * ``selected_places_error``: those sums against the reference's
      mask, ``sum |difference| / sum``: no limit, a reading;
    * ``selected_as_ruled``: whether every query of every layer of the
      PROGRAM selected exactly ``min(t + 1, top_k)`` keys."""
    out = moe_lm.step_comparison(layers, program, expected)
    num = den = 0.0
    for i, descr in enumerate(layers):
        if descr["type"] != ATTENTION:
            continue
        for name in INDEX_PARAMS:
            n, d, _ = moe_lm._distance(program["moments"][i][name],
                                       expected["moments"][i][name])
            num, den = num + n, den + d
    out["index_gradient_error"] = math.sqrt(num / max(den, 1e-300))
    out["index_loss_error"] = max(
        abs(program["losses"][k] - v) / max(abs(v), 1e-30)
        for k, v in expected["losses"].items() if k.startswith("index"))
    top_ks = [d["index"]["top_k"] for d in layers
              if d["type"] == ATTENTION]
    out["selected_as_ruled"] = all(
        numpy.array_equal(
            numpy.asarray(got),
            numpy.broadcast_to(numpy.minimum(
                numpy.arange(numpy.shape(got)[-1]) + 1, top_k),
                numpy.shape(got)))
        for got, top_k in zip(program["selected"], top_ks))
    if "selection" in program and "selection" in expected:
        want = numpy.asarray(expected["selection"])
        made = numpy.asarray(program["selection"])
        out["selection_error"] = float(
            numpy.count_nonzero(made != want)) / max(
                int(numpy.count_nonzero(want)), 1)
        if "selected_places" in program:
            where = numpy.arange(made.shape[-1], dtype=numpy.float64)
            timed = numpy.asarray(program["selected_places"][0])
            out["timed_selection_error"] = float(numpy.mean(
                (made.sum(-1) != numpy.asarray(program["selected"][0]))
                | (made @ where != timed)))
            out["selected_places_error"] = float(
                numpy.abs(timed - want @ where).sum()
                / max((want @ where).sum(), 1.0))
    return out


def agreement(program_losses, reference):
    """``(ok, report)``: the driver's comparison. ``correct`` needs the
    step inside every limit above (each number there: a step that
    lacks one is not correct), every query selecting as ruled, no
    selection bias moved, and every token routed ``top_k`` times."""
    program = numpy.asarray(program_losses, numpy.float64)
    losses = numpy.asarray(reference["losses"], numpy.float64)
    if program.shape != losses.shape:
        return False, {"error": "shapes %s vs %s"
                       % (program.shape, losses.shape)}
    step = reference["step"]
    limits = {"gradient_error": GRADIENT_TOLERANCE,
              "index_gradient_error": INDEX_GRADIENT_TOLERANCE,
              "index_loss_error": INDEX_LOSS_TOLERANCE,
              "selection_error": SELECTION_TOLERANCE,
              "timed_selection_error": TIMED_SELECTION_TOLERANCE,
              "update_error": UPDATE_TOLERANCE,
              "update_scale_error": UPDATE_SCALE_TOLERANCE}
    # a number that was never computed is not inside its limit
    ok = (all(name in step and step[name] <= limit
              for name, limit in limits.items())
          and step["selected_as_ruled"]
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
