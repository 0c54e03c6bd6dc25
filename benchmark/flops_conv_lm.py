"""Operations and bytes of the gated short-convolution and
grouped-query attention, sparse-expert family's layers, from shapes
alone.

``flops_lm.py``'s conventions and, for the layers that family shares
(the dense MLP, the sparse layer, the head, which counts one pass
whether or not it owns its matrix), its numbers: a multiply-add counts
2; training is 3x the forward pass; routed experts count at their
expectation; norms, rotary embedding, softmax and the gates count 0;
nothing recomputed is counted. The attention core counts the causal
triangle at the head size the equations have (64), never what a
lowering pads it to; the convolution its taps' multiply-adds. Nothing
here imports the program.
"""

from benchmark import flops_lm

CONV, ATTENTION = "short_conv", "grouped_attention"


def causal_pairs(positions):
    return positions * (positions + 1) // 2


def _attention(descr, dim, positions):
    h, d = descr["heads"], descr["head_dim"]
    kv = descr.get("kv_heads") or h
    weights = dim * h * d + 2 * dim * kv * d + h * d * dim
    # scores and the weighted sum, each 2 * d a causal pair and head
    return {"proj": 2.0 * weights,
            "core": 2.0 * 2 * d * h * causal_pairs(positions) / positions}


def _conv(descr, dim):
    return {"proj": 2.0 * (dim * 3 * dim + dim * dim),
            "mix": 2.0 * descr.get("taps", 3) * dim}


def layer_costs(layers):
    """``flops_lm.layer_costs``' rows, the mixers' among them:
    ``{"type", "branch", "parts": {name: forward FLOPs a token},
    "passes"}``."""
    first = layers[0]
    dim, positions = first["dim"], first["positions"]
    others = iter(flops_lm.layer_costs(
        [d for d in layers if d["type"] not in (CONV, ATTENTION)]))
    return [{"type": d["type"], "branch": d.get("branch"), "passes": 1,
             "parts": _conv(d, dim) if d["type"] == CONV
             else _attention(d, dim, positions)}
            if d["type"] in (CONV, ATTENTION) else next(others)
            for d in layers]


def forward_flops_per_token(layers):
    return sum(sum(c["parts"].values()) * c["passes"]
               for c in layer_costs(layers))


def train_flops_per_sample(layers):
    """A sample is one sequence of ``positions`` tokens."""
    return 3.0 * forward_flops_per_token(layers) * layers[0]["positions"]


def attention_core_floor_s(descr, positions, sequences, peaks,
                           compute_bytes=2):
    """Least seconds of one train step's causal attention core of one
    unit: the causal pairs' FLOPs three times (forward; the backward's
    two products each for scores and values), and the least bytes:
    ``q`` and the output at the query heads' width forward, those, the
    output's gradient and ``dq`` backward; ``k`` and ``v`` forward,
    those and ``dk``, ``dv`` backward, at the key/value heads' width,
    read or written once each: ``(seconds, bound)``."""
    h, d = descr["heads"], descr["head_dim"]
    kv = descr.get("kv_heads") or h
    flops = 3.0 * sequences * 2 * 2 * d * h * causal_pairs(positions)
    moved = sequences * positions * d * compute_bytes * (6 * h + 6 * kv)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops >= t_bytes else "memory"


def short_conv_mix_bytes(dim, tokens, compute_bytes=2):
    """Least bytes one train step's gates and convolution of one unit
    move through HBM: forward the three gates read and their mix
    written (4 x dim a token); backward the three gates and the mix's
    gradient read and the gates' gradient written (7 x dim a token).
    The taps themselves are ``dim x taps`` numbers: nothing."""
    return (4 + 7) * dim * tokens * compute_bytes


def short_conv_floor_s(descr, dim, positions, sequences, peaks):
    """Least seconds of one train step of one short-convolution unit,
    forward once and the backward (a rematerialized forward is the
    program's choice and no part of the least work): its two
    products' FLOPs three times at the peak rate PLUS its mix's bytes
    at the peak bandwidth, the two being one after the other:
    ``(seconds, seconds of the mix alone)``."""
    tokens = positions * sequences
    products = 3.0 * tokens * _conv(descr, dim)["proj"] \
        / peaks["bf16_flops_per_s"]
    mix = short_conv_mix_bytes(dim, tokens) / peaks["hbm_bytes_per_s"]
    return products + mix, mix
