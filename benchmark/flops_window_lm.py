"""Operations and bytes of the window/full grouped-query attention,
sparse-expert family's layers, from shapes alone.

``flops_lm.py``'s conventions and, for the layers that family shares
(the gated MLP, the sparse layer, the head), its numbers: a
multiply-add counts 2; training is 3x the forward pass; attention
counts the score pairs its mask shows, the BAND of a window layer and
not the square; routed experts count at their expectation; norms,
rotary embedding, softmax and the gates' sigmoid count 0; nothing
recomputed is counted. Nothing here imports the program.
"""

from benchmark import flops_lm

ATTENTION = "grouped_attention"


def score_pairs(positions, window=None):
    """Query-key pairs a head and sequence that the mask shows: the
    causal triangle, or the band of ``window`` keys a query, its own
    included (1,966,336 of 8,390,656 at 4,096 positions and 512)."""
    if window is None or window >= positions:
        return positions * (positions + 1) // 2
    return window * (window + 1) // 2 + (positions - window) * window


def _attention(descr, dim, positions):
    h, d = descr["heads"], descr["head_dim"]
    kv = descr.get("kv_heads") or h
    weights = dim * h * d + 2 * dim * kv * d + h * d * dim \
        + (dim * h if descr.get("gated", True) else 0)
    # scores and the weighted sum, each 2 * d a pair and head
    core = 2.0 * 2 * d * h * score_pairs(positions, descr.get("window")) \
        / positions
    return {"proj": 2.0 * weights, "core": core}


def layer_costs(layers):
    """``flops_lm.layer_costs``' rows, the attention layers' among
    them: ``{"type", "branch", "parts": {name: forward FLOPs a token},
    "passes"}``."""
    first = layers[0]
    others = iter(flops_lm.layer_costs(
        [d for d in layers if d["type"] != ATTENTION]))
    return [{"type": ATTENTION, "branch": d.get("branch"), "passes": 1,
             "parts": _attention(d, first["dim"], first["positions"])}
            if d["type"] == ATTENTION else next(others) for d in layers]


def forward_flops_per_token(layers):
    return sum(sum(c["parts"].values()) * c["passes"]
               for c in layer_costs(layers))


def train_flops_per_sample(layers):
    """A sample is one sequence of ``positions`` tokens."""
    return 3.0 * forward_flops_per_token(layers) * layers[0]["positions"]


def attention_core_floor_s(descr, positions, sequences, peaks,
                           compute_bytes=2):
    """Least seconds of one train step's attention core of one unit:
    the band's FLOPs three times (forward; the backward's two products
    each for scores and values), and the least bytes: ``q`` and the
    output at the query heads' width forward, those, the output's
    gradient and ``dq`` backward; ``k`` and ``v`` forward, those and
    ``dk``, ``dv`` backward, at the key/value heads' width, read or
    written once each: ``(seconds, bound)``."""
    h, d = descr["heads"], descr["head_dim"]
    kv = descr.get("kv_heads") or h
    flops = 3.0 * sequences * 2 * 2 * d * h * score_pairs(
        positions, descr.get("window"))
    moved = sequences * positions * d * compute_bytes * (6 * h + 6 * kv)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops >= t_bytes else "memory"
