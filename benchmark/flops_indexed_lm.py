"""Operations and bytes of the grouped-query attention under a learned
selection of keys, sparse-expert family's layers, from shapes alone.

``flops_lm.py``'s conventions and, for the layers that family shares
(the sparse layer, the head), its numbers: a multiply-add counts 2;
training is 3x the forward pass; routed experts count at their
expectation; norms, rotary embedding, softmax, relu and the search for
the selected keys count 0; nothing recomputed is counted. The
attention core counts the score pairs the SELECTION leaves
(``min(t + 1, top_k)`` a query), so that a lowering that computes the
causal square under a mask is credited with no skipped work; the
index's score product counts the causal pairs, which it has to see to
select among them. Nothing here imports the program.
"""

from benchmark import flops_lm

ATTENTION = "grouped_attention"


def causal_pairs(positions):
    return positions * (positions + 1) // 2


def selected_pairs(positions, top_k):
    """Query-key pairs a head and sequence that a selection of
    ``top_k`` keys a query, its own included, leaves of the causal
    triangle (14,681,088 of 33,558,528 at 8,192 positions and 2,048)."""
    full = min(positions, top_k)
    return causal_pairs(full) + (positions - full) * top_k


def _attention(descr, dim, positions):
    h, d = descr["heads"], descr["head_dim"]
    kv = descr.get("kv_heads") or h
    weights = dim * h * d + 2 * dim * kv * d + h * d * dim \
        + (dim * h if descr.get("gated", True) else 0)
    index = descr.get("index")
    if not index:
        return {"proj": 2.0 * weights,
                "core": 2.0 * 2 * d * h * causal_pairs(positions)
                / positions}
    hi, di = index["heads"], index["head_dim"]
    return {
        "proj": 2.0 * weights,
        "index_proj": 2.0 * dim * (hi * di + di + hi),
        # one product a causal pair and index head
        "index_scores": 2.0 * di * hi * causal_pairs(positions)
        / positions,
        # scores and the weighted sum, each 2 * d a selected pair and head
        "core": 2.0 * 2 * d * h * selected_pairs(
            positions, index["top_k"]) / positions}


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


def selected_core_floor_s(descr, positions, sequences, peaks,
                          compute_bytes=2):
    """Least seconds of one train step's attention core of one unit
    under its selection: the SELECTED pairs' FLOPs three times
    (forward; the backward's two products each for scores and values),
    and the least bytes: ``q`` and the output at the query heads' width
    forward, those, the output's gradient and ``dq`` backward; ``k``
    and ``v`` forward, those and ``dk``, ``dv`` backward, at the
    key/value heads' width, read or written once each: ``(seconds,
    bound)``."""
    h, d = descr["heads"], descr["head_dim"]
    kv = descr.get("kv_heads") or h
    flops = 3.0 * sequences * 2 * 2 * d * h * selected_pairs(
        positions, descr["index"]["top_k"])
    moved = sequences * positions * d * compute_bytes * (6 * h + 6 * kv)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops >= t_bytes else "memory"
