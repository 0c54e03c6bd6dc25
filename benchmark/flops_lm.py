"""Operations and bytes of a token model's layers, from shapes alone.

The yardstick's arithmetic for the latent-attention, sparse-expert
family, as ``flops.py`` is the convnets': nothing here imports the
program. A layer list is the ``layers`` of a file under ``configs/``.

* MODEL FLOPs: a multiply-add counts 2; training is 3x the forward
  pass; causal attention counts the half of the square it needs;
  routed experts count at their expectation, ``top_k * held /
  n_experts`` experts a token; norms, rotary embedding, softmax, the
  gating and the embedding's row reads count 0; what the program
  recomputes in its backward pass (``remat``) is not counted.
* The head is passed once by the main path and once by each side
  branch, and counts each time.
* Floors are the least time one chip could take: operations over the
  peak, or the least bytes a kernel's passes must move through HBM
  over the bandwidth, whichever is larger.
"""


def _attention(descr, dim, positions):
    h, nope = descr["heads"], descr["qk_nope_dim"]
    rope, v = descr["qk_rope_dim"], descr["v_dim"]
    weights = (dim * descr["q_rank"] + descr["q_rank"] * h * (nope + rope)
               + dim * (descr["kv_rank"] + rope)
               + descr["kv_rank"] * h * (nope + v) + h * v * dim)
    # scores and the weighted sum, each 2 * positions * width a head
    # and query, at half for the causal mask
    core = 0.5 * 2.0 * positions * h * ((nope + rope) + v)
    return {"proj": 2.0 * weights, "core": core}


def _expert(dim, hidden):
    return 2.0 * 3 * dim * hidden


def _moe(descr, dim):
    _, held = descr.get("experts_held") or (0, descr["n_experts"])
    return {"router": 2.0 * dim * descr["n_experts"],
            "shared": descr.get("shared_experts", 0)
            * _expert(dim, descr["hidden"]),
            "experts": descr["top_k"] * held / descr["n_experts"]
            * _expert(dim, descr["hidden"])}


def layer_costs(layers):
    """Per layer: ``{"type", "branch", "parts": {name: forward FLOPs a
    token}}``. The head's entry counts ONE pass; ``passes`` says how
    many the objective makes."""
    first = layers[0]
    dim, positions = first["dim"], first["positions"]
    branches = {d["branch"] for d in layers if d.get("branch")}
    out = []
    for descr in layers:
        ltype = descr["type"]
        if ltype == "latent_attention":
            parts = _attention(descr, dim, positions)
        elif ltype == "gated_mlp":
            parts = {"mlp": _expert(dim, descr["hidden"])}
        elif ltype == "moe":
            parts = _moe(descr, dim)
        elif ltype == "token_merge":
            parts = {"merge": 2.0 * 2 * dim * dim}
        elif ltype == "vocabulary_head":
            parts = {"head": 2.0 * dim * descr["vocabulary"]}
        elif ltype in ("token_embedding", "rms_norm"):
            parts = {}
        else:
            raise ValueError("no operation count for layer type %r"
                             % ltype)
        out.append({"type": ltype, "branch": descr.get("branch"),
                    "parts": parts,
                    "passes": 1 + len(branches)
                    if ltype == "vocabulary_head" else 1})
    return out


def forward_flops_per_token(layers):
    return sum(sum(c["parts"].values()) * c["passes"]
               for c in layer_costs(layers))


def train_flops_per_sample(layers):
    """A sample is one sequence of ``positions`` tokens."""
    return 3.0 * forward_flops_per_token(layers) * layers[0]["positions"]


def expert_gemm_floor_s(descr, dim, rows, peaks, compute_bytes=2,
                        weight_bytes=4):
    """Least seconds of one train step's grouped products of one
    sparse layer for ``rows`` rows routed to the experts held here
    (forward, gradient to the rows, gradient to the weights):
    ``(seconds, bound)``."""
    _, held = descr.get("experts_held") or (0, descr["n_experts"])
    hidden = descr["hidden"]
    flops = 3.0 * rows * _expert(dim, hidden)
    # forward and grad-input read the three matrices in the compute
    # dtype, grad-weights writes them in the parameters'; a row's
    # input, two hidden halves and output are read or written once a
    # pass in the compute dtype
    weights = held * 3 * dim * hidden * (2 * compute_bytes + weight_bytes)
    acts = 3 * rows * (2 * dim + 3 * hidden) * compute_bytes
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = (weights + acts) / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops >= t_bytes else "memory"


def attention_core_floor_s(descr, positions, sequences, peaks,
                           compute_bytes=2):
    """Least seconds of one train step's attention core of one unit,
    ``sequences`` sequences of ``positions``: causal FLOPs three times
    (forward; the backward's two products each for scores and
    values), and ``q, k, v`` read and the output written forward,
    those and the output's gradient read and three gradients written
    backward: ``(seconds, bound)``."""
    h, nope = descr["heads"], descr["qk_nope_dim"]
    rope, v = descr["qk_rope_dim"], descr["v_dim"]
    tokens = positions * sequences
    core = 0.5 * 2.0 * positions * h * ((nope + rope) + v)
    flops = 3.0 * core * tokens
    qk, val = h * (nope + rope), h * v
    moved = tokens * compute_bytes * (
        (2 * qk + 2 * val) + (2 * qk + 3 * val) + (2 * qk + val))
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops >= t_bytes else "memory"
