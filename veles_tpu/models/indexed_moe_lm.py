"""A language model of grouped-query attention layers under a LEARNED
SELECTION of keys, with sparse experts: the language model
Keye-VL-2.0-30B-A3B (``KeyeVL2``) configures, as StandardWorkflow
layer descriptors.

``python -m veles_tpu veles_tpu/models/indexed_moe_lm.py`` trains the
tiny preset (:data:`TINY`) on Zipf token rows through the launcher and
the fused step; ``layers(**sizes)`` gives the descriptors of any size,
the published one included (``benchmark/configs/`` holds that list cut
and written out). A chip may hold a share of a deployment's layer:
``experts_held`` of the routed experts (the router keeps all its
outputs), a slice of the vocabulary.

The chain: embedding; blocks of ``grouped_attention`` and a dropless
``moe`` (every layer sparse, no shared expert, the period is 1); the
final norm; the head. Every attention layer norms its query and key
heads and selects the ``top_k`` keys a query attends to by an index
of ``index_heads`` heads of ``index_head_dim`` on one index key head
(DeepSeek-V3.2's sparse attention); the index trains on its own term
of the objective, which the step adds to the model's loss.
"""

from veles_tpu.models.token_lm import TokenLMWorkflow, runner

#: the published sizes (huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B,
#: config.json, the language model's keys), with this repo's names
PUBLISHED = dict(
    dim=2048, head_dim=128, heads=32, kv_heads=4, rope_theta=1e7,
    eps=1e-6, index_heads=16, index_head_dim=64, index_top_k=2048,
    expert_hidden=768, n_experts=128, top_k=8, blocks=48,
    vocabulary=151936)

#: seconds on a CPU; every mechanism present: fewer key/value heads
#: than query heads, the q/k norm, an index that selects fewer keys
#: than the sequence holds and no multiple of the block, top-k > 1 of
#: more experts than are held, no shared expert
TINY = dict(
    dim=32, head_dim=8, heads=4, kv_heads=2, rope_theta=1e7, eps=1e-6,
    index_heads=3, index_head_dim=4, index_top_k=6, expert_hidden=16,
    n_experts=16, top_k=3, blocks=2, vocabulary=64, positions=16,
    block=8, experts_held=(0, 8))


def layers(dim, head_dim, heads, kv_heads, rope_theta, eps, index_heads,
           index_head_dim, index_top_k, expert_hidden, n_experts, top_k,
           blocks, vocabulary, positions, experts_held=None, block=512,
           head_chunk=2048, dispatch_rows=None, stddev=0.02,
           embedding_stddev=1.0, head_stddev=0.006, remat=False):
    """The layer descriptors. ``blocks`` counts the blocks;
    ``experts_held=(first, count)`` and ``vocabulary`` are this chip's
    share. The router scores by a softmax over all its outputs and
    renormalises the chosen weights (``norm_topk_prob``), scale 1, no
    selection bias. Embedding rows are filled wider than the matrices,
    as ``latent_moe_lm.layers`` says why."""
    fill = {"weights_filling": "gaussian", "weights_stddev": stddev}
    out = [dict(fill, weights_stddev=embedding_stddev,
                type="token_embedding", name="embedding",
                vocabulary=vocabulary, dim=dim, positions=positions)]
    for _ in range(blocks):
        out.append(dict(
            fill, type="grouped_attention", heads=heads,
            kv_heads=kv_heads, head_dim=head_dim, window=None,
            gated=False, qk_norm=True, eps=eps, block=block,
            remat=remat, rope_theta=rope_theta, rotary_fraction=1.0,
            yarn=None,
            index={"heads": index_heads, "head_dim": index_head_dim,
                   "top_k": index_top_k}))
        out.append(dict(
            fill, type="moe", n_experts=n_experts, hidden=expert_hidden,
            capacity_factor=None, top_k=top_k, scoring="softmax",
            normalize=True, scale=1.0, shared_experts=0,
            experts_held=list(experts_held or (0, n_experts)),
            bias_rate=0.0, dispatch_rows=dispatch_rows, eps=eps,
            remat=remat))
    out.append({"type": "rms_norm", "eps": eps})
    out.append({"type": "vocabulary_head", "vocabulary": vocabulary,
                "chunk": head_chunk, "weights_filling": "gaussian",
                "weights_stddev": head_stddev})
    return out


class IndexedMoELMWorkflow(TokenLMWorkflow):
    """The model on Zipf token rows; sizes default to :data:`TINY`."""

    TINY, layers = TINY, staticmethod(layers)


run = runner(IndexedMoELMWorkflow)
