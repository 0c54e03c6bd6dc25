"""A language model of window and global grouped-query attention
layers, the global ones without position encoding, whose sparse
experts are routed on the attention's own normed input: the block
SmallThinker-21BA3B (``smallthinker``) configures, as StandardWorkflow
layer descriptors.

``python -m veles_tpu veles_tpu/models/prerouted_moe_lm.py`` trains
the tiny preset (:data:`TINY`) on Zipf token rows through the launcher
and the fused step; ``layers(**sizes)`` gives the descriptors of any
size, the published one included (``benchmark/configs/`` holds that
list cut and written out). A chip may hold a share of a deployment's
layer: ``experts_held`` of the routed experts (the router keeps all
its outputs), a slice of the vocabulary.

The chain: embedding; one layer an entry of ``layout`` (the published
``sliding_window_layout``, which ``rope_layout`` repeats): a
``grouped_attention`` unit named ``attention<i>``, a window layer with
rotary embedding where the entry is 1 and a global NoPE layer (no
window, nothing rotated) where it is 0, no gate; then a dropless
``moe`` whose router reads ``attention<i>``'s normed input
(``route_from``: a router placed before attention), softmax top-k,
ReGLU experts (``activation="relu"``), no shared expert; the final
norm; the untied head.
"""

from veles_tpu.models.token_lm import TokenLMWorkflow, runner

#: the published sizes (huggingface.co/PowerInfer/
#: SmallThinker-21BA3B-Instruct, config.json), with this repo's names;
#: ``layout`` is ``sliding_window_layout`` verbatim
PUBLISHED = dict(
    dim=2560, heads=28, kv_heads=4, head_dim=128, window=4096,
    rope_theta=1.5e6, eps=1e-6, layout=(0, 1, 1, 1) * 13,
    expert_hidden=768, n_experts=64, top_k=6, vocabulary=151936)

#: seconds on a CPU; every mechanism present: both layer kinds, a
#: window shorter than the sequence and no multiple of the block,
#: groups of 3 query heads on a key/value head, top-k > 1 of more
#: experts than are held
TINY = dict(
    dim=32, heads=6, kv_heads=2, head_dim=8, window=5, rope_theta=1.5e6,
    eps=1e-6, layout=(0, 1, 1), expert_hidden=16, n_experts=16, top_k=3,
    vocabulary=64, positions=16, block=8, experts_held=(0, 8))


def layers(dim, heads, kv_heads, head_dim, window, rope_theta, eps,
           layout, expert_hidden, n_experts, top_k, vocabulary,
           positions, experts_held=None, block=512, head_chunk=2048,
           dispatch_rows=None, stddev=0.02, embedding_stddev=1.0,
           head_stddev=0.006, remat=False):
    """The layer descriptors, a layer an entry of ``layout`` (1: a
    window layer); ``experts_held=(first, count)`` and ``vocabulary``
    are this chip's share. Embedding rows are filled wider than the
    matrices, as ``latent_moe_lm.layers`` says why."""
    fill = {"weights_filling": "gaussian", "weights_stddev": stddev}
    out = [dict(fill, weights_stddev=embedding_stddev,
                type="token_embedding", name="embedding",
                vocabulary=vocabulary, dim=dim, positions=positions)]
    for i, windowed in enumerate(layout):
        attention = "attention%d" % i
        out.append(dict(
            fill, type="grouped_attention", name=attention, heads=heads,
            kv_heads=kv_heads, head_dim=head_dim,
            window=window if windowed else None,
            rope_theta=rope_theta if windowed else None,
            rotary_fraction=1.0, yarn=None, gated=False, eps=eps,
            block=block, remat=remat))
        out.append(dict(
            fill, type="moe", n_experts=n_experts, hidden=expert_hidden,
            capacity_factor=None, top_k=top_k, scoring="softmax",
            normalize=True, scale=1.0, shared_experts=0,
            experts_held=list(experts_held or (0, n_experts)),
            bias_rate=0.0, dispatch_rows=dispatch_rows, eps=eps,
            activation="relu", route_from=attention, remat=remat))
    out.append({"type": "rms_norm", "eps": eps})
    out.append({"type": "vocabulary_head", "vocabulary": vocabulary,
                "chunk": head_chunk, "weights_filling": "gaussian",
                "weights_stddev": head_stddev})
    return out


class PreRoutedMoELMWorkflow(TokenLMWorkflow):
    """The model on Zipf token rows; sizes default to :data:`TINY`."""

    TINY, layers = TINY, staticmethod(layers)


run = runner(PreRoutedMoELMWorkflow)
