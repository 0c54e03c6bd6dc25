"""A language model of window and full grouped-query attention layers
with sparse experts: the block Laguna-S-2.1 (``laguna``) configures,
as StandardWorkflow layer descriptors.

``python -m veles_tpu veles_tpu/models/window_moe_lm.py`` trains the
tiny preset (:data:`TINY`) on Zipf token rows through the launcher and
the fused step; ``layers(**sizes)`` gives the descriptors of any size,
the published one included (``benchmark/configs/`` holds that list cut
and written out). A chip may hold a share of a deployment's layer:
``experts_held`` of the routed experts (the router keeps all its
outputs), a slice of the vocabulary.

The chain: embedding; blocks of ``grouped_attention`` and a
feed-forward half, the first ``dense_layers`` of them a ``gated_mlp``
and the rest a dropless ``moe``; the final norm; the head. Block ``i``
is a full layer where ``i % period == 0`` and a window layer
otherwise; the two kinds differ in their query head count, their
rotary table and its fraction of a head; both gate each head's output.
"""

from veles_tpu.models.token_lm import TokenLMWorkflow, runner

#: the published sizes (huggingface.co/poolside/Laguna-S-2.1,
#: config.json), with this repo's names
PUBLISHED = dict(
    dim=3072, head_dim=128, kv_heads=8, full_heads=48, window_heads=72,
    window=512, period=4, eps=1e-6,
    full_rotary=dict(
        rope_theta=5e5, rotary_fraction=0.5,
        yarn=dict(factor=128.0, original_positions=8192, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.4852030263919618)),
    window_rotary=dict(rope_theta=1e4, rotary_fraction=1.0, yarn=None),
    dense_hidden=12288, expert_hidden=1024, n_experts=256, top_k=10,
    scale=2.5, shared_experts=1, blocks=48, dense_layers=1,
    vocabulary=100352)

#: seconds on a CPU; every mechanism present: both layer kinds with
#: different head counts, a window shorter than the sequence and no
#: multiple of the block, fewer key/value heads than query heads, YaRN
#: and half a head rotated on the full layers, the gate, top-k > 1 of
#: more experts than are held
TINY = dict(
    dim=32, head_dim=8, kv_heads=2, full_heads=4, window_heads=6,
    window=5, period=2, eps=1e-6,
    full_rotary=dict(
        rope_theta=5e5, rotary_fraction=0.5,
        yarn=dict(factor=8.0, original_positions=8, beta_fast=4.0,
                  beta_slow=1.0, attention_factor=1.2)),
    window_rotary=dict(rope_theta=1e4, rotary_fraction=1.0, yarn=None),
    dense_hidden=64, expert_hidden=16, n_experts=16, top_k=3,
    scale=2.5, shared_experts=1, blocks=3, dense_layers=1,
    vocabulary=64, positions=16, block=8, experts_held=(0, 8))


def layers(dim, head_dim, kv_heads, full_heads, window_heads, window,
           period, eps, full_rotary, window_rotary, dense_hidden,
           expert_hidden, n_experts, top_k, scale, shared_experts,
           blocks, dense_layers, vocabulary, positions,
           scoring="sigmoid", experts_held=None, block=512,
           head_chunk=2048, dispatch_rows=None, stddev=0.02,
           embedding_stddev=1.0, head_stddev=0.006, remat=False):
    """The layer descriptors. ``blocks`` counts the blocks;
    ``experts_held=(first, count)`` and ``vocabulary`` are this chip's
    share. ``scoring`` is the router's score function, which the
    published configuration does not name (``sigmoid`` assumed).
    Embedding rows are filled wider than the matrices, as
    ``latent_moe_lm.layers`` says why."""
    fill = {"weights_filling": "gaussian", "weights_stddev": stddev}
    out = [dict(fill, weights_stddev=embedding_stddev,
                type="token_embedding", name="embedding",
                vocabulary=vocabulary, dim=dim, positions=positions)]
    for i in range(blocks):
        full = i % period == 0
        out.append(dict(
            fill, type="grouped_attention",
            heads=full_heads if full else window_heads,
            kv_heads=kv_heads, head_dim=head_dim,
            window=None if full else window, gated=True, eps=eps,
            block=block, remat=remat,
            **(full_rotary if full else window_rotary)))
        if i < dense_layers:
            out.append(dict(fill, type="gated_mlp", hidden=dense_hidden,
                            eps=eps, remat=remat))
        else:
            out.append(dict(
                fill, type="moe", n_experts=n_experts,
                hidden=expert_hidden, capacity_factor=None, top_k=top_k,
                scoring=scoring, normalize=True, scale=scale,
                shared_experts=shared_experts,
                experts_held=list(experts_held or (0, n_experts)),
                bias_rate=0.0, dispatch_rows=dispatch_rows, eps=eps,
                remat=remat))
    out.append({"type": "rms_norm", "eps": eps})
    out.append({"type": "vocabulary_head", "vocabulary": vocabulary,
                "chunk": head_chunk, "weights_filling": "gaussian",
                "weights_stddev": head_stddev})
    return out


class WindowMoELMWorkflow(TokenLMWorkflow):
    """The model on Zipf token rows; sizes default to :data:`TINY`."""

    TINY, layers = TINY, staticmethod(layers)


run = runner(WindowMoELMWorkflow)
