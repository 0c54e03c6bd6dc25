"""A language model of gated short-convolution and grouped-query
attention blocks with sparse experts and one table for embedding and
head: the block LFM2-8B-A1B (``lfm2_moe``) configures, as
StandardWorkflow layer descriptors.

``python -m veles_tpu veles_tpu/models/conv_moe_lm.py`` trains the
tiny preset (:data:`TINY`) on Zipf token rows through the launcher and
the fused step; ``layers(**sizes)`` gives the descriptors of any size,
the published one included (``benchmark/configs/`` holds that list cut
and written out). A chip may hold a share of a deployment's layer:
``experts_held`` of the routed experts (the router keeps all its
outputs), a slice of the vocabulary.

The chain: embedding; one block an entry of ``layer_types``, a LIST
and not a period: ``conv`` a ``short_conv`` unit, ``full_attention``
a ``grouped_attention`` unit (a q/k norm, rotary over the whole head,
no window, no gate), each followed by a feed-forward half, a
``gated_mlp`` in the first ``dense_layers`` blocks and a dropless
``moe`` (sigmoid scores, a selection bias that picks and does not
weigh) in the rest; the final norm; the head, which reads the
embedding's table (``tied_to``).
"""

from veles_tpu.config import root
from veles_tpu.loader.tokens import TokenLoader
from veles_tpu.models.latent_moe_lm import ADAM, ZipfTokens
from veles_tpu.standard_workflow import StandardWorkflow

#: the published sizes (huggingface.co/LiquidAI/LFM2-8B-A1B,
#: config.json), with this repo's names; ``layer_types`` verbatim
PUBLISHED = dict(
    dim=2048, heads=32, kv_heads=8, head_dim=64, rope_theta=1e6,
    eps=1e-5, taps=3, conv_bias=False,
    layer_types=(
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv",
        "conv"),
    dense_layers=2, dense_hidden=7168, expert_hidden=1792, n_experts=32,
    top_k=4, scale=1.0, vocabulary=65536)

#: seconds on a CPU; every mechanism present: both block kinds in a
#: list no period gives, 3 taps on a sequence no multiple of them, a
#: head size below the lane, fewer key/value heads than query heads,
#: dense blocks by count (one of each kind), top-k > 1 of more experts
#: than are held, a selection bias that moves, the tied head
TINY = dict(
    dim=32, heads=4, kv_heads=2, head_dim=8, rope_theta=1e6, eps=1e-5,
    taps=3, conv_bias=False,
    layer_types=("conv", "full_attention", "conv", "conv",
                 "full_attention"),
    dense_layers=2, dense_hidden=64, expert_hidden=16, n_experts=16,
    top_k=3, scale=1.0, vocabulary=64, positions=16, block=8,
    experts_held=(0, 8))

MIXERS = {"conv": "short_conv", "full_attention": "grouped_attention"}


def layers(dim, heads, kv_heads, head_dim, rope_theta, eps, taps,
           conv_bias, layer_types, dense_layers, dense_hidden,
           expert_hidden, n_experts, top_k, scale, vocabulary, positions,
           bias_rate=1e-3, normalize_eps=1e-6, experts_held=None,
           block=512, head_chunk=2048, dispatch_rows=None, stddev=0.02,
           table_stddev=0.006, remat=False):
    """The layer descriptors, a block an entry of ``layer_types``;
    ``experts_held=(first, count)`` and ``vocabulary`` are this chip's
    share. The table is the head's too and is filled as the other
    token models fill their head (``table_stddev``): behind the final
    norm a row of std ``s`` gives logits of std ``s sqrt(dim)``, and a
    fresh softmax is to stay near uniform (rows of 1.0, as an untied
    embedding has them, would saturate it)."""
    fill = {"weights_filling": "gaussian", "weights_stddev": stddev}
    out = [dict(fill, weights_stddev=table_stddev,
                type="token_embedding", name="embedding",
                vocabulary=vocabulary, dim=dim, positions=positions)]
    for i, kind in enumerate(layer_types):
        if MIXERS[kind] == "short_conv":
            out.append(dict(fill, type="short_conv", taps=taps,
                            bias=conv_bias, eps=eps, remat=remat))
        else:
            out.append(dict(
                fill, type="grouped_attention", heads=heads,
                kv_heads=kv_heads, head_dim=head_dim, window=None,
                gated=False, qk_norm=True, eps=eps, block=block,
                remat=remat, rope_theta=rope_theta, rotary_fraction=1.0,
                yarn=None))
        if i < dense_layers:
            out.append(dict(fill, type="gated_mlp", hidden=dense_hidden,
                            eps=eps, remat=remat))
        else:
            out.append(dict(
                fill, type="moe", n_experts=n_experts,
                hidden=expert_hidden, capacity_factor=None, top_k=top_k,
                scoring="sigmoid", normalize=True,
                normalize_eps=normalize_eps, scale=scale,
                shared_experts=0,
                experts_held=list(experts_held or (0, n_experts)),
                bias_rate=bias_rate, dispatch_rows=dispatch_rows, eps=eps,
                remat=remat))
    out.append({"type": "rms_norm", "eps": eps})
    out.append({"type": "vocabulary_head", "vocabulary": vocabulary,
                "chunk": head_chunk, "tied_to": "embedding"})
    return out


class ConvMoELMWorkflow(StandardWorkflow):
    """The model on Zipf token rows; sizes default to :data:`TINY`."""

    def __init__(self, workflow=None, sizes=None, n_train=64,
                 n_valid=16, minibatch_size=8, seed=1, **kwargs):
        sizes = dict(TINY, **(sizes or {}))
        for name, value in ADAM.items():
            kwargs.setdefault(name, value)
        super(ConvMoELMWorkflow, self).__init__(
            workflow,
            loader=lambda wf: TokenLoader(
                wf, provider=ZipfTokens(
                    n_train, n_valid, sizes["positions"] + 1,
                    sizes["vocabulary"], seed),
                minibatch_size=minibatch_size),
            layers=layers(**sizes), loss="softmax", **kwargs)


def run(load, main):
    cfg = root.conv_moe_lm
    load(ConvMoELMWorkflow,
         n_train=cfg.get("n_train", 64), n_valid=cfg.get("n_valid", 16),
         minibatch_size=cfg.get("minibatch_size", 8),
         max_epochs=cfg.get("max_epochs", 3))
    main()
