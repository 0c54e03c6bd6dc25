"""A latent-attention, sparse-expert language model with multi-token
prediction: the block of the DeepSeek-V3 line as GLM-4.7-Flash
(``glm4_moe_lite``) configures it, as StandardWorkflow layer
descriptors.

``python -m veles_tpu veles_tpu/models/latent_moe_lm.py`` trains the
tiny preset (:data:`TINY`) on Zipf token rows through the launcher and
the fused step; ``layers(**sizes)`` gives the descriptors of any size,
the published one included (``benchmark/configs/`` holds that list
written out). A chip may hold a share of a deployment's layer:
``experts_held`` of the routed experts (the router keeps all its
outputs), a slice of the vocabulary.

The chain: embedding; blocks of ``latent_attention`` and a
feed-forward half, the first ``dense_layers`` of them a ``gated_mlp``
and the rest a dropless ``moe``; the final norm; the MTP module as the
side branch ``mtp`` (``token_merge``, one block, its norm); the head,
which scores the main path and the branch.
"""

import numpy

from veles_tpu.config import root
from veles_tpu.loader.tokens import TokenLoader, zipf_ids
from veles_tpu.standard_workflow import StandardWorkflow

#: the published sizes (huggingface.co/zai-org/GLM-4.7-Flash,
#: config.json), with this repo's names
PUBLISHED = dict(
    dim=2048, heads=20, q_rank=768, kv_rank=512, qk_nope_dim=192,
    qk_rope_dim=64, v_dim=256, rope_theta=1e6, eps=1e-5,
    dense_hidden=10240, expert_hidden=1536, n_experts=64, top_k=4,
    scale=1.8, shared_experts=1, blocks=47, dense_layers=1,
    vocabulary=154880, mtp_weight=0.3, bias_rate=1e-3)

#: seconds on a CPU; every mechanism present
TINY = dict(
    dim=32, heads=2, q_rank=16, kv_rank=8, qk_nope_dim=8, qk_rope_dim=8,
    v_dim=12, rope_theta=1e6, eps=1e-5, dense_hidden=64,
    expert_hidden=16, n_experts=8, top_k=2, scale=1.8, shared_experts=1,
    blocks=2, dense_layers=1, vocabulary=64, mtp_weight=0.3,
    bias_rate=1e-3, positions=16, block=8)


def layers(dim, heads, q_rank, kv_rank, qk_nope_dim, qk_rope_dim, v_dim,
           rope_theta, eps, dense_hidden, expert_hidden, n_experts,
           top_k, scale, shared_experts, blocks, dense_layers,
           vocabulary, positions, mtp_weight=0.3, bias_rate=1e-3,
           experts_held=None, block=512, head_chunk=2048,
           dispatch_rows=None, stddev=0.02, embedding_stddev=1.0,
           head_stddev=0.006, remat=False):
    """The layer descriptors. ``blocks`` counts the main path's
    blocks; ``experts_held=(first, count)`` and ``vocabulary`` are
    this chip's share. The embedding's rows are filled wider than the
    matrices (``embedding_stddev``): at the matrices' 0.02 the first
    attention's output, nearly one vector for all tokens, drowns the
    embedding, and fresh routers see one state."""
    fill = {"weights_filling": "gaussian", "weights_stddev": stddev}

    def attention(**more):
        return dict(
            fill, type="latent_attention", heads=heads, q_rank=q_rank,
            kv_rank=kv_rank, qk_nope_dim=qk_nope_dim,
            qk_rope_dim=qk_rope_dim, v_dim=v_dim, rope_theta=rope_theta,
            eps=eps, block=block, remat=remat, **more)

    def sparse(**more):
        return dict(
            fill, type="moe", n_experts=n_experts, hidden=expert_hidden,
            capacity_factor=None, top_k=top_k, scoring="sigmoid",
            normalize=True, scale=scale, shared_experts=shared_experts,
            experts_held=list(experts_held or (0, n_experts)),
            bias_rate=bias_rate, dispatch_rows=dispatch_rows, eps=eps,
            remat=remat, **more)

    out = [dict(fill, weights_stddev=embedding_stddev,
                type="token_embedding", name="embedding",
                vocabulary=vocabulary, dim=dim, positions=positions)]
    for i in range(blocks):
        out.append(attention())
        out.append(dict(fill, type="gated_mlp", hidden=dense_hidden,
                        eps=eps, remat=remat)
                   if i < dense_layers else sparse())
    out.append({"type": "rms_norm", "eps": eps})
    out.append(dict(fill, type="token_merge", branch="mtp",
                    embedding="embedding", shift=1,
                    objective_weight=mtp_weight, eps=eps))
    out.append(attention(branch="mtp"))
    out.append(sparse(branch="mtp"))
    out.append({"type": "rms_norm", "eps": eps, "branch": "mtp"})
    out.append({"type": "vocabulary_head", "vocabulary": vocabulary,
                "chunk": head_chunk, "weights_filling": "gaussian",
                "weights_stddev": head_stddev})
    return out


#: Adam as the benchmark's configuration assumes it, without its warm-up
ADAM = dict(solver="adam", learning_rate=3e-4, momentum=0.0,
            weights_decay=0.0,
            solver_hp={"beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8})


class ZipfTokens(object):
    """Provider of Zipf token rows from a seed."""

    def __init__(self, n_train, n_valid, length, vocabulary, seed=1,
                 exponent=1.0):
        self.spec = (n_train, n_valid, length, vocabulary, seed,
                     exponent)

    def __call__(self):
        n_train, n_valid, length, vocabulary, seed, exponent = self.spec
        ids = zipf_ids(numpy.random.default_rng(seed), n_train + n_valid,
                       length, vocabulary, exponent)
        return ids[n_valid:], ids[:n_valid]


class LatentMoELMWorkflow(StandardWorkflow):
    """The model on Zipf token rows; sizes default to :data:`TINY`."""

    def __init__(self, workflow=None, sizes=None, n_train=64,
                 n_valid=16, minibatch_size=8, seed=1, **kwargs):
        sizes = dict(TINY, **(sizes or {}))
        for name, value in ADAM.items():
            kwargs.setdefault(name, value)
        super(LatentMoELMWorkflow, self).__init__(
            workflow,
            loader=lambda wf: TokenLoader(
                wf, provider=ZipfTokens(
                    n_train, n_valid, sizes["positions"] + 2,
                    sizes["vocabulary"], seed),
                minibatch_size=minibatch_size),
            layers=layers(**sizes), loss="softmax", **kwargs)


def run(load, main):
    cfg = root.latent_moe_lm
    load(LatentMoELMWorkflow,
         n_train=cfg.get("n_train", 64), n_valid=cfg.get("n_valid", 16),
         minibatch_size=cfg.get("minibatch_size", 8),
         max_epochs=cfg.get("max_epochs", 3))
    main()
