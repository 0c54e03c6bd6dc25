"""What the token-model families share: Adam as the benchmark's
configurations assume it, Zipf token rows from a seed, and one
workflow that trains a family's layer descriptors on them.

A family module (``latent_moe_lm.py``, ``window_moe_lm.py``, ...)
holds its ``PUBLISHED`` and ``TINY`` sizes and its ``layers(**sizes)``,
declares its workflow as a :class:`TokenLMWorkflow` that names those
two, and hands the CLI ``run = runner(<its workflow>)``.
"""

import numpy

from veles_tpu.config import root
from veles_tpu.loader.tokens import TokenLoader, zipf_ids
from veles_tpu.standard_workflow import StandardWorkflow

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


def row_length(chain):
    """Ids a row holds for the layer descriptors ``chain``: the
    embedding's positions, the target after the last of them, and a
    ``token_merge`` branch's ``shift`` more."""
    embedding, = (d for d in chain if d["type"] == "token_embedding")
    return embedding["positions"] + 1 + sum(
        d["shift"] for d in chain if d["type"] == "token_merge")


class TokenLMWorkflow(StandardWorkflow):
    """A family's model on Zipf token rows, trained by Adam unless
    ``kwargs`` say otherwise; sizes default to the family's
    :attr:`TINY`."""

    hide_from_registry = True

    #: the family's preset, which ``sizes`` override, and its
    #: ``layers(**sizes)`` (a ``staticmethod``)
    TINY = layers = None

    def __init__(self, workflow=None, sizes=None, n_train=64,
                 n_valid=16, minibatch_size=8, seed=1, **kwargs):
        sizes = dict(self.TINY, **(sizes or {}))
        chain = self.layers(**sizes)
        for name, value in ADAM.items():
            kwargs.setdefault(name, value)
        super(TokenLMWorkflow, self).__init__(
            workflow,
            loader=lambda wf: TokenLoader(
                wf, provider=ZipfTokens(
                    n_train, n_valid, row_length(chain),
                    sizes["vocabulary"], seed),
                minibatch_size=minibatch_size),
            layers=chain, loss="softmax", **kwargs)


def runner(cls):
    """The CLI's ``run(load, main)`` of the family whose workflow is
    ``cls``: its options are read under ``root.<the family module's
    name>`` (``root.latent_moe_lm.max_epochs=2``)."""
    def run(load, main):
        cfg = getattr(root, cls.__module__.rsplit(".", 1)[-1])
        load(cls, n_train=cfg.get("n_train", 64),
             n_valid=cfg.get("n_valid", 16),
             minibatch_size=cfg.get("minibatch_size", 8),
             max_epochs=cfg.get("max_epochs", 3))
        main()
    return run
