"""Token data sets: rows of ids for a language model.

A sample is one document cut to ``positions + lookahead`` ids: the
model embeds the first ``positions`` and scores position ``t`` against
id ``t + 1`` (and a side branch of shift ``s`` against ``t + 1 + s``),
so ``lookahead`` is one more than the largest shift. ``original_data``
holds the ids, ``original_labels`` the same rows from the second id
on: ``labels[:, t]`` is the target of position ``t``. One document a
row, no packing (a packed row needs a cross-document mask that the
attention units do not have).
"""

import numpy

from veles_tpu.loader.fullbatch import FullBatchLoader


def zipf_ids(rng, n, length, vocabulary, exponent=1.0):
    """``(n, length)`` int32 ids with rank-frequency ``1 / rank **
    exponent`` over ``vocabulary`` ids, the ranks dealt to ids by a
    permutation drawn from ``rng`` (a ``numpy.random.Generator``): a
    unigram to learn, and frequent ids all over the table."""
    p = 1.0 / numpy.arange(1, vocabulary + 1, dtype=numpy.float64) \
        ** exponent
    ids_of_rank = rng.permutation(vocabulary).astype(numpy.int32)
    return ids_of_rank[rng.choice(vocabulary, size=(n, length),
                                  p=p / p.sum())]


class TokenLoader(FullBatchLoader):
    """Full batch of token rows from ``provider() -> (train_ids,
    valid_ids)``, each ``(n, positions + lookahead)`` integers;
    validation first, as every full-batch loader lays them out."""

    hide_from_registry = True

    def __init__(self, workflow, provider=None, **kwargs):
        kwargs.setdefault("normalization_type", "none")
        super(TokenLoader, self).__init__(workflow, **kwargs)
        self.provider = provider

    def load_dataset(self):
        train, valid = self.provider()
        ids = numpy.concatenate([valid, train]).astype(numpy.int32)
        self.original_data.reset(ids)
        self.original_labels.reset(numpy.ascontiguousarray(ids[:, 1:]))
        self.class_lengths = [0, len(valid), len(train)]

    def create_minibatch_data(self):
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size,) + tuple(
                self.original_data.shape[1:]), numpy.int32))
