"""The benchmark's own token data set: Zipf ids from a seed.

A ``TokenLoader`` like any user's (``veles_tpu/loader/tokens.py``):
``original_data`` holds rows of ``positions + lookahead`` ids, one
document a row, ``original_labels`` the same rows from the second id
on. What is the benchmark's is how the ids are made: rank ``r`` of
``vocabulary`` has frequency ``1 / r ** exponent`` and the ranks are
dealt to ids by a permutation, both from ``numpy.random.Generator``
streams of ``--seed``, so the same seed gives the same ids. There is a
unigram to learn, so the loss can be held to "below the untrained
one", and frequent ids lie all over the table.
"""

import numpy

from veles_tpu.loader.tokens import TokenLoader


def generate(seed, total, length, vocabulary, exponent):
    """``(total, length)`` int32 ids, a pure function of the
    arguments."""
    order_seq, draw_seq = numpy.random.SeedSequence(seed).spawn(2)
    p = 1.0 / numpy.arange(1, vocabulary + 1, dtype=numpy.float64) \
        ** exponent
    ids_of_rank = numpy.random.default_rng(order_seq).permutation(
        vocabulary).astype(numpy.int32)
    ranks = numpy.random.default_rng(draw_seq).choice(
        vocabulary, size=(total, length), p=p / p.sum())
    return ids_of_rank[ranks]


class SeededTokenLoader(TokenLoader):
    """Validation rows first, then train, as every full-batch loader
    of the program lays them out."""

    hide_from_registry = True

    def __init__(self, workflow, n_train, n_valid, length, vocabulary,
                 seed, exponent=1.0, **kwargs):
        spec = (seed, n_train + n_valid, length, vocabulary, exponent)

        def provider():
            ids = generate(*spec)
            return ids[n_valid:], ids[:n_valid]

        super(SeededTokenLoader, self).__init__(
            workflow, provider=provider, **kwargs)
