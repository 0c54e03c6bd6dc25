"""The benchmark's own data set: image-shaped samples from a seed.

A ``FullBatchLoader`` like any user's, so the trainer sees nothing
special: ``original_data`` / ``original_labels`` on the host, uploaded
and staged by the program's own code. What is the benchmark's is how
the bytes are made. ``SyntheticImageLoader`` draws float64 from one
``RandomState`` (102 s for 16.5k AlexNet samples, PR 21); here the
bytes are a keyed counter hash, computed block by block in place on a
few threads and mapped through a 256-entry table of pixel values in
the storage dtype. The same seed gives the same bytes whatever the
thread count, because a byte depends on its position alone.

A pixel is ``(b >> 1) + shade[label][channel]`` in 0..254, scaled to
[-1, 1]: uniform noise plus a colour cast per class, so that there is
something to learn and the loss can be held to "below the untrained
one" without leaning on label frequencies.
"""

import mmap
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy

from veles_tpu.loader.fullbatch import FullBatchLoader

#: samples to a block: one block is one task. At 64 AlexNet-sized
#: samples the one temporary of a block (the table lookup's result,
#: 20 MB) stays under malloc's mmap threshold and is reused, not
#: mapped and faulted in again for every block
BLOCK = 64
#: threads that fill blocks (numpy releases the GIL in every in-place
#: pass below)
THREADS = 8


def storage_dtype(name):
    if name == "bfloat16":
        import ml_dtypes
        return numpy.dtype(ml_dtypes.bfloat16)
    return numpy.dtype(name)


def empty_huge(shape, dtype):
    """``numpy.empty`` on an anonymous mapping that asks for huge
    pages. On the sealed machines the first touch of a 4 KiB page costs
    ~26 us (5.4 s for 800 MB against 0.56 s with the hint, measured in
    PR 22's sandbox): 8 GB of samples would spend a minute of set-up
    in page faults. For the same reason every pass below writes into
    memory that is allocated once."""
    count = int(numpy.prod(shape))
    buf = mmap.mmap(-1, max(count * numpy.dtype(dtype).itemsize, 1))
    try:
        buf.madvise(mmap.MADV_HUGEPAGE)
    except (AttributeError, OSError):
        pass  # no such hint here: plain pages, same bytes
    return numpy.frombuffer(buf, dtype, count).reshape(shape)


def _mix(words, tmp):
    """Chris Wellons' ``lowbias32`` integer hash, in place on uint32."""
    for shift, factor in ((16, 0x7feb352d), (15, 0x846ca68b), (16, None)):
        numpy.right_shift(words, shift, out=tmp)
        numpy.bitwise_xor(words, tmp, out=words)
        if factor is not None:
            numpy.multiply(words, numpy.uint32(factor), out=words)


def generate(seed, total, side, channels, n_classes, dtype):
    """``(data, labels)``: ``(total, side, side, channels)`` in
    ``dtype`` and int32 labels, a pure function of the arguments.

    Byte ``j`` of the data set is byte ``j % 4`` of
    ``mix(mix(j // 4) ^ key)``, ``key`` drawn from the seed: a keyed
    counter hash, so a block's bytes depend on its position alone and
    the thread count changes nothing."""
    root = numpy.random.SeedSequence(seed)
    label_seq, shade_seq, key_seq = root.spawn(3)
    labels = numpy.random.default_rng(label_seq).integers(
        0, n_classes, total, dtype=numpy.int32)
    shades = numpy.random.default_rng(shade_seq).integers(
        0, 128, (n_classes, channels), dtype=numpy.uint8)
    key = numpy.uint32(key_seq.generate_state(1)[0])
    table = (numpy.arange(256, dtype=numpy.float32) / 127.0 - 1.0
             ).astype(dtype)
    data = empty_huge((total, side, side, channels), dtype)
    # the lookup runs on same-width unsigned views: numpy's take is
    # several times slower on an extension dtype such as bfloat16
    bits = numpy.dtype("u%d" % dtype.itemsize)
    table_bits, data_bits = table.view(bits), data.view(bits)
    row = side * channels
    sample = side * row
    block_words = -(-BLOCK * sample // 4)
    counter = empty_huge((block_words,), numpy.uint32)
    counter[:] = numpy.arange(block_words, dtype=numpy.uint32)
    scratch = threading.local()

    def fill(start):
        if not hasattr(scratch, "words"):
            scratch.words = empty_huge((block_words,), numpy.uint32)
            scratch.tmp = empty_huge((block_words,), numpy.uint32)
        stop = min(start + BLOCK, total)
        words, tmp = scratch.words, scratch.tmp
        first = numpy.uint32((start * sample // 4) % 2 ** 32)
        numpy.add(counter, first, out=words)
        _mix(words, tmp)
        numpy.bitwise_xor(words, key, out=words)
        _mix(words, tmp)
        # rows of side * channels bytes: the colour cast is tiled to a
        # whole row, so the add runs over long contiguous rows and not
        # over an inner axis of three
        pixels = words.view(numpy.uint8)[:(stop - start) * sample]
        pixels = pixels.reshape(stop - start, side, row)
        numpy.right_shift(pixels, 1, out=pixels)
        cast = numpy.tile(shades[labels[start:stop]], side)[:, None]
        numpy.add(pixels, cast, out=pixels)
        data_bits[start:stop].reshape(pixels.shape)[...] = \
            table_bits[pixels]

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(0, total, BLOCK)))
    return data, labels


class SeededImageLoader(FullBatchLoader):
    """Validation samples first, then train, as every full-batch
    loader of the program lays them out."""

    hide_from_registry = True

    def __init__(self, workflow, n_train, n_valid, side, channels,
                 n_classes, seed, dtype="float32", **kwargs):
        super(SeededImageLoader, self).__init__(workflow, **kwargs)
        self._spec = (seed, n_train + n_valid, side, channels,
                      n_classes, storage_dtype(dtype))
        self._lengths = [0, n_valid, n_train]

    def load_dataset(self):
        data, labels = generate(*self._spec)
        self.original_data.reset(data)
        self.original_labels.reset(labels)
        self.class_lengths = list(self._lengths)
