"""Small sample workflows mirroring the reference's Znicz sample set
(``.coveragerc:50-66``: wine, lines, kanji, channels — the samples the
reference shipped beyond the BASELINE configs).

The original datasets are not fetchable here (zero egress), so each
sample pairs its topology with a committed deterministic generator of
the same shape and difficulty class: ``wine`` (13-feature tabular,
3 classes), ``lines`` (oriented-stroke images, 4 angle classes — the
reference's conv primer), ``kanji`` (100-class warped glyph pairs on
the golden-digit renderer), and ``channels`` (TV-channel LOGO
recognition — the one sample whose distinctive capability is loading
class-per-directory image TREES from disk: ``generate_channels_dataset``
renders synthetic station logos into per-channel directories and
:class:`ChannelsWorkflow` trains through the real
``FileImageLoader``/scanner/decoder path, not an in-memory provider).
All run fused through StandardWorkflow.
"""

import numpy

from veles_tpu.loader.fullbatch import ProviderLoader
from veles_tpu.standard_workflow import StandardWorkflow


class WineProvider(object):
    """Tabular 13-feature, 3-class mixture dataset (UCI wine's shape):
    class-conditional Gaussians with overlapping covariance so a
    linear model errs a few percent, like the original."""

    def __init__(self, n_train=400, n_valid=100, seed=11):
        self.n_train = n_train
        self.n_valid = n_valid
        self.seed = seed

    def __call__(self):
        rng = numpy.random.RandomState(self.seed)
        total = self.n_train + self.n_valid
        labels = rng.randint(0, 3, total).astype(numpy.int32)
        centers = rng.randn(3, 13).astype(numpy.float32) * 1.5
        mix = rng.randn(13, 13).astype(numpy.float32) * 0.4
        data = centers[labels] + rng.randn(total, 13).astype(
            numpy.float32) @ mix
        return (data[:self.n_train], labels[:self.n_train],
                data[self.n_train:], labels[self.n_train:])


class LinesProvider(object):
    """Oriented-stroke images, 4 classes (horizontal / vertical / the
    two diagonals) — the shape of the reference's ``lines`` conv
    sample."""

    def __init__(self, n_train=800, n_valid=200, side=16, seed=5):
        self.n_train = n_train
        self.n_valid = n_valid
        self.side = side
        self.seed = seed

    def _draw(self, rng, klass):
        side = self.side
        img = rng.rand(side, side).astype(numpy.float32) * 0.25
        c = rng.randint(side // 4, 3 * side // 4)
        span = numpy.arange(side)
        if klass == 0:                      # horizontal
            img[c, :] += 1.0
        elif klass == 1:                    # vertical
            img[:, c] += 1.0
        elif klass == 2:                    # main diagonal
            off = rng.randint(-side // 4, side // 4)
            idx = numpy.clip(span + off, 0, side - 1)
            img[span, idx] += 1.0
        else:                               # anti-diagonal
            off = rng.randint(-side // 4, side // 4)
            idx = numpy.clip(side - 1 - span + off, 0, side - 1)
            img[span, idx] += 1.0
        return numpy.clip(img, 0.0, 1.0)

    def __call__(self):
        rng = numpy.random.RandomState(self.seed)
        total = self.n_train + self.n_valid
        labels = rng.randint(0, 4, total).astype(numpy.int32)
        data = numpy.stack([self._draw(rng, int(k)) for k in labels])
        data = data[..., None]  # NHWC
        return (data[:self.n_train], labels[:self.n_train],
                data[self.n_train:], labels[self.n_train:])


class KanjiProvider(object):
    """Many-class glyph classification (the reference ``kanji``
    sample's shape): each class is an ordered PAIR of digit glyphs
    rendered side by side (10×10 = 100 classes), warped per sample
    with the golden-digit renderer — small images, many classes, high
    intra-class variation."""

    def __init__(self, n_train=4000, n_valid=800, seed=17):
        self.n_train = n_train
        self.n_valid = n_valid
        self.seed = seed

    def __call__(self):
        from veles_tpu.datasets import _render
        rng = numpy.random.RandomState(self.seed)
        total = self.n_train + self.n_valid
        labels = rng.randint(0, 100, total).astype(numpy.int32)
        data = numpy.zeros((total, 24, 48), numpy.float32)
        for i, lbl in enumerate(labels):
            left = _render(int(lbl) // 10, rng, size=24)
            right = _render(int(lbl) % 10, rng, size=24)
            data[i, :, :24] = left
            data[i, :, 24:] = right
        return (data[:self.n_train], labels[:self.n_train],
                data[self.n_train:], labels[self.n_train:])


class TabularLoader(ProviderLoader):
    """Device-resident full batch over any (tx, ty, vx, vy) provider,
    mean/dispersion-normalized by default (the wine sample's recipe)."""

    hide_from_registry = True

    def __init__(self, workflow, provider=None, **kwargs):
        kwargs.setdefault("normalization_type", "mean_disp")
        super(TabularLoader, self).__init__(workflow, provider=provider,
                                            **kwargs)


class WineWorkflow(StandardWorkflow):
    """13 → 10 tanh → 3 softmax (the reference wine sample's shape)."""

    hide_from_registry = True

    def __init__(self, workflow=None, provider=None, minibatch_size=50,
                 **kwargs):
        provider = provider or WineProvider()
        kwargs.setdefault("learning_rate", 0.1)
        kwargs.setdefault("loss", "softmax")
        super(WineWorkflow, self).__init__(
            workflow,
            loader=lambda w: TabularLoader(
                w, provider=provider, minibatch_size=minibatch_size),
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 10},
                {"type": "softmax", "output_sample_shape": 3},
            ], **kwargs)


class KanjiWorkflow(StandardWorkflow):
    """Conv net over glyph pairs, 100 classes (reference kanji
    sample's shape class). At the defaults (20k samples, momentum 0.9
    with the learning rate scaled down to keep the same effective
    step) it reaches **3.95%** validation error in 20 epochs on one
    chip — the r3 momentum-free recipe (lr 0.2) plateaued at 7.1%;
    lr-decay variants at this budget undertrain (r4 sweep)."""

    hide_from_registry = True

    def __init__(self, workflow=None, provider=None, minibatch_size=100,
                 **kwargs):
        provider = provider or KanjiProvider(n_train=20000,
                                             n_valid=2000)
        kwargs.setdefault("learning_rate", 0.04)
        kwargs.setdefault("momentum", 0.9)
        kwargs.setdefault("loss", "softmax")
        super(KanjiWorkflow, self).__init__(
            workflow,
            loader=lambda w: TabularLoader(
                w, provider=provider, minibatch_size=minibatch_size,
                normalization_type="none"),
            layers=[
                {"type": "conv_relu", "n_kernels": 16, "kx": 5, "ky": 5},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "conv_relu", "n_kernels": 32, "kx": 3, "ky": 3},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "all2all_relu", "output_sample_shape": 128},
                {"type": "softmax", "output_sample_shape": 100},
            ], **kwargs)


def generate_channels_dataset(directory, n_channels=6, per_class=30,
                              side=32, seed=21):
    """Render a synthetic TV-channel-logo dataset into
    ``<directory>/{train,validation}/<channel-name>/*.png``.

    Each "channel" gets a distinct geometric emblem (bars / disc /
    frame / checker / stripes / cross) with per-image position jitter
    and background noise — the channels problem's shape (small images,
    one logo class per directory) without its unfetchable data. Returns
    the (train_paths, validation_paths) roots for
    :class:`~veles_tpu.loader.image.FileImageLoader`."""
    import os

    from PIL import Image

    rng = numpy.random.RandomState(seed)
    names = ["channel%02d" % i for i in range(n_channels)]

    def emblem(klass, jitter):
        img = (rng.rand(side, side, 3) * 60).astype(numpy.uint8)
        yy, xx = numpy.mgrid[0:side, 0:side]
        cy, cx = side // 2 + jitter[0], side // 2 + jitter[1]
        color = numpy.zeros(3, numpy.uint8)
        color[klass % 3] = 230
        color[(klass + 1) % 3] = 120 if klass >= 3 else 0
        kind = klass % 6
        if kind == 0:
            mask = (xx // 4) % 2 == 0                       # bars
        elif kind == 1:
            mask = (yy - cy) ** 2 + (xx - cx) ** 2 < (side // 3) ** 2
        elif kind == 2:
            border = side // 5
            mask = ((numpy.minimum.reduce([yy, xx, side - 1 - yy,
                                           side - 1 - xx]) > border) &
                    (numpy.minimum.reduce([yy, xx, side - 1 - yy,
                                           side - 1 - xx]) < 2 * border))
        elif kind == 3:
            mask = ((yy // 4) + (xx // 4)) % 2 == 0         # checker
        elif kind == 4:
            mask = (yy // 4) % 2 == 0                       # stripes
        else:
            mask = (abs(yy - cy) < 3) | (abs(xx - cx) < 3)  # cross
        img[mask] = color
        return img

    splits = {"train": per_class, "validation": max(per_class // 4, 2)}
    for split, count in splits.items():
        for klass, name in enumerate(names):
            d = os.path.join(directory, split, name)
            os.makedirs(d, exist_ok=True)
            for i in range(count):
                jitter = rng.randint(-3, 4, size=2)
                Image.fromarray(emblem(klass, jitter)).save(
                    os.path.join(d, "frame%03d.png" % i))
    return ([os.path.join(directory, "train")],
            [os.path.join(directory, "validation")])


class ChannelsWorkflow(StandardWorkflow):
    """Conv net over channel-logo image directories (reference
    ``channels`` sample family): the loader is the real directory-tree
    :class:`~veles_tpu.loader.image.FileImageLoader` — scan, decode,
    resize, normalize — with labels from directory names."""

    hide_from_registry = True

    def __init__(self, workflow=None, train_paths=(),
                 validation_paths=(), n_classes=6, minibatch_size=30,
                 size=(32, 32), **kwargs):
        from veles_tpu.loader.image import FileImageLoader
        kwargs.setdefault("learning_rate", 0.05)
        kwargs.setdefault("loss", "softmax")
        loader_kwargs = {
            "train_paths": tuple(train_paths),
            "validation_paths": tuple(validation_paths),
            "size": size, "minibatch_size": minibatch_size,
            "normalization_type": "linear",
        }
        super(ChannelsWorkflow, self).__init__(
            workflow,
            loader=lambda w: FileImageLoader(w, **loader_kwargs),
            layers=[
                {"type": "conv_relu", "n_kernels": 12, "kx": 5, "ky": 5},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "conv_relu", "n_kernels": 24, "kx": 3, "ky": 3},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "all2all_relu", "output_sample_shape": 64},
                {"type": "softmax", "output_sample_shape": n_classes},
            ], **kwargs)


class SequenceProvider(object):
    """Needle-token sequence classification (the attention sample's
    task): every sample is a (seq, dim) block of noise tokens with ONE
    position carrying one of ``n_classes`` fixed key patterns; the
    label is which pattern. Content-based lookup across positions —
    attention's home turf (the 2015 reference has no sequence models
    at all)."""

    def __init__(self, n_train=1600, n_valid=320, seq=16, dim=16,
                 n_classes=8, seed=23):
        self.args = (n_train, n_valid, seq, dim, n_classes, seed)

    def __call__(self):
        n_train, n_valid, seq, dim, n_classes, seed = self.args
        rng = numpy.random.RandomState(seed)
        patterns = rng.randn(n_classes, dim).astype(numpy.float32) * 2.0

        def make(n):
            x = rng.randn(n, seq, dim).astype(numpy.float32) * 0.3
            y = rng.randint(0, n_classes, n).astype(numpy.int32)
            pos = rng.randint(0, seq, n)
            x[numpy.arange(n), pos] = patterns[y] + \
                rng.randn(n, dim).astype(numpy.float32) * 0.2
            return x, y

        tx, ty = make(n_train)
        vx, vy = make(n_valid)
        return tx, ty, vx, vy


class SequenceWorkflow(StandardWorkflow):
    """Attention stack over token sequences: the beyond-reference
    long-context building block as a full training workflow — runs
    FUSED through the same step compiler as every other sample, and
    each attention layer can switch to ring attention on a seq mesh
    (``MultiHeadAttentionForward.use_ring``). ``moe=True`` inserts a
    Switch-style expert FFN between the attention layers
    (``MoEForward.use_experts`` shards it over an expert mesh).

    A toy of the two sharded schedules, on float features with one
    label a sequence. The repo's TOKEN model — embedding, rotary
    latent attention, dropless top-k experts with a shared expert,
    multi-token prediction, a per-token loss — is
    ``models/latent_moe_lm.py`` (``latent_attention``, ``moe`` with
    ``capacity_factor=None``, ``token_merge``, ``vocabulary_head``),
    which the benchmark runs at published widths."""

    hide_from_registry = True

    def __init__(self, workflow=None, provider=None, minibatch_size=80,
                 heads=4, n_classes=8, moe=False, n_experts=4,
                 **kwargs):
        provider = provider or SequenceProvider(n_classes=n_classes)
        kwargs.setdefault("learning_rate", 0.1)
        kwargs.setdefault("loss", "softmax")
        layers = [
            {"type": "attention", "heads": heads, "causal": False},
        ]
        if moe:
            layers.append({"type": "moe", "n_experts": n_experts})
        layers += [
            {"type": "attention", "heads": heads, "causal": False},
            {"type": "softmax", "output_sample_shape": n_classes},
        ]
        super(SequenceWorkflow, self).__init__(
            workflow,
            loader=lambda w: TabularLoader(
                w, provider=provider, minibatch_size=minibatch_size,
                sequence=True, normalization_type="none"),
            layers=layers, **kwargs)


class LinesWorkflow(StandardWorkflow):
    """Small conv net over oriented strokes (reference lines sample)."""

    hide_from_registry = True

    def __init__(self, workflow=None, provider=None, minibatch_size=50,
                 **kwargs):
        provider = provider or LinesProvider()
        kwargs.setdefault("learning_rate", 0.05)
        kwargs.setdefault("loss", "softmax")
        super(LinesWorkflow, self).__init__(
            workflow,
            loader=lambda w: TabularLoader(
                w, provider=provider, minibatch_size=minibatch_size,
                normalization_type="none"),
            layers=[
                {"type": "conv_relu", "n_kernels": 8, "kx": 3, "ky": 3},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "all2all_relu", "output_sample_shape": 32},
                {"type": "softmax", "output_sample_shape": 4},
            ], **kwargs)
