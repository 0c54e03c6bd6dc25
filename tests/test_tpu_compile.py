"""The staged data set, compiled for a TPU that is described and not
attached (``jax.experimental.topologies``): no chip, no timing, but
the chip's own compiler and the runtime's own default layouts.

What PR 25 found on the v5e and what this file keeps true: a staged
shape whose trailing dims are not whole tiles gets a samples-minor
default layout, and each segment then opens with a copy of the whole
data set; the shape :func:`veles_tpu.train.step.staged_row_shape`
picks is read in place. The topology is described inside a fixture,
never at import: only one process may load the TPU's library, and
every xdist worker imports this file."""

import collections
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from veles_tpu import prng
from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import VALIDATION
from veles_tpu.telemetry import profiler
from veles_tpu.train import FusedTrainer
from veles_tpu.train import step

#: the flagship's entry conv and sample, a small head behind it: the
#: data set's shape and the gather are what is compiled for
LAYERS = [
    {"type": "conv_str", "n_kernels": 96, "kx": 11, "ky": 11,
     "sliding": (4, 4), "padding": 2, "space_to_depth": True},
    {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
    {"type": "all2all_str", "output_sample_shape": 32},
    {"type": "softmax", "output_sample_shape": 10},
]
SIDE, BATCH = 227, 128
#: 16,384 + 640 samples, the benchmark's resident traffic; and a count
#: that no tile divides
SAMPLES = (17024, 17001)


@pytest.fixture(scope="module")
def four_chips():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache and cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_eval(monkeypatch, one_chip, n_samples, row_shape=None):
    """The eval segment of a bf16 trainer staged under ``row_shape``
    (default: the rule), compiled for ``one_chip`` with the data set
    at ``n_samples``; and the data set's abstract value."""
    from veles_tpu.models.alexnet import (AlexNetWorkflow,
                                          SyntheticImageLoader)
    from veles_tpu.nn import precision
    jitted = {}

    class Capturing(FusedTrainer):
        def _compile_eval(self, fn):
            jitted["eval"] = super()._compile_eval(fn)
            return jitted["eval"]

    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    if row_shape is not None:
        monkeypatch.setattr(step, "staged_row_shape",
                            lambda n_elements, dtype: row_shape)
    prng.get().seed(7)
    prng.get("loader").seed(8)
    wf = AlexNetWorkflow(
        DummyLauncher(),
        loader_factory=lambda w: SyntheticImageLoader(
            w, n_train=BATCH, n_valid=BATCH, side=SIDE, n_classes=10,
            dtype="bfloat16", minibatch_size=BATCH),
        layers=[dict(layer) for layer in LAYERS], max_epochs=1)
    wf.initialize(device=Device(backend="cpu"))
    trainer = Capturing(wf)
    params, _ = trainer.pull_params()

    def abstract(x, shape=None):
        return jax.ShapeDtypeStruct(
            jnp.shape(x) if shape is None else shape,
            jnp.result_type(x), sharding=one_chip)

    data, truth = trainer._data_args
    dataset = abstract(data, (n_samples,) + data.shape[1:])
    idx = trainer._segment_indices(VALIDATION)
    compiled = jitted["eval"].lower(
        (dataset, abstract(truth, (n_samples,))),
        jax.tree_util.tree_map(abstract, params),
        abstract(idx, (5,) + idx.shape[1:])).compile()
    return compiled, dataset


@pytest.mark.parametrize("n_samples", SAMPLES)
def test_staged_dataset_is_read_in_place_on_v5e(
        monkeypatch, one_chip, no_compile_cache, n_samples):
    compiled, dataset = compiled_eval(monkeypatch, one_chip, n_samples)
    assert dataset.shape[1:] == (16, 10112)
    layout = compiled.input_formats[0][0][0].layout
    assert tuple(layout.major_to_minor) == (0, 1, 2)
    assert profiler.dataset_relayout_bytes(compiled, dataset.shape) == 0
    # and nothing hides a second data set among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < \
        dataset.size * dataset.dtype.itemsize // 4


def test_rows_of_partial_tiles_are_copied_every_call_on_v5e(
        monkeypatch, one_chip, no_compile_cache):
    """The shape staged until PR 25, (n, rows_y, rows_x * 48): the
    detector reads the copy that PR 24's trace showed, padded."""
    compiled, dataset = compiled_eval(monkeypatch, one_chip, SAMPLES[0],
                                      row_shape=(58, 2784))
    layout = compiled.input_formats[0][0][0].layout
    assert tuple(layout.major_to_minor) != (0, 1, 2)
    assert profiler.dataset_relayout_bytes(compiled, dataset.shape) == \
        SAMPLES[0] * 64 * 2816 * 2


# -- the latent-attention core (PR 28) ---------------------------------------

#: the token cell's core: 2 sequences, 20 heads, 4,096 positions, heads
#: of 256, 512 queries a block
CORE_SHAPE, CORE_BLOCK = (2, 20, 4096, 256), 512


def f32_score_blocks(text):
    """Shapes of the float32 arrays of an HLO ``text`` that are as
    large as a block of scores of :data:`CORE_SHAPE`: ``block`` or all
    queries against 1,024 keys or more."""
    batch, heads, seq, _ = CORE_SHAPE
    found = set()
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        shape = tuple(int(d) for d in dims.split(","))
        if len(shape) == 4 and shape[:2] == (batch, heads) and \
                shape[2] in (CORE_BLOCK, seq) and shape[3] >= 1024:
            found.add(shape)
    return found


@pytest.mark.parametrize("core", ["fused", "blockwise"])
def test_attention_core_keeps_its_scores_on_the_v5e(
        one_chip, no_compile_cache, core):
    """The gradient of the unit's core at the published shape, bf16,
    compiled for the v5e under the unit's scope. Fused: Mosaic custom
    calls whose ``op_name`` carries the unit's scope and ``/core``
    (what ``benchmark/readers/trace_lm.py`` joins device events by),
    forward and backward, and no float32 array of a score block's
    size anywhere in the program. XLA's blocks, the control: no such
    call, and the score blocks are there."""
    from veles_tpu.parallel import sequence
    fn = getattr(sequence, core + "_attention")
    assert sequence.fused_refusal(
        *(jax.ShapeDtypeStruct(CORE_SHAPE, jnp.bfloat16),) * 3,
        CORE_BLOCK) is None

    def loss(q, k, v):
        with step.device_scope("u03.latent_attention3"):
            with jax.named_scope("core"):
                out = fn(q, k, v, 1.0 / 16, CORE_BLOCK)
        return jnp.sum(out.astype(jnp.float32))

    operand = jax.ShapeDtypeStruct(CORE_SHAPE, jnp.bfloat16,
                                   sharding=one_chip)
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        *(operand,) * 3).compile().as_text()
    kernels = [re.search(r'op_name="([^"]*)"', line)
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    scores = f32_score_blocks(text)
    if core == "blockwise":
        assert not kernels and (2, 20, 512, 4096) in scores
        return
    assert len(kernels) == 3 and not scores, (kernels, scores)
    names = [m.group(1) for m in kernels if m]
    assert len(names) == 3
    for name in names:
        assert "veles.u03.latent_attention3" in name and "/core/" in name
    assert sum("transpose(" in name for name in names) == 2


def test_rematerialized_attention_runs_three_kernels_a_unit_on_the_v5e(
        monkeypatch, one_chip, no_compile_cache):
    """The gradient of two rematerialized latent-attention units at the
    token cell's shapes and published head sizes, bf16, compiled for
    the v5e (PR 30). With :func:`veles_tpu.remat.checkpoint` each unit
    is three Mosaic calls (forward, dk/dv, dq), every one under its
    unit's scope and ``/core``; a plain ``jax.checkpoint``, the
    control, runs the first unit's forward kernel twice (the second's
    primal pass is dead in the gradient of a sum). A further unit that
    keeps its core's output and row statistics adds no more than 100
    MB to the program's temporaries over what a further unit adds
    anyway (85.2 MB by the shapes; the kernel's raw outputs, each
    statistic 128 lanes wide, would be 420)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as flash
    from veles_tpu import remat
    from veles_tpu.nn import precision
    from veles_tpu.nn.attention import LatentAttentionForward
    for name in ("_flash_attention_impl", "_flash_attention_bwd"):
        assert callable(getattr(flash, name, None)), \
            "jaxlib's flash_attention has no %s any more: " \
            "parallel/sequence.py fused_attention calls it" % name
    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    batch, heads, seq, head = CORE_SHAPE
    units = [LatentAttentionForward(
        DummyLauncher(), name="latent_attention%d" % i, heads=heads,
        q_rank=768, kv_rank=512, qk_nope_dim=192, qk_rope_dim=64,
        v_dim=head, rope_theta=1e6, block=CORE_BLOCK) for i in (1, 3)]
    tags = [step.unit_tag(i, fwd) for i, fwd in zip((1, 3), units)]
    x = jax.ShapeDtypeStruct((batch, seq, 2048), jnp.bfloat16,
                             sharding=one_chip)
    params = [{k: jax.ShapeDtypeStruct(shape, jnp.float32,
                                       sharding=one_chip)
               for k, (shape, _) in fwd.param_shapes(x.shape).items()}
              for fwd in units]

    def compiled(wrap, n_units):
        def loss(params, x):
            for fwd, tag, p in zip(units, tags, params):
                with step.device_scope(tag):
                    x = wrap(fwd.apply)(p, x)
            return jnp.sum(x.astype(jnp.float32))
        program = jax.jit(jax.grad(loss, (0, 1))).lower(
            params[:n_units], x).compile()
        names = [re.search(r'op_name="([^"]*)"', line).group(1)
                 for line in program.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        return names, program.memory_analysis().temp_size_in_bytes

    def keeping(fn):
        return lambda *args: remat.checkpoint(fn)(*args)[0]

    (plain, plain_bytes), (kept, kept_bytes) = (
        compiled(wrap, 2) for wrap in (jax.checkpoint, keeping))
    assert len(plain) == 7 and len(kept) == 6, (plain, kept)
    for tag in tags:
        mine = [n for n in kept if "veles.%s" % tag in n and "/core/" in n]
        assert len(mine) == 3 and \
            sum("transpose(" in n for n in mine) == 2, kept
    further = (kept_bytes - compiled(keeping, 1)[1]) - \
        (plain_bytes - compiled(jax.checkpoint, 1)[1])
    assert 80e6 < further <= 100e6, further


# -- the grouped, banded core (PR 31) ----------------------------------------

#: the window/full cell's attention units at the published widths:
#: one sequence, 72 or 48 query heads over 8 key/value heads of 128,
#: 512 queries a block
BAND_SEQ, BAND_BLOCK = 2048, 512


@pytest.mark.parametrize("heads,window", [(72, 512), (48, None)])
def test_grouped_attention_unit_keeps_its_scores_and_its_scope_on_the_v5e(
        monkeypatch, one_chip, no_compile_cache, heads, window):
    """The gradient of a rematerialized grouped-attention unit at the
    published shape (a window layer of 72 query heads, a full layer of
    48; 8 key/value heads), bf16, compiled for the v5e under the unit's
    scope: two Mosaic calls a unit (forward and ONE backward; the
    forward once, its output and row statistics kept), each under the
    unit's scope and ``/core``; no array of a score block's size or of
    the whole square's anywhere in the program; the kernels' key and
    value operands have the 8 heads the projections made, not the
    query heads' count."""
    from veles_tpu import remat
    from veles_tpu.nn import precision
    from veles_tpu.nn.attention import GroupedAttentionForward
    from veles_tpu.parallel import sequence
    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fwd = GroupedAttentionForward(
        DummyLauncher(), name="grouped_attention3", heads=heads,
        kv_heads=8, head_dim=128, window=window, rope_theta=5e5,
        rotary_fraction=0.5, block=BAND_BLOCK, yarn=dict(
            factor=128.0, original_positions=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.4852030263919618))
    tag = step.unit_tag(3, fwd)
    x = jax.ShapeDtypeStruct((1, BAND_SEQ, 3072), jnp.bfloat16,
                             sharding=one_chip)
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32,
                                      sharding=one_chip)
              for k, (shape, _) in fwd.param_shapes(x.shape).items()}
    assert sequence.fused_refusal(
        jax.ShapeDtypeStruct((1, heads, BAND_SEQ, 128), jnp.bfloat16),
        *(jax.ShapeDtypeStruct((1, 8, BAND_SEQ, 128), jnp.bfloat16),) * 2,
        BAND_BLOCK, window) is None

    def loss(p, x):
        with step.device_scope(tag):
            out, _ = remat.checkpoint(lambda p, x: fwd.apply(p, x))(p, x)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        params, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in calls]
    assert len(names) == 2, names
    for name in names:
        assert "veles.%s" % tag in name and "/core/" in name, name
    assert sum("transpose(" in name for name in names) == 1
    # the kernels' own names are the device events' (the trace's rows)
    assert sorted(re.match(r"\s*%([a-z_]+)", line).group(1)
                  for line in calls) == [
        "band_attention_backward", "band_attention_forward"]
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        shape = tuple(int(d) for d in dims.split(","))
        # no scores by head: not a block's, not the square's
        assert not (len(shape) >= 3 and shape[-2] in (BAND_BLOCK, BAND_SEQ)
                    and shape[-1] in (BAND_BLOCK, 2 * BAND_BLOCK,
                                      BAND_SEQ)), shape
    # every kernel takes its keys and values at the 8 heads they have
    kv = "bf16[1,8,%d,128]" % BAND_SEQ
    for line in calls:
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}\}",
                             line).group(1)
        assert operands.count(kv) == 2, operands


#: the pre-routed cell's cores: one sequence of 16,384, 28 query heads
#: on 4 key/value heads of 128, 512 queries a block
LONG_SEQ, LONG_HEADS = 16384, (28, 4)


@pytest.mark.parametrize("window", [4096, None])
def test_banded_backward_fits_the_v5e_at_16k_positions(
        one_chip, no_compile_cache, window):
    """The banded core's gradient at the pre-routed cell's shapes,
    bf16, compiled for the v5e: the backward is one Mosaic call whose
    VMEM (the float32 dk/dv scratch of 16,384 positions, 16 MiB, the
    outputs' buffers and the blocks) the compiler accepts, so an
    overrun fails here and not on the chip."""
    from veles_tpu.ops import band_attention
    from veles_tpu.parallel import sequence
    q, kv = (jax.ShapeDtypeStruct((1, heads, LONG_SEQ, 128), jnp.bfloat16,
                                  sharding=one_chip)
             for heads in LONG_HEADS)
    assert sequence.fused_refusal(q, kv, kv, BAND_BLOCK, window) is None
    assert band_attention.backward_vmem_bytes(
        LONG_SEQ, 128, jnp.bfloat16) == 48 * 2**20

    def loss(q, k, v):
        out = sequence.banded_attention(q, k, v, 1.0 / 128 ** 0.5,
                                        BAND_BLOCK, window)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert sorted(re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1)
                  for line in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line) == [
        "band_attention_backward", "band_attention_forward"]


# -- the selected core (PR 33) ----------------------------------------------

#: one sequence of half the cell's length, the published widths: 32
#: query heads on 4 key/value heads of 128, an index of 16 heads of 64
#: that selects 2,048 keys, 512 queries a block
SELECTED_SEQ = 4096


def test_selected_attention_unit_keeps_its_four_scopes_on_the_v5e(
        monkeypatch, one_chip, no_compile_cache):
    """The value and gradient of a rematerialized grouped-attention
    unit under a learned selection of keys, bf16, compiled for the v5e
    under the unit's scope, its term of the objective differentiated
    with its output, as a train step compiles it: the core is the
    selected kernels, a forward and a probabilities kernel a block of
    queries in the forward pass, ONE backward kernel in the backward
    pass (the probabilities come out of it), each under the unit's
    scope and ``/core``; the four sub-scopes the core names itself with
    (``index``, ``select``, ``core``, ``index_loss``) survive in
    ``op_name``, each behind the unit's scope, in the forward and in the
    backward pass (the search runs in the forward alone: its masks are
    kept); no float32 array of two heads' scores for a block, let alone
    of the square (float32 arrays of 512 x 512 tiles are the heads'
    summed probabilities, a tile a block pair); and the program fits in a tenth of the chip beside
    its operands."""
    from veles_tpu import remat
    from veles_tpu.nn import precision
    from veles_tpu.nn.attention import GroupedAttentionForward
    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fwd = GroupedAttentionForward(
        DummyLauncher(), name="grouped_attention1", heads=32, kv_heads=4,
        head_dim=128, gated=False, qk_norm=True, rope_theta=1e7,
        block=512, index={"heads": 16, "head_dim": 64, "top_k": 2048})
    tag = step.unit_tag(1, fwd)
    x = jax.ShapeDtypeStruct((1, SELECTED_SEQ, 2048), jnp.bfloat16,
                             sharding=one_chip)
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32,
                                      sharding=one_chip)
              for k, (shape, _) in fwd.param_shapes(x.shape).items()}

    def loss(p, v):
        ctx = step.StepContext([fwd], [p], None, True)

        def fn(p, v):
            y, stats = fwd.apply_step(p, v, ctx)
            return y, stats[fwd.OBJECTIVE_STAT]
        with step.device_scope(tag):
            (y, term), kept = remat.checkpoint(fn)(p, v)
        # the output, a statistic a row and head, the masks of the
        # four blocks that end past 2,048 keys, a byte a pair
        assert kept == 32 * SELECTED_SEQ * (128 * 2 + 4) + 512 * (
            2560 + 3072 + 3584 + 4096)
        return jnp.sum(y.astype(jnp.float32)) + term

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    kernels = collections.Counter()
    for line in calls:
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "veles.%s" % tag in name and "/core/" in name, name
        # the kernels' own names are the device events' (the trace's rows)
        kernels[re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1),
                "transpose(" in name] += 1
    blocks = SELECTED_SEQ // 512
    assert kernels == {("select_attention_forward", False): blocks,
                       ("select_attention_probabilities", False): blocks,
                       ("select_attention_backward", True): 1}, kernels
    names = re.findall(r'op_name="([^"]*)"', text)
    seen = set()
    for name in names:
        if "veles.%s" % tag not in name:
            continue
        behind = name.split("veles.%s" % tag)[-1].split("/")
        for part in ("index", "select", "core", "index_loss", "proj"):
            if part in behind:
                seen.add((part, "transpose(" in name))
    assert {(part, False) for part in ("index", "select", "core",
                                       "proj")} <= seen
    assert {(part, True) for part in ("index", "core", "index_loss",
                                      "proj")} <= seen
    assert ("select", True) not in seen
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        shape = tuple(int(d) for d in dims.split(","))
        # the scores stay in the kernels: as large are a block's index
        # scores and summed probabilities (one head's worth) and the
        # summed probabilities as the backward kernel gives them, 512 x
        # 512 tiles, a block pair each; never a group's scores against
        # the keys up to a block's end, never the square
        tiles = len(shape) == 4 and shape[0] == 1 and shape[2:] == (512,
                                                                   512)
        assert tiles or not (
            len(shape) >= 3 and shape[-1] >= 512
            and shape[-2] in (512, SELECTED_SEQ)
            and math.prod(shape[:-2]) >= 2), shape
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.6e9


#: the selected cell's core: one sequence of 8,192, 32 query heads on 4
#: key/value heads of 128, 512 queries a block
KEYE_SEQ, KEYE_HEADS = 8192, (32, 4)


def test_selected_kernels_fit_the_v5e_at_8k_positions(one_chip,
                                                      no_compile_cache):
    """The selected kernels at the Keye cell's shapes, bf16, compiled
    for the v5e, each at its largest: the backward over the whole
    triangle (its float32 dk/dv scratch of 8,192 positions and a
    group's dq; the heads' summed probabilities out of it), the
    forward and the probabilities of the last block of queries (every
    head's queries held): the VMEM each asks for under
    :func:`sequence.selected_refusal`'s rule the compiler accepts, so
    an overrun fails here and not on the chip."""
    from veles_tpu.ops import select_attention
    from veles_tpu.parallel import sequence

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, kv = (shaped((1, heads, KEYE_SEQ, 128)) for heads in KEYE_HEADS)
    assert sequence.selected_refusal(q, kv, kv, 512) is None
    blocks = KEYE_SEQ // 512
    pairs = blocks * (blocks + 1) // 2
    last = blocks - 1
    scale = 1.0 / 128 ** 0.5
    programs = {
        "select_attention_backward": (
            lambda q, k, v, lse, tiles: select_attention.backward(
                q, k, v, q, lse, q, tiles, scale, True),
            (q, kv, kv, shaped((1, 32, KEYE_SEQ), jnp.float32),
             shaped((1, pairs, 512, 512), jnp.int8))),
        "select_attention_forward": (
            lambda q, k, v, mask: select_attention.block_forward(
                q, k, v, mask, last, scale),
            (q, kv, kv, shaped((1, 512, KEYE_SEQ), jnp.int8))),
        "select_attention_probabilities": (
            lambda q, k, lse, tiles: select_attention.block_probabilities(
                q, k, lse, tiles, last, scale),
            (q, kv, shaped((1, 32, 1, 512), jnp.float32),
             shaped((1, blocks, 512, 512), jnp.int8)))}
    for name, (fn, operands) in programs.items():
        text = jax.jit(fn).lower(*operands).compile().as_text()
        assert [re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1)
                for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line] == [
            name]


def test_short_conv_unit_writes_its_gates_once_in_bf16_on_the_v5e(
        monkeypatch, one_chip, no_compile_cache):
    """The gradient of a rematerialized short-convolution unit at the
    published width (2,048 channels, 3 taps, 8,192 positions), bf16,
    compiled for the v5e under the unit's scope: ``proj`` and ``mix``
    survive in ``op_name``, each behind the unit's scope, in the
    forward pass, in the forward run again and in the backward pass;
    no Mosaic call and no convolution of 2,048 groups (shifted
    multiply-adds that XLA fuses with the gates); the three gates
    reach HBM in bfloat16 and never in float32 (split in the compute
    dtype and widened a third at a time: widened before the split the
    compiler wrote ``f32[1,8192,6144]``, 201 MB, and the unit's
    gradient alone took 437 MB of temporaries where this takes
    336)."""
    from veles_tpu import remat
    from veles_tpu.nn import precision
    from veles_tpu.nn.short_conv import ShortConvForward
    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    fwd = ShortConvForward(DummyLauncher(), name="short_conv5", taps=3,
                           eps=1e-5)
    tag = step.unit_tag(5, fwd)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip)
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32,
                                      sharding=one_chip)
              for k, (shape, _) in fwd.param_shapes(x.shape).items()}

    def loss(p, v):
        with step.device_scope(tag):
            y, kept = remat.checkpoint(lambda p, v: fwd.apply(p, v))(p, v)
        assert kept == 0
        return jnp.sum(y.astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert "feature_group_count=2048" not in text
    seen = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        if "veles.%s" % tag not in name:
            continue
        behind = name.split("veles.%s" % tag)[-1].split("/")
        for part in ("proj", "mix"):
            if part in behind:
                seen.add((part, "transpose(" in name,
                          "rematted_computation" in behind))
    assert seen == {(part, *where) for part in ("proj", "mix")
                    for where in ((False, False), (True, True),
                                  (True, False))}
    # what a fusion hands on goes through HBM; what it holds inside
    # does not
    written = set()
    for out in re.findall(r" = (.*?) fusion\(", text):
        written |= set(re.findall(r"(f32|bf16)\[(?:1,)?([\d,]+)\]", out))
    assert ("bf16", "8192,6144") in written
    assert ("f32", "8192,6144") not in written
    # 369.8 MB with the value beside the gradient
    assert compiled.memory_analysis().temp_size_in_bytes < 4.0e8


# -- the sparse layer's combine (PR 32) ---------------------------------------

#: the most that the gradient of one sparse unit of a token cell may
#: hold in temporaries, MB. Read here with the v5e's compiler (PR 32):
#: the GLM cell's layer 4,971.4 by the parent's combine (one row a
#: (token, slot)), 4,967.3 by this one and 5,135.1 by this one as
#: plain ``weight * row`` without a checkpoint of its own (the
#: overflow branch then keeps its rows a second time, in float32, for
#: the weights' gradient); the Laguna cell's 2,153.6, 1,823.5 and
#: 1,957.0. At GLM's shape the two combines differ by less than a
#: refusion elsewhere in the unit would, so its limit lies between
#: this combine and the same without its checkpoint: what it guards
#: is the second copy of the rows. (What the combine removes is
#: asserted by shape below.) The Laguna limit is the parent's reading.
SPARSE_UNIT_MB = {"glm47flash-ep8share.pretrain4k": 5050.0,
                  "laguna-s21-ep32share.pretrain-1seq": 2153.6}


def sparse_unit_program(monkeypatch, one_chip, cell, **overrides):
    """``(unit, tag, shape of x, program)``: the gradient of one
    rematerialized dropless unit as the cell's configuration describes
    it (``overrides`` its keys), bf16, compiled for the v5e under the
    unit's scope."""
    import os
    from benchmark import harness
    from veles_tpu import remat
    from veles_tpu.nn import precision
    from veles_tpu.nn.moe import MoEForward
    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    bench = harness.Benchmark(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    config = bench.config(bench.cell(cell))
    descr = dict(next(d for d in config["layers"] if d["type"] == "moe"),
                 **overrides)
    batch, seq = config["batch"], config["layers"][0]["positions"]
    dim, hidden = config["hidden_size"], descr["hidden"]
    held, shared = descr["experts_held"][1], descr["shared_experts"]
    fwd = MoEForward(DummyLauncher(), name="moe4", **{
        k: v for k, v in descr.items() if k not in ("type", "remat")})
    tag = step.unit_tag(4, fwd)
    shapes = {"weights": (dim, fwd.n_experts), "norm": (dim,),
              "select_bias": (fwd.n_experts,),
              "gate": (held, dim, hidden), "up": (held, dim, hidden),
              "down": (held, hidden, dim),
              "shared_gate": (shared, dim, hidden),
              "shared_up": (shared, dim, hidden),
              "shared_down": (shared, hidden, dim)}
    params = {k: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)
              for k, dims in shapes.items()
              if k in ("weights", "up", "down") + fwd.extra}
    x = jax.ShapeDtypeStruct((batch, seq, dim), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        with step.device_scope(tag):
            (y, _), _ = remat.checkpoint(
                lambda p, x: fwd.apply_step(p, x, None))(p, x)
        return jnp.sum(y.astype(jnp.float32))

    return fwd, tag, x.shape, jax.jit(jax.grad(loss, (0, 1))).lower(
        params, x).compile()


@pytest.mark.parametrize("cell", sorted(SPARSE_UNIT_MB))
def test_sparse_unit_moves_rows_and_keeps_its_scopes_on_the_v5e(
        monkeypatch, one_chip, no_compile_cache, cell):
    """The gradient of one rematerialized dropless unit as a token
    cell's configuration describes it (``(2, 4096, 2048)``, 8 of 64
    experts held, top-4, a bound of 8,192 rows; ``(1, 2048, 3072)``,
    8 of 256, top-10, 2,048), bf16, compiled for the v5e under the
    unit's scope: it compiles, its operations keep the ``route``,
    ``experts`` and ``shared`` sub-scopes in ``op_name``, nothing in
    the program has the shape ``(tokens, top_k, dim)`` that a combine
    by slot gathers, and the program's temporaries stay under
    ``SPARSE_UNIT_MB``."""
    fwd, tag, (batch, seq, dim), program = sparse_unit_program(
        monkeypatch, one_chip, cell)
    text = program.as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for part in ("route", "experts", "shared"):
        mine = [n for n in names
                if "veles.%s" % tag in n and "/%s/" % part in n]
        assert mine, part
        assert any("transpose(" in n for n in mine), part
    assert "[%d,%d,%d]" % (batch * seq, fwd.top_k, dim) not in text
    temporaries = program.memory_analysis().temp_size_in_bytes / 1e6
    assert temporaries <= SPARSE_UNIT_MB[cell], temporaries


def test_the_overflow_in_chunks_keeps_less_than_the_unbounded_path(
        monkeypatch, one_chip, no_compile_cache):
    """The Keye cell's sparse unit (8,192 tokens, 16 of 128 experts
    held, top-8): the gradient under its bound of 16,384 rows, whose
    overflow runs in chunks of the bound, needs no more temporaries
    than the same unit without a bound, which routes all 65,536
    possible rows at once (v5e compiler, PR 39: 2,000.8 MB against
    2,181.8; 1,983.8 while each chunk's gradient was rounded on its
    own; 5,413.4 while the overflow was ``lax.cond``'s branch at
    65,536 rows and the cond's own gradient held both paths'
    residuals)."""
    cell = "keye-vl2-ep8share.pretrain8k-1seq"
    bounded = sparse_unit_program(monkeypatch, one_chip, cell)[3]
    unbounded = sparse_unit_program(monkeypatch, one_chip, cell,
                                    dispatch_rows=None)[3]
    mb = [p.memory_analysis().temp_size_in_bytes / 1e6
          for p in (bounded, unbounded)]
    assert mb[0] <= mb[1], mb


# -- the partitioned step's fenced backward pass (PR 34) --------------------

#: AlexNet's opening in small (a conv, an LRN and a pool in front of a
#: second conv) and two dense layers behind it
PARTITIONED_LAYERS = [
    {"type": "conv_str", "n_kernels": 32, "kx": 5, "ky": 5, "padding": 2},
    {"type": "norm", "n": 5, "alpha": 1e-4, "beta": 0.75},
    {"type": "max_pooling", "kx": 2, "ky": 2, "sliding": (2, 2)},
    {"type": "conv_str", "n_kernels": 64, "kx": 3, "ky": 3, "padding": 1},
    {"type": "all2all_str", "output_sample_shape": 128},
    {"type": "softmax", "output_sample_shape": 10},
]


def partitioned_trainer(monkeypatch, batch=64, side=24):
    """A bf16 ``GSPMDTrainer`` over ``PARTITIONED_LAYERS`` on a 4x1
    mesh of host devices, and the function it handed to
    ``_compile_train``."""
    from veles_tpu.models.alexnet import (AlexNetWorkflow,
                                          SyntheticImageLoader)
    from veles_tpu.nn import precision
    from veles_tpu.parallel import gspmd
    traced = {}

    class Capturing(gspmd.GSPMDTrainer):
        def _compile_train(self, fn):
            traced["fn"] = fn
            return super()._compile_train(fn)

    monkeypatch.setattr(precision, "_forced",
                        precision.POLICIES["bfloat16"])
    prng.get().seed(7)
    prng.get("loader").seed(8)
    wf = AlexNetWorkflow(
        DummyLauncher(),
        loader_factory=lambda w: SyntheticImageLoader(
            w, n_train=batch, n_valid=batch, side=side, n_classes=10,
            dtype="bfloat16", minibatch_size=batch),
        layers=[dict(layer) for layer in PARTITIONED_LAYERS], max_epochs=1)
    wf.initialize(device=Device(backend="cpu"))
    trainer = Capturing(wf, mesh=gspmd.gspmd_mesh(
        batch=4, devices=jax.devices("cpu")[:4]))
    return trainer, traced["fn"]


@pytest.fixture(scope="module")
def schedule_reader():
    """``scripts/partitioned_schedule.py``, whose readers of a
    scheduled text the tests below share."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "partitioned_schedule", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "partitioned_schedule.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def partitioned_text(monkeypatch, four_chips, batch=64):
    """The scheduled text of ``partitioned_trainer``'s train segment
    over two steps of a planned fetch, compiled for a 4x1 mesh of the
    described ``v5e:2x2`` (the trainer built on host devices, its mesh
    moved onto the described chips, its own ``_compile_train`` asked
    again: what ``scripts/partitioned_schedule.py`` does for the
    benchmark's cell)."""
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.parallel import dp, gspmd
    from veles_tpu.parallel.mesh import named_sharding
    trainer, fn = partitioned_trainer(monkeypatch, batch=batch)
    params, states = trainer.pull_params()
    trainer.mesh = gspmd.gspmd_mesh(batch=4, devices=four_chips)
    trainer._data_spec = named_sharding(trainer.mesh, trainer.axis)
    jitted = trainer._compile_train(fn)
    repl = named_sharding(trainer.mesh)

    def abstract(x, sharding, shape=None):
        return jax.ShapeDtypeStruct(
            jnp.shape(x) if shape is None else shape,
            jnp.result_type(x), sharding=sharding)

    idx = trainer._segment_indices(TRAIN)
    plan, _ = dp.plan_fetch(idx, 4, dp.exchange_capacity(idx.shape[1], 4))
    by_step = named_sharding(trainer.mesh, None, trainer.axis)
    operands = (
        tuple(abstract(a, trainer._data_spec) for a in trainer._data_args),
        jax.tree_util.tree_map(lambda v: abstract(v, repl), params),
        jax.tree_util.tree_map(lambda v: abstract(v, repl), states),
        dp.FetchPlan(*(abstract(a, by_step, (2,) + a.shape[1:])
                       for a in plan)),
        abstract(jax.random.PRNGKey(0), repl, (2, 2)))
    text = jitted.lower(*operands).compile().as_text()
    assert "is_scheduled=true" in text
    return text


def test_partitioned_step_fences_its_entry_unit_on_the_v5e(
        monkeypatch, four_chips, no_compile_cache, schedule_reader):
    """From the scheduled text of the train segment: behind the entry
    conv nothing of the LRN's backward is computed inside the conv's
    own backward (its weights gradient and its bias sum); every
    gradient all-reduce is synchronous, as the compiler's defaults
    have it; and the exchange of the dense gradient is the compiler's
    own, float32 partial products summed in float32 and rounded to
    bf16 once."""
    text = partitioned_text(monkeypatch, four_chips)
    backward = schedule_reader.backward_schedule(text)
    entry = [row for row in backward if "Bu00" in row[4]]
    assert entry and any(row[4]["Bu00"] for row in entry), backward
    assert not any("Bu01" in row[4] for row in entry), entry
    rows = [row for row in schedule_reader.collective_schedule(text)
            if row["gradient"]]
    assert rows and all(row["form"] == "sync" for row in rows), rows
    assert sum("bf16[9216,128]" in row["payload"] for row in rows) == 1
    # the sums are the compiler's: float32 partials in, one rounding out
    line = next(line for line in text.splitlines()
                if "bf16[9216,128]" in line and " all-reduce(" in line)
    add = re.search(r"to_apply=(%[\w.\-]+)", line).group(1)
    assert re.search(r"^%s \([\w.]+: f32\[\]" % re.escape(add), text, re.M)
    assert re.search(r"f32\[9216,128\]\S* (?:fusion|convolution|dot)\(",
                     text)


def test_partitioned_step_exchanges_the_planned_rows_on_the_v5e(
        monkeypatch, four_chips, no_compile_cache, schedule_reader):
    """The minibatch fetch in the scheduled text: ONE all-to-all of
    ``shards x capacity`` rows of the data set under ``veles.in`` (and
    one of as many labels), and no collective as large as the padded
    global minibatch, which the partitioner's gather on global ids
    all-reduced on every step; the gradient all-reduces stand as they
    were. (A batch of 256: at 64 a pair's capacity is a shard's whole
    share.)"""
    from veles_tpu.parallel import dp
    batch, row_bytes = 256, 24 * 24 * 3 * 2
    rows = schedule_reader.collective_schedule(
        partitioned_text(monkeypatch, four_chips, batch=batch))
    exchange = 4 * dp.exchange_capacity(batch, 4) * row_bytes
    fetch = [row for row in rows if "veles.in" in row["op_name"]]
    assert fetch and all(row["kind"] == "all-to-all" for row in fetch)
    assert [row["bytes"] for row in fetch
            if "bf16" in row["payload"]] == [exchange], fetch
    assert exchange < batch * row_bytes
    assert not [row for row in rows if not row["gradient"]
                and row["bytes"] >= batch * row_bytes], rows
    gradients = [row for row in rows if row["gradient"]]
    assert len(gradients) == 2 and all(
        row["form"] == "sync" for row in gradients), gradients


def test_partitioned_step_unfenced_computes_the_lrn_backward_twice(
        monkeypatch, four_chips, no_compile_cache, schedule_reader):
    """What the fence behind the entry unit is for: without it the
    v5e compiler takes the LRN's backward into the entry conv's own
    backward fusions as their producer."""
    from veles_tpu.parallel import dp
    monkeypatch.setattr(dp, "fenced", lambda x: x)
    backward = schedule_reader.backward_schedule(
        partitioned_text(monkeypatch, four_chips))
    assert any("Bu00" in row[4] and "Bu01" in row[4] for row in backward)


def test_partitioned_step_takes_no_compiler_option(monkeypatch):
    """``_compile_train`` hands ``jax.jit`` no compiler option, on
    any platform: the fence is the program's, and the compiler's own
    scheduling of it is left alone."""
    import veles_tpu.parallel.dp as dp
    seen = []
    jit = jax.jit

    def recording(fn, **kwargs):
        seen.append(kwargs)
        return jit(fn, **kwargs)

    monkeypatch.setattr(dp.jax, "jit", recording)
    partitioned_trainer(monkeypatch)
    train = [kw for kw in seen if "donate_argnums" in kw]
    assert train and not any("compiler_options" in kw for kw in train)
