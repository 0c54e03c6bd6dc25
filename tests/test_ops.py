"""Kernel-level numerics tests vs numpy oracles (cf. tests/test_ocl_blas.py,
test_mean_disp_normalizer.py, test_random.py in the reference)."""

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import (gather_minibatch, gemm, join_arrays,
                           matrix_reduce, mean_disp_normalize)
from veles_tpu.ops.gemm import (_kahan_matmul_loop, kahan_matmul,
                                pairwise_matmul, pallas_kahan_gemm)
from veles_tpu.ops.normalize import compute_mean_disp
from veles_tpu.ops.random import fill_xorshift, uniform, xorshift128plus

RNG = numpy.random.RandomState(42)


class TestGemm(object):
    def setup_method(self, _):
        self.a = RNG.rand(48, 64).astype(numpy.float32)
        self.b = RNG.rand(64, 32).astype(numpy.float32)

    def test_level0_matches_numpy(self):
        out = gemm(jnp.asarray(self.a), jnp.asarray(self.b))
        numpy.testing.assert_allclose(out, self.a @ self.b, rtol=1e-5)

    @pytest.mark.parametrize("tb", [False, True])
    @pytest.mark.parametrize("ta", [False, True])
    @pytest.mark.parametrize("level", [0, 1, 2])
    def test_disciplines_match_float64(self, level, ta, tb):
        ref = (self.a.astype(numpy.float64) @
               self.b.astype(numpy.float64))
        out = gemm(jnp.asarray(self.a.T if ta else self.a),
                   jnp.asarray(self.b.T if tb else self.b),
                   transpose_a=ta, transpose_b=tb,
                   precision_level=level)
        assert out.dtype == jnp.float32
        numpy.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_alpha_beta_c(self):
        c = RNG.rand(48, 32).astype(numpy.float32)
        out = gemm(jnp.asarray(self.a), jnp.asarray(self.b), alpha=2.0,
                   beta=0.5, c=jnp.asarray(c))
        numpy.testing.assert_allclose(out, 2 * (self.a @ self.b) + 0.5 * c,
                                      rtol=1e-5)

    def test_kahan_beats_naive_on_hostile_input(self):
        # large cancellation: values spanning 8 orders of magnitude
        k = 4096
        a = (RNG.rand(4, k).astype(numpy.float32) *
             numpy.logspace(0, 8, k, dtype=numpy.float32))
        a[:, 1::2] *= -1
        b = numpy.ones((k, 4), numpy.float32)
        exact = a.astype(numpy.float64) @ b.astype(numpy.float64)
        naive = numpy.asarray(kahan_matmul(jnp.asarray(a), jnp.asarray(b),
                                           chunk=k))  # single chunk = plain
        kahan = numpy.asarray(kahan_matmul(jnp.asarray(a), jnp.asarray(b),
                                           chunk=64))
        err_kahan = numpy.abs(kahan - exact).max()
        err_naive = numpy.abs(naive - exact).max()
        assert err_kahan <= err_naive * 1.001

    @pytest.mark.parametrize("k", [256, 100])
    @pytest.mark.parametrize("chunk", [None, 64, 128])
    def test_kahan_chunks(self, chunk, k):
        """Every chunk length, and a K it does not divide (zero
        padding), against float64."""
        rng = numpy.random.RandomState(3)
        a = (rng.rand(128, k) - 0.5).astype(numpy.float32)
        b = (rng.rand(k, 128) - 0.5).astype(numpy.float32)
        ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
        out = kahan_matmul(jnp.asarray(a), jnp.asarray(b), chunk=chunk)
        numpy.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("k", [64, 100])
    @pytest.mark.parametrize("parts", [None, 1, 2, 4, 8])
    def test_pairwise_parts(self, parts, k):
        """Every split of K, and a K no power of two divides (100 = 4
        x 25: ``parts`` 8 falls to 4), against float64."""
        rng = numpy.random.RandomState(4)
        a = (rng.rand(32, k) - 0.5).astype(numpy.float32)
        b = (rng.rand(k, 16) - 0.5).astype(numpy.float32)
        ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
        out = pairwise_matmul(jnp.asarray(a), jnp.asarray(b), parts=parts)
        numpy.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)


class TestReduce(object):
    def test_ops(self):
        x = RNG.rand(33, 17).astype(numpy.float32)
        numpy.testing.assert_allclose(matrix_reduce(x, "sum", 0),
                                      x.sum(0), rtol=1e-5)
        numpy.testing.assert_allclose(matrix_reduce(x, "max", 1),
                                      x.max(1), rtol=1e-6)
        numpy.testing.assert_allclose(matrix_reduce(x, "mean", 0),
                                      x.mean(0), rtol=1e-5)
        numpy.testing.assert_array_equal(matrix_reduce(x, "argmax", 1),
                                         x.argmax(1))


class TestRandom(object):
    def test_xorshift128plus_deterministic(self):
        s = numpy.array([123456789, 987654321], dtype=numpy.uint64)
        s1, v1 = xorshift128plus(s)
        s2, v2 = xorshift128plus(s)
        assert v1 == v2
        _, v3 = xorshift128plus(s1)
        assert v3 != v1

    def test_fill_evolves_state(self):
        s = numpy.array([1, 2], dtype=numpy.uint64)
        s_after, out = fill_xorshift(s, 16)
        assert len(set(out.tolist())) > 10
        _, out2 = fill_xorshift(s, 16)
        numpy.testing.assert_array_equal(out, out2)  # same seed, same stream

    def test_uniform_range_and_reproducibility(self):
        import jax
        key = jax.random.PRNGKey(7)
        u = uniform(key, (1000,), vmin=-2.0, vmax=3.0)
        assert float(u.min()) >= -2.0 and float(u.max()) < 3.0
        u2 = uniform(key, (1000,), vmin=-2.0, vmax=3.0)
        numpy.testing.assert_array_equal(u, u2)


class TestGather(object):
    def test_basic(self):
        data = RNG.rand(10, 4).astype(numpy.float32)
        labels = numpy.arange(10, dtype=numpy.int32)
        idx = numpy.array([3, 7, 1], dtype=numpy.int32)
        mb, lbl = gather_minibatch(jnp.asarray(data), jnp.asarray(idx),
                                   jnp.asarray(labels))
        numpy.testing.assert_allclose(mb, data[idx])
        numpy.testing.assert_array_equal(lbl, labels[idx])

    def test_padding(self):
        data = RNG.rand(5, 3).astype(numpy.float32)
        labels = numpy.arange(5, dtype=numpy.int32)
        idx = numpy.array([4, -1, 2], dtype=numpy.int32)
        mb, lbl = gather_minibatch(jnp.asarray(data), jnp.asarray(idx),
                                   jnp.asarray(labels))
        numpy.testing.assert_allclose(mb[1], numpy.zeros(3))
        assert int(lbl[1]) == -1
        numpy.testing.assert_allclose(mb[2], data[2])

    def test_no_labels(self):
        data = RNG.rand(5, 3).astype(numpy.float32)
        idx = numpy.array([0, 1], dtype=numpy.int32)
        mb, lbl = gather_minibatch(jnp.asarray(data), jnp.asarray(idx))
        assert lbl is None
        numpy.testing.assert_allclose(mb, data[:2])


class TestNormalize(object):
    def test_matches_formula(self):
        x = RNG.rand(8, 5).astype(numpy.float32)
        mean = x.mean(0)
        rdisp = 1.0 / (x.max(0) - x.min(0))
        out = mean_disp_normalize(jnp.asarray(x), jnp.asarray(mean),
                                  jnp.asarray(rdisp))
        numpy.testing.assert_allclose(out, (x - mean) * rdisp, rtol=1e-5)

    def test_compute_mean_disp(self):
        x = RNG.rand(100, 7).astype(numpy.float32)
        mean, rdisp = compute_mean_disp(jnp.asarray(x))
        numpy.testing.assert_allclose(mean, x.mean(0), rtol=1e-5)
        numpy.testing.assert_allclose(rdisp, 1.0 / (x.max(0) - x.min(0)),
                                      rtol=1e-4)

    def test_constant_feature_guard(self):
        x = numpy.ones((10, 2), numpy.float32)
        mean, rdisp = compute_mean_disp(jnp.asarray(x))
        assert numpy.isfinite(numpy.asarray(rdisp)).all()


class TestJoin(object):
    def test_join_flattens(self):
        a = RNG.rand(4, 2, 3).astype(numpy.float32)
        b = RNG.rand(4, 5).astype(numpy.float32)
        out = join_arrays(jnp.asarray(a), jnp.asarray(b))
        assert out.shape == (4, 11)
        numpy.testing.assert_allclose(out[:, :6], a.reshape(4, 6))

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            join_arrays()


@pytest.mark.parametrize("bm,bn,bk", [(256, 256, 512), (128, 128, 128),
                                      (64, 256, 256)])
def test_pallas_kahan_gemm_matches_loop_kahan(bm, bn, bk):
    """The Pallas Kahan carrier (precision_level=1 on TPU), run by the
    interpreter at three tilings, must agree with the fori-loop Kahan
    and with float64 to f32 roundoff; ``gemm`` off the TPU takes the
    loop itself, so this asserts the dispatch contract both ways."""
    rng = numpy.random.RandomState(5)
    a = (rng.rand(256, 512) - 0.5).astype("f")
    b = (rng.rand(512, 256) - 0.5).astype("f")
    ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
    a, b = jnp.asarray(a), jnp.asarray(b)
    loop = numpy.asarray(_kahan_matmul_loop(a, b))
    fused = numpy.asarray(pallas_kahan_gemm(a, b, bm=bm, bn=bn, bk=bk,
                                            interpret=True))
    numpy.testing.assert_allclose(fused, loop, rtol=1e-6, atol=1e-4)
    numpy.testing.assert_allclose(fused, ref, rtol=1e-4, atol=1e-5)
    via_gemm = numpy.asarray(gemm(a, b, precision_level=1))
    numpy.testing.assert_allclose(via_gemm, loop, rtol=1e-6, atol=1e-4)


class TestSolverState(object):
    def test_sgd_state_structure_mirrors_input(self):
        """A pre-r4 snapshot's opt_state has no 'step' counter; the
        update must not add one (the lax.scan carry pytree would
        change structure mid-resume). Fresh init-built state carries
        and advances it."""
        import jax.numpy as jnp
        from veles_tpu.nn.optim import get_solver
        sgd = get_solver("sgd")
        params = {"w": jnp.ones((3,))}
        grads = {"w": jnp.ones((3,))}
        hp = {"learning_rate": 0.1}
        fresh = sgd.init(params)
        assert "step" in fresh
        _, out = sgd.update(params, grads, fresh, hp)
        assert float(out["step"]) == 1.0
        legacy = {"velocity": {"w": jnp.zeros((3,))}}
        _, out = sgd.update(params, grads, legacy, hp)
        assert set(out) == {"velocity"}
