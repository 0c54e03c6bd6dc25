"""Direct unit coverage for the parallel layer's sharding RULES
(ISSUE 15 satellite): tp.py's column/row alternation, pp.py's
heterogeneous-stage packing and ep.py's contracts — the specs the GSPMD
step consumes, previously exercised only through whole-model e2e runs."""

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.sharding import PartitionSpec as P

from veles_tpu.parallel.mesh import build_mesh, named_sharding
from veles_tpu.parallel.tp import tp_param_shardings


class _FakeForward(object):
    """Minimal unit exposing the two attributes tp_param_shardings
    reads: ``param_arrays()`` keys and ``weights.shape``."""

    def __init__(self, *shape, bias=True):
        self.weights = numpy.zeros(shape, numpy.float32)
        self._bias = (numpy.zeros(shape[-1], numpy.float32)
                      if bias else None)

    def param_arrays(self):
        params = {"weights": self.weights}
        if self._bias is not None:
            params["bias"] = self._bias
        return params


class _NoParams(object):
    def param_arrays(self):
        return {}


# -- tp.py: the model-axis rules the GSPMD step consumes ---------------------


class TestTpParamShardings(object):
    def setup_method(self, _):
        self.mesh = build_mesh({"data": 2, "model": 4})

    def test_dense_column_row_alternation(self):
        stack = [_FakeForward(16, 32), _FakeForward(32, 32),
                 _FakeForward(32, 16), _FakeForward(16, 8)]
        specs = tp_param_shardings(stack, self.mesh)
        # layer 0: column (split fan-out), bias sharded with it
        assert specs[0]["weights"].spec == P(None, "model")
        assert specs[0]["bias"].spec == P("model")
        # layer 1: row (split fan-in), bias replicated (psum'd output)
        assert specs[1]["weights"].spec == P("model", None)
        assert specs[1]["bias"].spec == P()
        # layer 2: column again
        assert specs[2]["weights"].spec == P(None, "model")
        # LAST layer always replicated (feeds the loss)
        assert specs[3]["weights"].spec == P()
        assert specs[3]["bias"].spec == P()

    def test_conv_hwio_shards_channel_dims(self):
        stack = [_FakeForward(3, 3, 3, 32), _FakeForward(3, 3, 32, 64),
                 _FakeForward(64, 8)]
        specs = tp_param_shardings(stack, self.mesh)
        # conv column: split cout, spatial dims untouched
        assert specs[0]["weights"].spec == P(None, None, None, "model")
        # conv row: split cin
        assert specs[1]["weights"].spec == P(None, None, "model", None)

    def test_indivisible_dim_stays_replicated_without_phase_consume(self):
        # fan-out 30 % 4 != 0: layer 0 stays replicated and the
        # alternation phase is NOT consumed — layer 1 is the first
        # COLUMN layer, not a row one
        stack = [_FakeForward(16, 30), _FakeForward(30, 32),
                 _FakeForward(32, 8)]
        specs = tp_param_shardings(stack, self.mesh)
        assert specs[0]["weights"].spec == P()
        assert specs[1]["weights"].spec == P(None, "model")

    def test_paramless_and_odd_rank_layers_replicated(self):
        stack = [_NoParams(), _FakeForward(16, 32),
                 _FakeForward(8,), _FakeForward(32, 8)]
        specs = tp_param_shardings(stack, self.mesh)
        assert specs[0] == {}
        assert specs[1]["weights"].spec == P(None, "model")
        # rank-1 "weights": not a (fin, fout)/(HWIO) layer — replicated
        assert specs[2]["weights"].spec == P()
        assert len(specs) == len(stack)

    def test_specs_compile_into_a_sharded_program(self):
        """The specs are consumable as jit in_shardings — the exact
        seam the GSPMD step drives."""
        stack = [_FakeForward(16, 32), _FakeForward(32, 8),
                 _FakeForward(8, 4)]
        specs = tp_param_shardings(stack, self.mesh)
        params = [{k: jax.device_put(
            numpy.random.RandomState(i).rand(*v.shape).astype("f"),
            specs[i][k]) for k, v in fwd.param_arrays().items()}
            for i, fwd in enumerate(stack)]

        def forward(x, params):
            for layer in params:
                x = jnp.tanh(x @ layer["weights"] + layer["bias"])
            return x

        x = numpy.random.RandomState(9).rand(8, 16).astype("f")
        sharded = jax.jit(forward)(
            jax.device_put(x, named_sharding(self.mesh, "data")),
            params)
        ref = forward(jnp.asarray(x),
                      [{k: jnp.asarray(numpy.asarray(v))
                        for k, v in layer.items()} for layer in params])
        numpy.testing.assert_allclose(numpy.asarray(sharded),
                                      numpy.asarray(ref), atol=1e-6)


# -- pp.py: heterogeneous stage packing --------------------------------------


class TestStageParamPacking(object):
    def test_stack_and_unflatten_roundtrip_bit_exact(self):
        from veles_tpu.parallel.pp import stack_stage_params
        rng = numpy.random.RandomState(3)
        stages = [
            {"w": jnp.asarray(rng.randn(4, 6).astype("f")),
             "b": jnp.asarray(rng.randn(6).astype("f"))},
            {"k": jnp.asarray(rng.randn(2, 2, 3).astype("f"))},
            {},  # a parameterless stage packs to the zero vector
        ]
        stacked, unflattens = stack_stage_params(stages)
        assert stacked.shape[0] == 3
        # padded to the LARGEST stage; every stage row round-trips
        assert stacked.shape[1] == 4 * 6 + 6
        for i, stage in enumerate(stages):
            restored = unflattens[i](stacked[i])
            assert set(restored) == set(stage)
            for key in stage:
                assert (numpy.asarray(restored[key]) ==
                        numpy.asarray(stage[key])).all()

    def test_unflatten_preserves_dtypes(self):
        from veles_tpu.parallel.pp import stack_stage_params
        stages = [{"w": jnp.asarray(numpy.ones((2, 2), numpy.float32)),
                   "n": jnp.asarray(numpy.arange(3, dtype=numpy.int32))}]
        stacked, unflattens = stack_stage_params(stages)
        restored = unflattens[0](stacked[0])
        assert restored["n"].dtype == jnp.int32
        assert (numpy.asarray(restored["n"]) == [0, 1, 2]).all()

    def test_hetero_pipeline_rejects_stage_count_mismatch(self):
        from veles_tpu.parallel.pp import (hetero_pipeline_apply,
                                           stack_stage_params)
        mesh = build_mesh({"pipe": 8})
        stages = [{"w": jnp.zeros((2, 2))}] * 3  # 3 fns on an 8-axis
        stacked, unflattens = stack_stage_params(stages)
        with pytest.raises(ValueError, match="stage fns"):
            hetero_pipeline_apply(
                [lambda p, x: x] * 3, stages, stacked, unflattens,
                jnp.zeros((2, 4, 2)), mesh)


# -- ep.py: contracts --------------------------------------------------------


class TestExpertParallelContracts(object):
    def test_reference_rejects_indivisible_tokens(self):
        from veles_tpu.parallel.ep import moe_ffn_reference
        rng = numpy.random.RandomState(0)
        with pytest.raises(ValueError, match="divisible"):
            moe_ffn_reference(
                jnp.asarray(rng.randn(10, 4).astype("f")),
                jnp.asarray(rng.randn(4, 8).astype("f")),
                jnp.asarray(rng.randn(8, 4, 8).astype("f")),
                jnp.asarray(rng.randn(8, 8, 4).astype("f")), 8)

    def test_load_balance_loss_minimized_at_uniform(self):
        from veles_tpu.parallel.ep import load_balance_loss
        n, E = 64, 8
        # perfectly uniform hard routing with uniform probs: loss = 1
        probs = jnp.full((n, E), 1.0 / E)
        probs = probs.at[jnp.arange(n), jnp.arange(n) % E].add(1e-6)
        assert float(load_balance_loss(probs)) == pytest.approx(
            1.0, abs=1e-3)
        # collapse onto one expert: loss -> E
        collapsed = jnp.zeros((n, E)).at[:, 0].set(1.0)
        assert float(load_balance_loss(collapsed)) == pytest.approx(
            float(E), abs=1e-3)

    def test_load_balance_loss_mask_ignores_padded_rows(self):
        from veles_tpu.parallel.ep import load_balance_loss
        rng = numpy.random.RandomState(1)
        real = jax.nn.softmax(
            jnp.asarray(rng.randn(32, 4).astype("f")), axis=-1)
        # padding rows all route to expert 0 — unweighted, they skew
        # the stats; masked, they vanish
        pad = jnp.zeros((32, 4)).at[:, 0].set(1.0)
        probs = jnp.concatenate([real, pad])
        weights = jnp.concatenate([jnp.ones(32), jnp.zeros(32)])
        masked = float(load_balance_loss(probs, weights))
        clean = float(load_balance_loss(real))
        assert masked == pytest.approx(clean, rel=1e-5)
        assert float(load_balance_loss(probs)) > masked


# -- jax.shard_map as the parallel layer calls it -----------------------------


class TestShardMap(object):
    def test_real_shard_map_runs_a_psum(self):
        """``jax.shard_map`` with the keywords tp/pp/ep/sequence pass
        (``check_vma=False``): an explicit psum over the mesh."""
        import functools
        mesh = build_mesh({"model": 8})

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(P("model"),),
            out_specs=P(), check_vma=False)
        def total(x):
            return jax.lax.psum(jnp.sum(x), "model")

        x = jnp.arange(16, dtype=jnp.float32)
        assert float(total(x)) == float(jnp.sum(x))
