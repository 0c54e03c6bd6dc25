"""Shape-aware kernel autotuner (veles_tpu/ops/autotune.py).

Covers the ISSUE 6 contract: cache round-trip (search -> persist ->
reload picks the same config without re-measuring), corrupt-cache-file
fallback, CPU no-measure fallback, env-knob precedence, and numerical
equivalence of every (op, config) candidate against the XLA reference
at small shapes. The search machinery itself runs on CPU through
Pallas interpret mode (``VELES_AUTOTUNE_FORCE=interpret``), the same
forced path the CI smoke step exercises.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import autotune

gemm_mod = autotune._gemm_mod()
from veles_tpu.ops.lrn import _call_bwd, _call_fwd  # noqa: E402
from veles_tpu.ops.reduce import pallas_column_reduce  # noqa: E402

RNG = numpy.random.RandomState(7)


@pytest.fixture
def tuner_env(tmp_path, monkeypatch):
    """Isolated cache file + fast interpret-mode search."""
    cache_file = str(tmp_path / "tune.json")
    monkeypatch.setenv("VELES_AUTOTUNE_CACHE", cache_file)
    monkeypatch.setenv("VELES_AUTOTUNE_FORCE", "interpret")
    monkeypatch.setenv("VELES_AUTOTUNE_ITERS", "1")
    monkeypatch.setenv("VELES_AUTOTUNE_BUDGET_S", "60")
    autotune.reset()
    yield cache_file
    autotune.reset()


def _rand(shape, dtype=numpy.float32, seed=3):
    return jnp.asarray(numpy.random.RandomState(seed)
                       .rand(*shape).astype(dtype) - 0.5)


# -- mode / env-knob precedence ---------------------------------------------

class TestModeResolution(object):
    def test_default_is_cache(self, monkeypatch):
        monkeypatch.delenv("VELES_AUTOTUNE", raising=False)
        assert autotune.mode() == "cache"

    def test_env_knob_wins_over_config(self, monkeypatch):
        from veles_tpu.config import root
        before = root.common.engine.get("autotune")
        root.common.engine["autotune"] = "search"
        try:
            monkeypatch.setenv("VELES_AUTOTUNE", "off")
            assert autotune.mode() == "off"
            monkeypatch.delenv("VELES_AUTOTUNE")
            assert autotune.mode() == "search"
        finally:
            root.common.engine["autotune"] = before

    def test_invalid_mode_falls_back_to_cache(self, monkeypatch):
        monkeypatch.setenv("VELES_AUTOTUNE", "bogus")
        assert autotune.mode() == "cache"

    def test_off_returns_default_without_touching_cache(
            self, monkeypatch, tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "off")
        monkeypatch.setattr(autotune, "get_cache", lambda *a: (
            pytest.fail("off mode must not consult the cache")))
        assert autotune.gemm_plan(128, 128, 128, "float32") == \
            ("default", None)

    def test_cpu_cache_mode_never_measures(self, monkeypatch,
                                           tuner_env):
        """cache mode + cold cache: a miss answers immediately."""
        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        monkeypatch.setattr(autotune, "_search", lambda *a: (
            pytest.fail("cache mode must never measure")))
        assert autotune.gemm_plan(128, 128, 128, "float32") == \
            ("default", None)

    def test_cpu_search_mode_without_force_never_measures(
            self, monkeypatch, tuner_env):
        """search mode on an untunable backend (CPU, no FORCE) must
        degrade to the default plan without blocking."""
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        monkeypatch.delenv("VELES_AUTOTUNE_FORCE")
        assert not autotune.tunable()
        monkeypatch.setattr(autotune, "_search", lambda *a: (
            pytest.fail("untunable backend must not measure")))
        assert autotune.gemm_plan(128, 128, 128, "float32") == \
            ("default", None)


# -- cache round-trip --------------------------------------------------------

class TestCacheRoundTrip(object):
    def test_search_persists_and_warm_reload_skips_measuring(
            self, monkeypatch, tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        impl, cfg = autotune.gemm_plan(128, 128, 128, "float32")
        assert impl in ("xla", "pallas")

        blob = json.load(open(tuner_env))
        assert blob["version"] == autotune.CACHE_VERSION
        [key] = [k for k in blob["entries"] if k.startswith("gemm|")]
        assert blob["entries"][key]["impl"] == impl

        # a fresh process (reset drops the in-memory singletons) in
        # cache mode must answer the SAME plan from disk, zero sweeps
        autotune.reset()
        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        monkeypatch.setattr(autotune, "_search", lambda *a: (
            pytest.fail("warm cache must not re-measure")))
        assert autotune.gemm_plan(128, 128, 128, "float32") == \
            (impl, cfg)

    def test_search_races_once_per_key(self, monkeypatch, tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        calls = []
        real = autotune._search

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)
        monkeypatch.setattr(autotune, "_search", counting)
        autotune.reduce_plan(256, 128, "float32")
        autotune.reduce_plan(256, 128, "float32")
        assert len(calls) == 1

    def test_corrupt_cache_file_is_empty_not_fatal(
            self, monkeypatch, tuner_env):
        with open(tuner_env, "w") as f:
            f.write("{not json")
        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        assert autotune.gemm_plan(128, 128, 128, "float32") == \
            ("default", None)
        # and a search-mode put self-heals the file
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        autotune.reduce_plan(256, 128, "float32")
        blob = json.load(open(tuner_env))
        assert blob["version"] == autotune.CACHE_VERSION

    def test_stale_schema_version_is_empty(self, monkeypatch,
                                           tuner_env):
        with open(tuner_env, "w") as f:
            json.dump({"version": -1, "entries": {"gemm|x": {}}}, f)
        assert len(autotune.get_cache()) == 0

    def test_search_under_jit_trace_defers_without_persisting(
            self, monkeypatch, tuner_env):
        """A consult from inside a jit trace cannot measure; it must
        answer default WITHOUT writing a poisoned entry, leaving the
        shape tunable by a later eager consult (gemm_bench --autotune
        runs eagerly; unit forward passes are jitted)."""
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        real_search, walked = autotune._search, []
        monkeypatch.setattr(
            autotune, "_search",
            lambda *a, **k: walked.append(a) or real_search(*a, **k))

        @jax.jit
        def traced(a, b):
            return gemm_mod.gemm(a, b)
        x = _rand((128, 128))
        traced(x, x).block_until_ready()
        # deferred, not searched-and-failed on tracers
        assert walked == []
        assert not os.path.exists(tuner_env) or not json.load(
            open(tuner_env))["entries"]
        # the same shape still tunes eagerly afterwards
        impl, _ = autotune.gemm_plan(128, 128, 128, "float32")
        assert impl in ("xla", "pallas")
        blob = json.load(open(tuner_env))
        assert all(e["impl"] != "default"
                   for e in blob["entries"].values())

    def test_trace_state_is_seen_from_inside_a_trace(self):
        """Pins the one JAX-private call the deferral rests on."""
        seen = []
        jax.jit(lambda x: (seen.append(autotune._trace_state_clean()),
                           x)[1])(1.0)
        assert seen == [False]
        assert autotune._trace_state_clean() is True

    def test_failed_baseline_does_not_mislabel_survivor(
            self, monkeypatch, tuner_env):
        """If the native baseline candidate fails to measure, the
        fastest survivor wins outright and the entry must not claim a
        surviving alternative as 'baseline'."""
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        real = autotune._measure
        baseline_impl = []

        def flaky(fn, args, iters=None):
            if not baseline_impl:  # first (= baseline) candidate
                baseline_impl.append(True)
                raise RuntimeError("baseline would not build")
            return real(fn, args, iters)
        monkeypatch.setattr(autotune, "_measure", flaky)
        impl, _ = autotune.gemm_plan(128, 128, 128, "float32")
        assert impl != "default"
        blob = json.load(open(tuner_env))
        [entry] = blob["entries"].values()
        assert entry["baseline_impl"] is None
        assert "baseline_ms" not in entry

    def test_failed_search_is_not_persisted(self, monkeypatch,
                                            tuner_env):
        """If every candidate fails to build/measure, nothing must be
        written: a transient failure must not become a permanent
        'default' winner on disk."""
        monkeypatch.setenv("VELES_AUTOTUNE", "search")

        def broken(*a, **kw):
            raise RuntimeError("measurement broke")
        monkeypatch.setattr(autotune, "_measure", broken)
        assert autotune.gemm_plan(128, 128, 128, "float32") == \
            ("default", None)
        assert not os.path.exists(tuner_env) or not json.load(
            open(tuner_env))["entries"]

    def test_warm_counts_entries(self, monkeypatch, tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        autotune.reduce_plan(256, 128, "float32")
        autotune.reset()
        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        assert autotune.warm() == 1
        monkeypatch.setenv("VELES_AUTOTUNE", "off")
        assert autotune.warm() == 0


# -- numerical equivalence of every candidate -------------------------------

class TestCandidateNumerics(object):
    """Every (op, config) candidate the searcher may pick must agree
    with the XLA reference — a fast wrong kernel must never win."""

    def test_gemm_candidates(self):
        m = n = k = 128
        a, b = _rand((m, k)), _rand((k, n), seed=4)
        ref = jnp.dot(a, b, preferred_element_type=jnp.float32)
        cands = autotune.gemm_candidates(m, n, k, "float32")
        assert cands[0] == ("xla", None)
        assert any(impl == "pallas" for impl, _ in cands)
        for impl, cfg in cands:
            if impl != "pallas":
                continue
            out = gemm_mod.pallas_gemm(
                a, b, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
                out_dtype=jnp.float32,
                dimension_semantics=autotune.ds_tuple(cfg),
                interpret=True)
            numpy.testing.assert_allclose(out, ref, rtol=1e-5,
                                          err_msg=str(cfg))

    def test_kahan_candidates(self):
        m = n = 128
        k = 256
        a, b = _rand((m, k)), _rand((k, n), seed=4)
        ref = (numpy.asarray(a, numpy.float64) @
               numpy.asarray(b, numpy.float64))
        for chunk in (None, 64, 128):
            out = gemm_mod._kahan_matmul_loop(a, b, chunk=chunk)
            numpy.testing.assert_allclose(out, ref, rtol=1e-4,
                                          atol=1e-6)
        for impl, cfg in autotune.gemm_candidates(m, n, k, "float32",
                                                  scratch=2):
            if impl != "pallas":
                continue
            out = gemm_mod.pallas_kahan_gemm(
                a, b, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
                dimension_semantics=autotune.ds_tuple(cfg),
                interpret=True)
            numpy.testing.assert_allclose(out, ref, rtol=1e-4,
                                          atol=1e-6, err_msg=str(cfg))

    def test_pairwise_parts_candidates(self):
        a, b = _rand((32, 64)), _rand((64, 16), seed=4)
        ref = numpy.asarray(a) @ numpy.asarray(b)
        for parts in (1, 2, 4, 8):
            out = gemm_mod.pairwise_matmul(a, b, parts=parts)
            numpy.testing.assert_allclose(out, ref, rtol=1e-4,
                                          atol=1e-6)

    @pytest.mark.parametrize("act", ["linear", "tanh", "sigmoid",
                                     "relu", "strict_relu"])
    def test_fused_epilogue_candidates(self, act):
        m, k, n = 128, 128, 128
        x, w = _rand((m, k)), _rand((k, n), seed=4)
        bias = _rand((n,), seed=5)
        ref = gemm_mod.epilogue_fn(act)(
            jnp.dot(x, w, preferred_element_type=jnp.float32) +
            bias.astype(jnp.float32))
        for impl, cfg in autotune.gemm_candidates(m, n, k, "float32"):
            if impl != "pallas":
                continue
            out = gemm_mod.pallas_gemm(
                x, w, bias=bias, activation=act,
                bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
                out_dtype=jnp.float32,
                dimension_semantics=autotune.ds_tuple(cfg),
                interpret=True)
            numpy.testing.assert_allclose(out, ref, rtol=1e-5,
                                          atol=1e-6, err_msg=str(cfg))

    @pytest.mark.parametrize("act", ["linear", "tanh", "sigmoid",
                                     "relu", "strict_relu"])
    def test_fused_linear_vjp_matches_xla_chain(self, act):
        """The custom VJP (residuals (x, w, y), from-y derivative
        forms) must reproduce XLA's gradients for the unfused chain."""
        m, k, n = 16, 128, 128
        x, w = _rand((m, k)), _rand((k, n), seed=4)
        bias = _rand((n,), seed=5)
        cfg = (128, 128, 128, ("parallel", "parallel", "arbitrary"),
               True)

        def fused(x, w, b):
            return gemm_mod.fused_linear(
                x, w, b, act, jnp.float32, cfg).sum()

        def chain(x, w, b):
            return gemm_mod.epilogue_fn(act)(
                jnp.dot(x, w, preferred_element_type=jnp.float32) +
                b).sum()

        got = jax.grad(fused, argnums=(0, 1, 2))(x, w, bias)
        want = jax.grad(chain, argnums=(0, 1, 2))(x, w, bias)
        for g, r, name in zip(got, want, "x w b".split()):
            numpy.testing.assert_allclose(
                g, r, rtol=2e-4, atol=2e-5,
                err_msg="%s grad (%s)" % (name, act))

    def test_lrn_block_rows_candidates(self):
        rows, c = 512, 64
        x = _rand((rows, c))
        g = _rand((rows, c), seed=4)
        ref_f = _call_fwd(x, 2.0, 1e-4, 0.75, 5, True, block_rows=512)
        ref_b = _call_bwd(x, g, 2.0, 1e-4, 0.75, 5, True,
                          block_rows=512)
        for br in (128, 256):
            out = _call_fwd(x, 2.0, 1e-4, 0.75, 5, True,
                            block_rows=br)
            numpy.testing.assert_allclose(out, ref_f, rtol=1e-5)
            out = _call_bwd(x, g, 2.0, 1e-4, 0.75, 5, True,
                            block_rows=br)
            numpy.testing.assert_allclose(out, ref_b, rtol=1e-5)

    def test_reduce_block_rows_candidates(self):
        x = _rand((512, 64))
        ref = numpy.asarray(x, numpy.float64).sum(axis=0)
        for br in (128, 256, 512):
            out = pallas_column_reduce(x, block_rows=br,
                                       interpret=True)
            numpy.testing.assert_allclose(out, ref, rtol=1e-5)


# -- tuned dispatch end-to-end ----------------------------------------------

class TestTunedDispatch(object):
    def test_search_plan_drives_gemm_dispatch(self, monkeypatch,
                                              tuner_env):
        """A forced Pallas winner in the cache re-routes gemm(); the
        result stays correct."""
        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        cfg = {"bm": 128, "bn": 128, "bk": 128,
               "ds": ["parallel", "parallel", "arbitrary"]}
        autotune.get_cache().put(
            autotune._key("gemm", m=128, n=128, k=128,
                          dtype="float32", ta=0, tb=0),
            {"impl": "pallas", "config": cfg})
        a, b = _rand((128, 128)), _rand((128, 128), seed=4)
        from veles_tpu.ops.gemm import gemm
        out = gemm(a, b)
        numpy.testing.assert_allclose(
            out, numpy.asarray(a) @ numpy.asarray(b), rtol=1e-5)

    def test_linear_plan_search_roundtrip(self, monkeypatch,
                                          tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        impl, cfg = autotune.linear_plan(128, 128, 128, "float32",
                                         "relu", "float32")
        assert impl in ("xla", "pallas")
        entry = json.load(open(tuner_env))["entries"]
        assert any(k.startswith("linear|") for k in entry)

    def test_all2all_fused_forward_matches_unfused(
            self, monkeypatch, tuner_env):
        """With a cached fused-linear winner, All2All.apply takes the
        fused kernel and matches the XLA chain output."""
        from veles_tpu.dummy import DummyWorkflow
        from veles_tpu.nn.all2all import All2AllRELU

        monkeypatch.setenv("VELES_AUTOTUNE", "off")
        wf = DummyWorkflow()
        unit = All2AllRELU(wf, output_sample_shape=(128,))
        x = _rand((16, 128))
        params = {"weights": _rand((128, 128), seed=8),
                  "bias": _rand((128,), seed=9)}
        ref = unit.apply(params, x)

        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        cfg = {"bm": 128, "bn": 128, "bk": 128,
               "ds": ["parallel", "parallel", "arbitrary"]}
        autotune.get_cache().put(
            autotune._key("linear", m=16, n=128, k=128,
                          dtype="float32", act="relu", out="float32"),
            {"impl": "pallas", "config": cfg})
        out = unit.apply(params, x)
        numpy.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_reduce_plan_xla_winner_dispatches_to_xla(
            self, monkeypatch, tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "cache")
        autotune.get_cache().put(
            autotune._key("col_reduce", m=64, n=32, dtype="float32"),
            {"impl": "xla", "config": None})
        x = _rand((64, 32))
        out = pallas_column_reduce(x)
        numpy.testing.assert_allclose(
            out, numpy.asarray(x).sum(axis=0), rtol=1e-5)

    def test_summary_reports_counters(self, monkeypatch, tuner_env):
        monkeypatch.setenv("VELES_AUTOTUNE", "search")
        autotune.reduce_plan(256, 128, "float32")
        s = autotune.summary()
        assert s["mode"] == "search"
        assert s["entries"]
        assert s["searches"] >= 1
