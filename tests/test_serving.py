"""Serving engine + router integration tests."""

import gc

import jax
import numpy as np
import pytest

from repro.core.energy_model import AccuracyModel, BilinearModel, LLMProfile
from repro.energy.meter import WallClockMeter
from repro.models import get_api
from repro.serving import EnergyAwareRouter, InferenceEngine, Request, Sampler
from repro.serving.decode_check import decode_logit_errors
from helpers import reduced


@pytest.fixture(scope="module")
def engine_pair():
    cfg, api = reduced("qwen3-1.7b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cached = InferenceEngine(cfg, params, kv_cache=True, bucket=8)
    uncached = InferenceEngine(cfg, params, kv_cache=False, bucket=8)
    return cfg, cached, uncached


@pytest.mark.slow  # real token-by-token generation loops on the engine
class TestEngine:
    def test_generates_requested_tokens(self, engine_pair):
        cfg, eng, _ = engine_pair
        toks = np.ones((2, 8), np.int32)
        out, stats = eng.generate({"tokens": toks}, 6)
        assert out.shape == (2, 6)
        assert (out >= 0).all() and (out < cfg.vocab_size).all()
        assert stats.prefill_s > 0 and stats.decode_s > 0
        assert stats.tau_in == 8 and stats.tau_out == 6

    def test_greedy_modes_agree(self, engine_pair):
        """KV-cached and paper-mode (recompute) greedy decoding must produce
        the same tokens — same computation, different caching."""
        cfg, cached, uncached = engine_pair
        rng = np.random.default_rng(1)
        toks = rng.integers(1, cfg.vocab_size, (2, 8)).astype(np.int32)
        a, _ = cached.generate({"tokens": toks}, 5)
        b, _ = uncached.generate({"tokens": toks}, 5)
        np.testing.assert_array_equal(a, b)

    def test_meter_integration(self):
        cfg, api = reduced("llama3.2-3b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(cfg, params, kv_cache=True,
                              meter=WallClockMeter(), bucket=8)
        _, stats = eng.generate({"tokens": np.ones((1, 8), np.int32)}, 4)
        assert stats.energy_j > 0
        assert stats.decode_energy_j > 0

    def test_temperature_sampling_seeded(self, engine_pair):
        cfg, eng, _ = engine_pair
        eng_t = InferenceEngine(cfg, eng.params, kv_cache=True, bucket=8,
                                sampler=Sampler(temperature=1.0), seed=42)
        toks = np.ones((1, 8), np.int32)
        a, _ = eng_t.generate({"tokens": toks}, 4)
        eng_t2 = InferenceEngine(cfg, eng.params, kv_cache=True, bucket=8,
                                 sampler=Sampler(temperature=1.0), seed=42)
        b, _ = eng_t2.generate({"tokens": toks}, 4)
        np.testing.assert_array_equal(a, b)


class TestRouter:
    def _profiles(self):
        return [
            LLMProfile("small", BilinearModel((0.1, 0.4, 1e-4)),
                       BilinearModel((1e-3, 4e-3, 1e-6)), AccuracyModel(50.0)),
            LLMProfile("big", BilinearModel((0.5, 2.0, 5e-4)),
                       BilinearModel((5e-3, 2e-2, 5e-6)), AccuracyModel(65.0)),
        ]

    def test_route_partitions_requests(self):
        router = EnergyAwareRouter(self._profiles(), zeta=0.5)
        reqs = [Request(i, np.zeros(16 + i, np.int32), 32) for i in range(10)]
        plan = router.route(reqs)
        assigned = sum(len(v) for v in plan.per_model.values())
        assert assigned == 10
        for name, rs in plan.per_model.items():
            for r in rs:
                assert r.model == name

    def test_zeta_extremes_route_differently(self):
        router_e = EnergyAwareRouter(self._profiles(), zeta=1.0)
        router_a = EnergyAwareRouter(self._profiles(), zeta=0.0)
        reqs = [Request(i, np.zeros(64, np.int32), 64) for i in range(8)]
        pe = router_e.route(list(reqs))
        pa = router_a.route(list(reqs))
        assert len(pe.per_model["small"]) > len(pa.per_model["small"])


class TestDecodeCheck:
    """Cached decode vs one full forward pass, through the engine's own
    programs, as the chip smoke runs it at published widths."""

    # reduced widths on the CPU: f32 agrees to rounding; bf16 reads below 0.01
    LIMITS = {"float32": 1e-4, "bfloat16": 0.05}

    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-130m"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_passes_and_control_fails(self, arch, dtype):
        cfg, api = reduced(arch)
        cfg = cfg.replace(param_dtype=dtype)
        eng = InferenceEngine(cfg, api.init_params(cfg, jax.random.PRNGKey(0)),
                              bucket=16)
        toks = np.random.default_rng(0).integers(
            1, cfg.vocab_size, (4, 32)).astype(np.int32)
        r = decode_logit_errors(eng, toks, 16)
        limit = self.LIMITS[dtype]
        assert r["error"] <= limit
        assert r["missing_token"] > limit
        # an SSM's decode reads no position; attention's reads it for RoPE
        # and the cache slot
        if cfg.family == "ssm":
            assert r["position_shift"] is None
        else:
            assert r["position_shift"] > limit
        # the check ran the served programs: two prefill lengths, one decode
        assert eng.compile_count == 3


class TestCompiledFn:
    def test_compiles_once_per_signature(self):
        cfg, api = reduced("qwen3-1.7b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(cfg, params, kv_cache=False, bucket=8)
        toks = np.ones((1, 8), np.int32)
        eng.generate({"tokens": toks}, 3)
        assert eng.compile_count == 3          # prefix lengths 8, 9, 10
        eng.generate({"tokens": toks}, 3)
        assert eng.compile_count == 3 and eng.compile_s > 0

    def test_dropped_engine_frees_weights_without_gc(self):
        cfg, api = reduced("qwen3-1.7b")
        live = lambda: sum(a.nbytes for a in jax.live_arrays())  # noqa: E731
        before = live()
        gc.disable()
        try:
            eng = InferenceEngine(cfg, api.init_params(cfg, jax.random.PRNGKey(0)),
                                  bucket=8)
            eng.generate({"tokens": np.ones((1, 8), np.int32)}, 2)
            assert live() > before
            del eng
            assert live() == before
        finally:
            gc.enable()
