"""Launch-layer unit tests: rules, legalization, cache specs, HLO parser.

The multi-device dry-run itself is exercised in test_dryrun_mini.py (in a
subprocess with forced host devices)."""

from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import shard
from repro.analysis.hlo import HLOModule, analyze_hlo_text
from repro.configs import INPUT_SHAPES, get_config
from repro.launch import sharding as shardrules
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache
from repro.models import get_api
from repro.models import cache as cachelib

AXES = {"data": 16, "model": 16}


class TestLegalizeSpec:
    def test_divisible_kept(self):
        out = shard.legalize_spec((64, 128), P("data", "model"), AXES)
        assert tuple(out) == ("data", "model")

    def test_relocates_kv_heads_to_seq(self):
        # [L, B, S, Hkv=8, D] with model on kv heads -> moves to S
        out = shard.legalize_spec((28, 128, 32768, 8, 128),
                                  P(None, "data", None, "model"), AXES)
        assert tuple(out) == (None, "data", "model")

    def test_relocates_odd_vocab_to_dmodel(self):
        out = shard.legalize_spec((92553, 2048), P("model", None), AXES)
        assert tuple(out) == (None, "model")

    def test_drops_when_nothing_fits(self):
        out = shard.legalize_spec((3, 5), P("model", None), AXES)
        assert tuple(out) == ()

    def test_tuple_axes(self):
        out = shard.legalize_spec((256, 7168), P(("data", "model"), None), AXES)
        assert tuple(out) == (("data", "model"),)


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self):
        before = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)

    def test_env_var_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == tmp_path
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_fixed_path_in_checkout_otherwise(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = use_compile_cache()
        assert path == CHECKOUT_CACHE_DIR
        assert path.parent == Path(__file__).resolve().parents[1]
        assert jax.config.jax_compilation_cache_dir == str(path)


class TestRules:
    def test_resolve_dedups_mesh_axes(self):
        rules = {"expert": "model", "mlp": "model"}
        spec = shard.resolve(("expert", "embed_w", "mlp"), rules)
        assert tuple(spec) == ("model",)

    def test_constrain_noop_without_rules(self):
        x = jax.numpy.ones((4, 4))
        assert shard.constrain(x, "batch", "mlp") is x

    def test_shape_overrides(self):
        tr = shardrules.shape_rule_overrides(INPUT_SHAPES["train_4k"])
        assert tr["seq"] == "model"
        dc = shardrules.shape_rule_overrides(INPUT_SHAPES["decode_32k"])
        assert dc["embed_w"] == "model" and dc["heads"] is None
        lg = shardrules.shape_rule_overrides(INPUT_SHAPES["long_500k"])
        assert lg["batch"] is None and lg["kv_seq"] == "data"

    def test_config_overrides_v3_experts(self):
        cfg = get_config("deepseek-v3-671b")
        ov = shardrules.config_rule_overrides(cfg)
        assert ov["expert"] == ("data", "model")


class TestCacheSpecs:
    @pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v3-671b",
                                      "mamba2-130m", "recurrentgemma-9b",
                                      "seamless-m4t-large-v2"])
    def test_cache_pspecs_structure_matches(self, arch):
        cfg = get_config(arch + "-reduced")
        api = get_api(cfg)
        cache = api.init_cache(cfg, 2, 32)
        rules = shard.make_rules()
        specs = shardrules.cache_pspecs(cache, rules)
        # identical pytree structure
        assert (jax.tree.structure(cache) ==
                jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)))


class TestOptStateSpecs:
    def test_adamw_mirrors_params(self):
        cfg = get_config("qwen3-1.7b")
        api = get_api(cfg)
        rules = shard.make_rules()
        specs = shardrules.opt_state_pspecs("adamw", api.param_defs(cfg), rules)
        assert "m" in specs and "v" in specs and "step" in specs

    def test_adafactor_factored(self):
        cfg = get_config("deepseek-v3-671b")
        api = get_api(cfg)
        rules = shard.make_rules()
        specs = shardrules.opt_state_pspecs("adafactor", api.param_defs(cfg), rules)
        leaf = specs["f"]["embed"]
        assert set(leaf) == {"vr", "vc"}


class TestHLOParser:
    HLO = """
HloModule test

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add = f32[] add(%x, %y)
}

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %iter = s32[] get-tuple-element(%p), index=0
  %h = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %w = f32[128,128]{1,0} constant({...})
  %dot.1 = f32[8,128]{1,0} dot(%h, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128]{1,0} all-reduce(%dot.1), to_apply=%add.clone
  ROOT %t = (s32[], f32[8,128]) tuple(%iter, %ar)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]) parameter(0)
  %iter = s32[] get-tuple-element(%p), index=0
  %k = s32[] constant(5)
  ROOT %lt = pred[] compare(%iter, %k), direction=LT
}

ENTRY %main (a: f32[8,128]) -> f32[8,128] {
  %a = f32[8,128]{1,0} parameter(0)
  %i0 = s32[] constant(0)
  %t0 = (s32[], f32[8,128]) tuple(%i0, %a)
  %while.1 = (s32[], f32[8,128]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,128]{1,0} get-tuple-element(%while.1), index=1
}
"""

    def test_trip_count_multiplication(self):
        t = analyze_hlo_text(self.HLO)
        assert t.flops == pytest.approx(5 * 2 * 8 * 128 * 128)
        assert t.collective_bytes["all-reduce"] == pytest.approx(5 * 8 * 128 * 4)
        assert t.collective_count["all-reduce"] == 5

    def test_shape_bytes(self):
        from repro.analysis.hlo import _shape_bytes
        assert _shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
        assert _shape_bytes("bf16[2,4]") == 16
        assert _shape_bytes("(s32[], f32[8,8])") == 4 + 256


class TestFloatNormalization:
    def test_counts_entry_f32_upcasts_only(self):
        from repro.analysis.hlo import float_normalization_bytes
        hlo = """
HloModule m

%wrapped_convert_computation.1 (p: bf16[1024,1024]) -> f32[1024,1024] {
  %p = bf16[1024,1024]{1,0} parameter(0)
  ROOT %c = f32[1024,1024]{1,0} convert(%p)
}

ENTRY %main (a: bf16[1024,1024]) -> f32[8,8] {
  %a = bf16[1024,1024]{1,0} parameter(0)
  %wrapped_convert.1 = f32[1024,1024]{1,0} fusion(%a), kind=kLoop, calls=%wrapped_convert_computation.1
  %small = f32[8,8]{1,0} convert(%a)
  ROOT %r = f32[8,8]{1,0} slice(%wrapped_convert.1), slice={[0:8],[0:8]}
}
"""
        b = float_normalization_bytes(hlo)
        assert b == 1024 * 1024 * 4  # the big upcast, not the 256 B one
