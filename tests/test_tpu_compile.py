"""Compile rehearsals for one TPU v5e chip, described and not attached.

The engine's prefill and decode programs for qwen3-1.7b and mamba2-130m at
published widths go through the TPU compiler, which refuses what the chip
cannot run, and each program's arguments, temporaries and outputs must fit
the chip's 16 GB. A compile is not a run: nothing here says anything about
results or times.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import get_api
from repro.serving import InferenceEngine

HBM_BYTES = 16 * 10**9        # one v5e chip
BATCH, PROMPT, CACHE_LEN = 8, 512, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _engine(arch: str, sharding) -> InferenceEngine:
    cfg = get_config(arch)
    params = jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype),
                          get_api(cfg).param_shapes(cfg))
    return InferenceEngine(cfg, params)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return total


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-130m"])
def test_prefill_compiles_for_one_chip(one_chip, arch):
    eng = _engine(arch, one_chip)
    tokens = _on(one_chip, (BATCH, PROMPT), jnp.int32)
    compiled = eng._prefill.executable(eng.params, {"tokens": tokens},
                                       cache_len=CACHE_LEN, long_context=False)
    _fits(compiled)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mamba2-130m"])
def test_decode_compiles_for_one_chip(one_chip, arch):
    eng = _engine(arch, one_chip)
    cache = jax.tree.map(
        lambda s: _on(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: eng.api.init_cache(eng.cfg, BATCH, CACHE_LEN)))
    token = _on(one_chip, (BATCH,), jnp.int32)
    key = _on(one_chip, (2,), jnp.uint32)
    compiled = eng._decode.executable(eng.params, cache, token, key)
    _fits(compiled)
