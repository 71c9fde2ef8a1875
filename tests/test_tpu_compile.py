"""Compile rehearsals for one TPU v5e chip, described and not attached.

The engine's prefill and decode programs for qwen3-1.7b and mamba2-130m at
published widths go through the TPU compiler, which refuses what the chip
cannot run, and each program's arguments, temporaries and outputs must fit
the chip's 16 GB. A compile is not a run: nothing here says anything about
results or times. Two rehearsals also hold a compiled structure: mamba2's
decode updates its f32 state stack in place, and qwen3's prefill runs its
attention as a flash kernel, with no score tensor in device memory.
"""

from __future__ import annotations

import os
import re

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


def _unfused_ops(hlo: str) -> list[str]:
    """Instructions of the computations that no fusion calls: each result
    is a buffer of its own in device memory."""
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", hlo))
    ops, keep = [], False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            keep = head.group(1) not in fused
        elif keep and " = " in line:
            ops.append(line.strip())
    return ops


def test_ssm_decode_updates_state_in_place(one_chip):
    """mamba2-130m's decode program at the benchmark cell's batch (64) and
    cache length writes the donated f32 state stack in place: no copy of
    the whole stack, temporaries far below one stack, the output state
    aliased to the input's buffer, and no fusion that stages a layer's
    updated state in a buffer of its own (each layer's update is fused
    into its in-place write)."""
    eng = _engine("mamba2-130m", one_chip)
    batch = 64
    cache = jax.tree.map(
        lambda s: _on(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: eng.api.init_cache(eng.cfg, batch, 2048)))
    token = _on(one_chip, (batch,), jnp.int32)
    key = _on(one_chip, (2,), jnp.uint32)
    compiled = eng._decode.executable(eng.params, cache, token, key)
    _fits(compiled)

    state = cache.state
    state_bytes = state.size * state.dtype.itemsize
    hlo = compiled.as_text()
    stack = "f32[" + ",".join(map(str, state.shape)) + "]"
    copies = re.findall(rf"= {re.escape(stack)}\S* copy(?:-start)?\(", hlo)
    assert not copies, copies
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.1 * state_bytes, m.temp_size_in_bytes
    assert m.alias_size_in_bytes >= state_bytes, m.alias_size_in_bytes
    layer = re.compile(r"f32\[(1,)?" + ",".join(map(str, state.shape[1:])) + r"\]")
    staged = [op for op in _unfused_ops(hlo)
              if " fusion(" in op and layer.search(op.split(" fusion(")[0])]
    assert not staged, staged


# temporaries of the qwen3 prefill at B 16, prompt 1280, cache 1792 when its
# jnp attention formed f32[16,8,2,1280,1280] scores whole
JNP_ATTENTION_TEMP_1280 = 5_831_752_192


@pytest.mark.parametrize("prompt, cache_len", [(1536, 2048), (1280, 1792)])
def test_dense_prefill_attention_is_a_flash_kernel(one_chip, prompt, cache_len):
    """qwen3-1.7b's prefill at the benchmark cell's batch (16) and prompt
    lengths that take 512 and 256 blocks: the attention is a Mosaic call
    and no f32 [B, Hkv, G, q, k] score tensor is left in the program."""
    eng = _engine("qwen3-1.7b", one_chip)
    tokens = _on(one_chip, (16, prompt), jnp.int32)
    compiled = eng._prefill.executable(eng.params, {"tokens": tokens},
                                       cache_len=cache_len, long_context=False)
    _fits(compiled)
    hlo = compiled.as_text()
    assert not re.findall(r"f32\[16,8,2,\d+,\d+\]", hlo)
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 1
    if prompt == 1280:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < JNP_ATTENTION_TEMP_1280, temp
