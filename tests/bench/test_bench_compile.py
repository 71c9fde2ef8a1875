"""Compile rehearsals of every benchmark cell for one TPU v5e chip,
described and not attached.

For each cell, the weight draw (one jitted program) and the engine's
prefill and decode programs at the cell's largest prompt and cache lengths
go through the TPU compiler, which refuses what the chip cannot run, and
each program's arguments, temporaries and outputs must stay under the
chip's 16 GB. A compile is not a run: nothing here says anything about
results or times.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, jobs  # noqa: E402

HBM_BYTES = 16 * 10**9
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


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


def _on(sharding, s):
    return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    return total


def _cell(name: str) -> tuple[SimpleNamespace, object, object]:
    from repro.models import get_api

    cell = harness.load_cell(name, ROOT)
    cfg = harness.model_config(cell.model, cell.entry["config"])
    return cell, cfg, get_api(cfg)


@pytest.mark.parametrize("name", CELLS)
def test_cell_programs_fit_one_chip(one_chip, name):
    from repro.serving.engine import InferenceEngine

    cell, cfg, api = _cell(name)
    key = _on(one_chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    init = jax.jit(lambda k: api.init_params(cfg, k)).lower(key).compile()
    _bytes(init)

    params = jax.tree.map(lambda s: _on(one_chip, s), api.param_shapes(cfg))
    eng = InferenceEngine(cfg, params, bucket=cell.params["bucket"])
    shapes = jobs.shapes(jobs.job(cell.traffic, cell.params), eng.bucket)
    B = cell.params["batch"]
    # the two longest prompts: one is an odd multiple of 256, whose
    # attention scores are formed whole rather than in chunks of 512
    longest = {}
    for s0, cache_len in shapes:
        longest[s0] = max(cache_len, longest.get(s0, 0))
    for s0 in sorted(longest)[-2:]:
        tokens = _on(one_chip, jax.ShapeDtypeStruct((B, s0), jnp.int32))
        _bytes(eng._prefill.executable(params, {"tokens": tokens},
                                       cache_len=longest[s0],
                                       long_context=eng.long_context))
    cache_len = max(c for _, c in shapes)
    cache = jax.tree.map(lambda s: _on(one_chip, s), jax.eval_shape(
        lambda: api.init_cache(cfg, B, cache_len)))
    token = _on(one_chip, jax.ShapeDtypeStruct((B,), jnp.int32))
    _bytes(eng._decode.executable(params, cache, token, key))
