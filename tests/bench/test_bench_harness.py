"""The harness end to end on the CPU at a tiny size: a sound run is
correct, the float8 control and each fault the timed path can have are
not, and the entry point refuses to run without a TPU."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import check, harness
from bench.reference import weights


def _run(family, trace=False):
    return harness.run(bench_tiny.cell(family), bench_tiny.SEED, 0.05, trace,
                       time.perf_counter(), 1, peaks=bench_tiny.PEAKS)


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_sound_run_is_correct(family):
    r = _run(family)
    assert r["correct"], r["check"]
    assert list(r)[-1] == "check"
    assert set(r["metrics"]) == {"output_tokens_per_s", "ttft_p95_ms",
                                 "tpot_p95_ms", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0


def test_traced_run_reports_per_layer_metrics():
    r = _run("dense", trace=True)
    assert r["correct"]
    assert 0 < r["metrics"]["slot_occupancy"]["value"] <= 100
    assert {"busy_s", "window_s"} <= set(r["device"])
    # the CPU trace has no TPU plane: the trace readers stay silent
    assert "decode_roofline" not in r["metrics"]
    assert "device_idle_share" not in r["metrics"]


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_programs_are_named_as_compiled(family):
    server = harness.Server(bench_tiny.cell(family), bench_tiny.SEED)
    server.warm_up()
    names = server.programs()
    assert names["prefill"] and names["decode"]
    assert not names["prefill"] & names["decode"]
    assert names["decode"] == {"jit__decode"}


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_reference_draws_the_served_weights(family):
    cell = bench_tiny.cell(family)
    server = harness.Server(cell, bench_tiny.SEED)
    ref = check.Reference(cell.model, bench_tiny.SEED)
    flat = {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(server.params)[0]}
    assert set(flat) == set(ref.w)
    for path, leaf in flat.items():
        assert leaf.dtype == ref.w[path].dtype, path
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref.w[path]))


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_fp8_control_fails_the_limit(family):
    cell = bench_tiny.cell(family)
    server = harness.Server(cell, bench_tiny.SEED)
    server.warm_up()
    records = harness.run_window(server, 0.05)
    vocab = server.cfg.vocab_size
    server.free()
    numbers = harness.check_outputs(cell, records, bench_tiny.SEED, vocab,
                                    control=True)
    assert not harness.is_correct(numbers), numbers


def _altered_token(monkeypatch):
    """Every served token is the best one's neighbour."""
    from repro.serving.sampler import Sampler

    def call(self, logits, key):
        return ((jnp.argmax(logits, -1) + 1) % 256).astype(jnp.int32)
    monkeypatch.setattr(Sampler, "__call__", call)


def _state_unchanged(monkeypatch):
    """Each decode step returns the cache it was given (position aside)."""
    from repro.serving import engine as engine_mod

    real = engine_mod.get_api

    def get_api(cfg):
        api = real(cfg)

        def decode_step(cfg, params, cache, batch):
            logits, new = api.decode_step(cfg, params, cache, batch)
            return logits, dataclasses.replace(cache, pos=new.pos)
        return dataclasses.replace(api, decode_step=decode_step)
    monkeypatch.setattr(engine_mod, "get_api", get_api)


@pytest.mark.parametrize("family", ["dense", "ssm"])
@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_fault_in_timed_path_is_not_correct(monkeypatch, family, fault):
    fault(monkeypatch)
    r = _run(family)
    assert not r["correct"], r["check"]


def _entry(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.decode-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_a_cpu():
    p = _entry(bench_tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_entry_point_fails_without_the_program(tmp_path):
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.ROOT / "bench", tmp_path / "bench")
    p = _entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_result_line_is_json():
    r = _run("ssm")
    assert json.loads(json.dumps(r)) == r


def test_compile_inside_the_window_fails_the_run(monkeypatch):
    monkeypatch.setattr(harness.Server, "warm_up", lambda self: None)
    with pytest.raises(SystemExit, match="compiled inside"):
        _run("dense")
