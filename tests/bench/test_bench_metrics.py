"""The benchmark's arithmetic on the CPU: the job, the window, the
end-to-end metrics, the per-layer readers and the cost of each step,
against hand counts at a tiny size."""

from __future__ import annotations

import importlib.util
import json
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from bench import harness, jobs
from bench.cost import dense, ssm


def _rec(out_lens, t_d, t_r, decode_s, prefill_s=0.1, s0=8):
    b = jobs.Batch(s0, tuple(range(len(out_lens))), tuple(out_lens))
    return harness.Record(b, np.zeros((len(out_lens), s0), np.int32),
                          np.zeros((len(out_lens), b.max_new), np.int32),
                          t_d, t_r, prefill_s, decode_s)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, bench_tiny.ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_window_metrics_take_every_request():
    recs = [_rec((5, 3), 0.0, 1.0, 0.8), _rec((9, 2), 1.0, 3.0, 1.5)]
    m = harness.window_metrics(recs)
    assert m["output_tokens_per_s"] == pytest.approx(19 / 3.0)
    assert m["ttft_p95_ms"] == pytest.approx(np.percentile([200, 200, 500, 500], 95))
    assert m["tpot_p95_ms"] == pytest.approx(
        np.percentile([200, 400, 187.5, 1500], 95))


class _FakeServer:
    def __init__(self, n):
        self.batches = [None] * n

    def serve(self, i):
        return _rec((4, 4), float(i), float(i + 1), 0.5)


def test_window_closes_at_the_batch_in_flight():
    recs = harness.run_window(_FakeServer(10), 2.5)
    assert len(recs) == 3          # the third batch crosses 2.5 s and finishes
    assert recs[-1].t_return - recs[0].t_dispatch == 3.0


def test_window_that_runs_dry_fails():
    with pytest.raises(RuntimeError, match="ran dry"):
        harness.run_window(_FakeServer(2), 10.0)


def test_occupancy_counts_only_requested_tokens():
    ctx = SimpleNamespace(records=[_rec((4, 1, 1, 2), 0, 1, 0.1)])
    assert _reader("slot_occupancy")(ctx) == pytest.approx(100 * 8 / 16)


def test_job_batches_hold_one_prompt_length_in_fill_order():
    traffic = json.loads((bench_tiny.DATA / "tiny-traffic.json").read_text())
    tin, tout = jobs.draw_lengths(traffic)
    assert (tin % traffic["grid"] == 0).all()
    assert tin.min() >= traffic["prompt"]["min"] and tin.max() <= traffic["prompt"]["max"]
    batches = jobs.make_batches(tin, tout, 4)
    last = [max(b.requests) for b in batches]
    assert last == sorted(last)
    for b in batches:
        assert {int(tin[i]) for i in b.requests} == {b.prompt_len}
        assert b.out_lens == tuple(int(tout[i]) for i in b.requests)


def test_cache_len_is_the_engines():
    from repro.serving.engine import InferenceEngine

    eng = InferenceEngine.__new__(InferenceEngine)
    eng.bucket = 256
    for s0, n in [(256, 1), (512, 300), (1536, 512), (3072, 32)]:
        assert jobs.cache_len(s0, n, 256) == eng._pad_len(s0 + n)


def test_same_sizes_for_every_seed():
    cell = bench_tiny.cell("dense")
    a = jobs.job(cell.traffic, cell.params)
    assert a == jobs.job(cell.traffic, cell.params)
    p1 = jobs.prompts(a, 512, 1)
    p2 = jobs.prompts(a, 512, 2**31 + 5)
    assert [p.shape for p in p1] == [p.shape for p in p2]
    assert not all((x == y).all() for x, y in zip(p1, p2))


DENSE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 256, "vocab_size": 512, "qk_norm": True,
         "tie_embeddings": False, "param_dtype": "bfloat16"}
SSM = {"n_layers": 2, "d_model": 64, "vocab_size": 300, "ssm_state": 16,
       "ssm_headdim": 16, "ssm_expand": 2, "ssm_ngroups": 1, "conv_kernel": 4,
       "param_dtype": "bfloat16"}


def test_dense_cost_hand_counts():
    # per layer 64*8*16 + 4*16*64 + 3*64*256 = 61440 matmul parameters
    assert dense.decode_step(DENSE, 2, 10) == (
        2 * 2 * (2 * 61440 + 64 * 512) + 2 * 2 * 4 * 4 * 16 * 11,
        2 * (2 * 61440 + 384 + 512 * 64 + 2 * 64) + 2 * 256 * 11 + 2 * 512 * 4)
    assert dense.prefill(DENSE, 2, 8) == (
        2 * 2 * 8 * 2 * 61440 + 2 * 2 * 64 * 512 + 2 * 2 * 4 * 4 * 16 * 36,
        2 * (2 * 61440 + 384 + 512 * 64 + 16 * 64) + 16 * 256 + 2 * 512 * 4)


def test_tied_head_is_read_once():
    tied = {**DENSE, "tie_embeddings": True}
    f, b = dense.decode_step(tied, 2, 10)
    assert b == dense.decode_step(DENSE, 2, 10)[1] - 2 * 2 * 64


def test_ssm_cost_hand_counts():
    # matmul 64*(256+32+8) + 128*64 = 27136; small 1016; per token 11520
    assert ssm.decode_step(SSM, 2, 999) == (
        2 * 2 * (2 * 27136 + 64 * 300) + 2 * 2 * 11520,
        2 * (2 * (27136 + 1016) + 64 + 300 * 64 + 2 * 64)
        + 2 * 2 * (16384 + 1920) + 2 * 300 * 4)
    f, b = ssm.prefill(SSM, 2, 8)
    assert f == 2 * 2 * 8 * 2 * 27136 + 2 * 2 * 64 * 300 + 2 * 2 * 8 * 11520
    assert b == (2 * (2 * (27136 + 1016) + 64 + 300 * 64 + 16 * 64)
                 + 2 * (16384 + 1920) + 2 * 300 * 4)


def test_roofline_and_mfu_readers():
    peak = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
    recs = [_rec((3, 2), 0.0, 1.0, decode_s=0.5, prefill_s=0.25, s0=8)]
    ctx = SimpleNamespace(records=recs, m=DENSE, cost=dense, peak=peak,
                          trace=None, programs={})
    assert _reader("decode_roofline")(ctx) is None       # no trace, no reading
    flops = sum(dense.decode_step(DENSE, 2, 8 + t)[0] for t in range(3))
    assert _reader("mfu.decode")(ctx) == pytest.approx(100 * flops / (0.5 * 1e12))
    f, _ = dense.prefill(DENSE, 2, 8)
    assert _reader("mfu.prefill")(ctx) == pytest.approx(100 * f / (0.25 * 1e12))
