"""The engine's host spans, as the JAX profiler records them on the CPU:
their names, nesting and counts per generate call in both KV modes, the
compile span, the meters' shared wait, and the decode span against the
metered decode seconds."""

from __future__ import annotations

import dataclasses
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from helpers import reduced
from repro.energy.hardware import TPU_NODE
from repro.energy.meter import ModeledMeter, WallClockMeter
from repro.serving import engine as E

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import trace as tracelib  # noqa: E402

MAX_NEW = 5
# engine.decode holds the metered decode seconds plus the opening and
# closing of its own span and the loop's first and last microseconds
DECODE_SLACK_S = 1e-3


@dataclasses.dataclass
class Call:
    """One generate call: its engine spans (the generate span first), the
    args of each span, and its stats."""
    spans: list[tracelib.Event]
    args: list[dict]
    stats: E.GenStats

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def inside(self, name, outer):
        return [s for s in self.named(name)
                if outer.start <= s.start and s.end <= outer.end]


def _args(path):
    """(name, start) -> args of each engine span in the trace."""
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out[e.name, float(e.start_ns)] = dict(e.stats)
    return out


@pytest.fixture(scope="module")
def calls():
    """Every call under one profiler session: the dense model cached (cold,
    warm, then warm with the two other meters), the SSM cached (cold, warm)
    and the dense model uncached (cold)."""
    dense, dense_api = reduced("qwen3-1.7b")
    ssm, ssm_api = reduced("mamba2-130m")
    dp = dense_api.init_params(dense, jax.random.PRNGKey(0))
    sp = ssm_api.init_params(ssm, jax.random.PRNGKey(0))
    cached = E.InferenceEngine(dense, dp, bucket=8)
    ssm_eng = E.InferenceEngine(ssm, sp, bucket=8)
    uncached = E.InferenceEngine(dense, dp, kv_cache=False, bucket=8)
    toks = np.random.default_rng(0).integers(1, 200, (2, 8)).astype(np.int32)
    meters = [None, None, WallClockMeter(),
              ModeledMeter(TPU_NODE, lambda: (1e9, 1e9))]
    plan = [(cached, m) for m in meters] + [(ssm_eng, None)] * 2 + [(uncached, None)]

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    stats = []
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            for eng, meter in plan:
                if meter is not None:
                    eng.meter = meter
                stats.append(eng.generate({"tokens": toks}, MAX_NEW)[1])
        path = str(next(Path(tmp).rglob("*.xplane.pb")))
        tr, args = tracelib.load(path), _args(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    spans = [e for e in tr.host if e.name in E.SPANS]
    gens = [e for e in spans if e.name == E.GENERATE]
    assert len(gens) == len(plan)
    out = {}
    names = ["cached-cold", "cached-warm", "cached-wallclock", "cached-modeled",
             "ssm-cold", "ssm-warm", "uncached-cold"]
    for key, g, st in zip(names, gens, stats):
        mine = [e for e in spans if g.start <= e.start and e.end <= g.end]
        out[key] = Call(mine, [args[e.name, e.start] for e in mine], st)
    return out


ALL = ["cached-cold", "cached-warm", "cached-wallclock", "cached-modeled",
       "ssm-cold", "ssm-warm", "uncached-cold"]
CACHED = [k for k in ALL if not k.startswith("uncached")]


@pytest.mark.parametrize("key", ALL)
def test_spans_nest_as_documented(calls, key):
    c = calls[key]
    gen = c.spans[0]
    assert gen.name == E.GENERATE and len(c.named(E.GENERATE)) == 1
    (pre,), (dec,) = c.named(E.PREFILL), c.named(E.DECODE)
    assert pre.end <= dec.start
    # prefill and decode cover the call but for its bookkeeping
    uncovered = (gen.end - gen.start) - (pre.end - pre.start) - (dec.end - dec.start)
    assert 0 <= uncovered <= 0.05 * (gen.end - gen.start)
    for s in c.named(E.STEP):
        assert dec.start <= s.start and s.end <= dec.end
    holders = c.named(E.STEP) + [pre]
    for name in (E.FETCH, E.WAIT):
        for s in c.named(name):
            assert sum(h.start <= s.start and s.end <= h.end for h in holders) == 1
    for s in c.named(E.COMPILE):
        assert pre.start <= s.start and s.end <= pre.end or \
            any(h.start <= s.start and s.end <= h.end for h in c.named(E.STEP))


@pytest.mark.parametrize("key", CACHED)
def test_cached_call_has_a_step_per_position_with_one_fetch_and_one_wait(calls, key):
    c = calls[key]
    steps = c.named(E.STEP)
    assert len(steps) == MAX_NEW
    for s in steps:
        assert len(c.inside(E.FETCH, s)) == 1 and len(c.inside(E.WAIT, s)) == 1
    (pre,) = c.named(E.PREFILL)
    assert len(c.inside(E.WAIT, pre)) == 1 and not c.inside(E.FETCH, pre)


def test_uncached_call_has_the_same_names(calls):
    c = calls["uncached-cold"]
    assert {s.name for s in c.spans} == set(E.SPANS)
    # the first full re-forward is the prefill, each later one a step
    steps = c.named(E.STEP)
    assert len(steps) == MAX_NEW - 1
    for s in steps + c.named(E.PREFILL):
        assert len(c.inside(E.FETCH, s)) == 1 and len(c.inside(E.WAIT, s)) == 1


@pytest.mark.parametrize("key, programs", [
    ("cached-cold", ["prefill", "decode"]), ("ssm-cold", ["prefill", "decode"]),
    ("cached-warm", []), ("ssm-warm", []), ("cached-wallclock", []),
    ("uncached-cold", ["prefill"] * MAX_NEW)])
def test_compile_span_once_per_new_signature(calls, key, programs):
    c = calls[key]
    got = [a["program"] for s, a in zip(c.spans, c.args) if s.name == E.COMPILE]
    assert got == programs


@pytest.mark.parametrize("key", ["cached-cold", "ssm-cold", "uncached-cold"])
def test_compile_span_counts_no_attention_kernel_on_cpu(calls, key):
    # the flash attention kernel is lowered only for TPU
    c = calls[key]
    got = [a["attn_kernels"] for s, a in zip(c.spans, c.args)
           if s.name == E.COMPILE]
    assert got and got == [0] * len(got)


@pytest.mark.parametrize("key", ALL)
def test_generate_span_carries_the_batch(calls, key):
    c = calls[key]
    a = c.args[0]
    assert (a["B"], a["prompt_len"], a["max_new"]) == (2, 8, MAX_NEW)
    assert a["cache_len"] == (8 + MAX_NEW - 1 if key.startswith("uncached") else 16)
    # each engine counts its own calls: the dense cached engine served four
    assert a["call"] == 1 + {"cold": 0, "warm": 1, "wallclock": 2,
                             "modeled": 3}[key.split("-")[1]]
    assert all(not a for s, a in zip(c.spans, c.args)
               if s.name in (E.PREFILL, E.DECODE, E.STEP, E.FETCH, E.WAIT))


@pytest.mark.parametrize("key", CACHED)
def test_decode_span_holds_the_metered_decode_seconds(calls, key):
    c = calls[key]
    (dec,) = c.named(E.DECODE)
    span_s = (dec.end - dec.start) / 1e9
    assert 0 <= span_s - c.stats.decode_s <= DECODE_SLACK_S


def test_uncached_spans_hold_the_metered_seconds(calls):
    # GenStats counts only the first re-forward's metered run as prefill and
    # the rest of the call, that step's sampling and compile included, as
    # decode; the prefill and decode spans together hold both
    c = calls["uncached-cold"]
    (pre,), (dec,) = c.named(E.PREFILL), c.named(E.DECODE)
    span_s = (dec.end - pre.start) / 1e9
    assert 0 <= span_s - c.stats.runtime_s <= DECODE_SLACK_S
