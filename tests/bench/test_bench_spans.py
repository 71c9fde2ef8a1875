"""The engine host loop's readers (`host_gap_share.decode`,
`host_gap_share.prefill`, `syncs_per_token.decode`): on a synthetic trace
worked out by hand, on one from a program without the engine's spans, and
on a tiny served engine recorded on one TPU v5e (`record_engine_trace.py`),
where the engine's spans and the chip's program events share one clock up to
a constant offset of the device's."""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace

import pytest

import bench_tiny
import record_engine_trace as rec
from bench import trace as tr
from bench.metrics import _spans

E = tr.Event
READERS = ("host_gap_share.decode", "host_gap_share.prefill",
           "syncs_per_token.decode")
CHIP = bench_tiny.DATA / "v5e_engine.xplane.pb"
SKEW_NS = 50e3      # the most skew allowed once the device's offset is out


def _read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, bench_tiny.ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _records(max_new, decode_s=0.0, n=1):
    return [SimpleNamespace(batch=SimpleNamespace(max_new=max_new),
                            decode_s=decode_s) for _ in range(n)]


def _ctx(trace, records, t0=0.0, t1=1000.0):
    return SimpleNamespace(trace=trace, records=records, t0=t0, t1=t1)


# one batch of two decode steps; ops busy 50-250 in prefill, 420-600 and
# 720-900 in decode
HOST = [E("bench.batch", 0, 1000), E("engine.generate", 10, 990),
        E("engine.prefill", 10, 300), E("engine.wait", 100, 290),
        E("engine.decode", 310, 980),
        E("engine.step", 310, 640), E("engine.fetch", 310, 330),
        E("engine.wait", 400, 600),
        E("engine.step", 640, 970), E("engine.fetch", 640, 660),
        E("engine.wait", 700, 960),
        E("engine.fetch", 1005, 1010)]              # outside the window
OPS = [E("a", 50, 150), E("b", 140, 250), E("c", 420, 600), E("d", 720, 900)]


def _trace(host=HOST, chips=1):
    return tr.Trace([list(OPS)] * chips, [[]] * chips, sorted(host, key=lambda e: e.start))


@pytest.mark.parametrize("chips", [1, 2])
def test_readers_by_hand(chips):
    ctx = _ctx(_trace(chips=chips), _records(2))
    # prefill 10-300: busy 50-250 of 290; decode 310-980: busy 360 of 670
    assert _read("host_gap_share.prefill", ctx) == pytest.approx(100 * 90 / 290)
    assert _read("host_gap_share.decode", ctx) == pytest.approx(100 * 310 / 670)
    # two fetches and two waits in decode; the prefill's wait is not counted
    assert _read("syncs_per_token.decode", ctx) == 2.0


def test_shares_are_silent_without_a_tpu_op_line():
    ctx = _ctx(tr.Trace([], [], HOST), _records(2))
    assert _read("host_gap_share.decode", ctx) is None
    assert _read("host_gap_share.prefill", ctx) is None
    assert _read("syncs_per_token.decode", ctx) == 2.0


def test_a_program_without_engine_spans_is_placed_by_the_host_clock():
    # decode is the batch's last 400 ns: 600-1000 (busy 720-900); prefill
    # 0-600 (busy 50-250 and 420-600)
    ctx = _ctx(_trace(host=[E("bench.batch", 0, 1000)]), _records(2, 400e-9))
    assert _read("host_gap_share.decode", ctx) == pytest.approx(100 * 220 / 400)
    assert _read("host_gap_share.prefill", ctx) == pytest.approx(100 * 220 / 600)
    assert _read("syncs_per_token.decode", ctx) == 0.0


def test_span_names_are_the_engines():
    from repro.serving import engine

    assert (_spans.GENERATE, _spans.PREFILL, _spans.DECODE) == (
        engine.GENERATE, engine.PREFILL, engine.DECODE)
    assert _spans.SYNCS == (engine.FETCH, engine.WAIT)
    assert {h for _, h in rec.PROGRAMS.values()} == {engine.PREFILL, engine.STEP}


@pytest.fixture(scope="module")
def chip():
    if not CHIP.exists():
        pytest.fail(f"missing {CHIP}")
    return tr.load(str(CHIP))


def _chip_ctx(chip):
    t0, t1 = tr.span_window(chip, "bench.batch")
    return _ctx(chip, _records(rec.MAX_NEW, n=rec.BATCHES), t0, t1)


def test_chip_trace_holds_the_engine_spans(chip):
    names = [e.name for e in chip.host]
    assert names.count("bench.batch") == rec.BATCHES
    for name, n in [("engine.generate", 1), ("engine.prefill", 1),
                    ("engine.decode", 1), ("engine.step", rec.MAX_NEW),
                    ("engine.fetch", rec.MAX_NEW),
                    ("engine.wait", rec.MAX_NEW + 1), ("engine.compile", 0)]:
        assert names.count(name) == n * rec.BATCHES, name


def test_chip_clock_offset_is_one_constant(chip):
    # one shift of the device's clock explains every program's launch and
    # wait; on this recording the device runs 0.93-1.64 ms ahead of the host
    lo, hi = rec.clock_offset_ns(chip)
    assert 0 < lo <= hi
    assert (lo, hi) == (928461.0, 1638082.0)


def test_chip_programs_run_inside_their_engine_spans(chip):
    counts = {p: sum(tr.module_name(m.name) == p for m in chip.modules[0])
              for p in rec.PROGRAMS}
    assert counts == {"jit__unknown": rec.BATCHES,
                      "jit__decode": rec.BATCHES * rec.MAX_NEW}
    # with the device clock's offset taken out, each prefill program lies in
    # an engine.prefill span and each decode program in an engine.step span
    lo, hi = rec.clock_offset_ns(chip)
    for offset in (lo, hi):
        skew = rec.skew_ns(chip, offset)
        assert max(skew.values()) <= SKEW_NS, skew
    # as recorded, a prefill program starts 208 us before its span opens
    assert rec.skew_ns(chip) == {"jit__unknown": 208449.0, "jit__decode": 0.0}


def test_chip_readers(chip):
    # a tiny engine: the chip idles in 99% of each phase
    ctx = _chip_ctx(chip)
    assert _read("syncs_per_token.decode", ctx) == 2.0
    assert _read("host_gap_share.decode", ctx) == pytest.approx(99.36597264540758)
    assert _read("host_gap_share.prefill", ctx) == pytest.approx(99.78360972478735)


def test_chip_idle_gaps_carry_engine_labels(chip):
    ctx = _chip_ctx(chip)
    gaps = dict(tr.idle_gaps(chip, ctx.t0, ctx.t1, n=100))
    assert "bench.batch" not in gaps
    assert gaps["engine.wait"] > 0
