"""The trace reduction: on synthetic events, and on a small `.xplane.pb`
in the TPU profile's layout kept in `data/` (written by
`make_probe_trace.py`): two `bench.batch` host spans, each around one
`prefill` and three `_decode` programs."""

from __future__ import annotations

import pytest

import bench_tiny
from bench import trace as tr

RECORDED = bench_tiny.DATA / "v5e_probe.xplane.pb"
E = tr.Event
# module names of the probe's programs, as the engine's are read from their
# compiled HLO
PROGRAMS = {"prefill": {"jit_prefill"}, "decode": {"jit__decode"}}


def _trace():
    ops = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40), E("a", 100, 150)]
    mods = [E("jit__decode(1)", 0, 20), E("jit_prefill(2)", 30, 40),
            E("jit__decode(1)", 100, 150)]
    host = [E("bench.batch", 0, 200), E("PjitFunction(_decode)", 60, 90)]
    return tr.Trace([ops], [mods], host)


def test_union_merges_and_clips():
    t = _trace()
    assert tr.union(t.ops[0], 0, 200) == [(0, 20), (30, 40), (100, 150)]
    assert tr.union(t.ops[0], 8, 120) == [(8, 20), (30, 40), (100, 120)]


def test_busy_and_idle_share():
    t = _trace()
    assert tr.busy_s(t, 0, 200) == pytest.approx(80e-9)
    assert tr.idle_share(t, 0, 200) == pytest.approx(0.6)


def test_module_name_drops_the_run_id():
    assert tr.module_name("jit__decode(12)") == "jit__decode"
    assert tr.module_name("jit__unknown") == "jit__unknown"
    assert tr.module_name("jit_f(x)") == "jit_f(x)"


def test_device_time_by_program():
    t = _trace()
    assert tr.program_s(t, PROGRAMS["decode"], 0, 200) == pytest.approx(70e-9)
    assert tr.program_s(t, PROGRAMS["prefill"], 0, 200) == pytest.approx(10e-9)


def test_breakdown():
    t = _trace()
    assert tr.top_ops(t, 0, 200)[0] == ["a", pytest.approx(60e-9)]
    gaps = dict(tr.idle_gaps(t, 0, 200, min_gap_ns=15))
    # 20-30 is short; 40-100 sits in the host's decode call; 150-200 in the batch
    assert gaps == {"PjitFunction(_decode)": pytest.approx(60e-9),
                    "bench.batch": pytest.approx(50e-9),
                    "between ops": pytest.approx(10e-9)}


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.fail(f"missing {RECORDED}")
    return tr.load(str(RECORDED))


def test_recorded_trace_has_one_chip_and_the_spans(recorded):
    assert len(recorded.ops) == 1 and recorded.ops[0]
    assert sum(e.name == "bench.batch" for e in recorded.host) == 2


def test_recorded_busy_within_the_window(recorded):
    t0, t1 = tr.span_window(recorded, "bench.batch")
    busy = tr.busy_s(recorded, t0, t1)
    assert 0 < busy < (t1 - t0) / 1e9
    assert 0 < tr.idle_share(recorded, t0, t1) < 1


def test_recorded_programs(recorded):
    t0, t1 = tr.span_window(recorded, "bench.batch")
    decode = tr.program_s(recorded, PROGRAMS["decode"], t0, t1)
    prefill = tr.program_s(recorded, PROGRAMS["prefill"], t0, t1)
    assert decode > 0 and prefill > 0
    # one program runs at a time, so their module events fit in the window
    assert decode + prefill <= (t1 - t0) / 1e9


def test_recorded_breakdown(recorded):
    t0, t1 = tr.span_window(recorded, "bench.batch")
    ops = tr.top_ops(recorded, t0, t1)
    gaps = tr.idle_gaps(recorded, t0, t1)
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) == pytest.approx(
        (t1 - t0) / 1e9 - tr.busy_s(recorded, t0, t1), rel=1e-6)


def test_recorded_numbers_by_hand(recorded):
    # per batch: prefill ops 25 + 34 us, three decode modules of 40 us of ops
    t0, t1 = tr.span_window(recorded, "bench.batch")
    assert (t1 - t0) / 1e3 == pytest.approx(12506)
    assert tr.busy_s(recorded, t0, t1) == pytest.approx(2 * (59 + 120) * 1e-6)
    assert tr.program_s(recorded, PROGRAMS["prefill"], t0, t1) == pytest.approx(120e-6)
    assert tr.program_s(recorded, PROGRAMS["decode"], t0, t1) == pytest.approx(240e-6)


# The same probe recorded on one TPU v5e by `record_trace.py`: the line and
# module names that the reduction reads, as the chip writes them.
CHIP = bench_tiny.DATA / "v5e_recorded.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    if not CHIP.exists():
        pytest.fail(f"missing {CHIP}")
    return tr.load(str(CHIP))


def test_chip_trace_has_one_chip_and_the_spans(chip):
    assert len(chip.ops) == 1 and chip.ops[0] and chip.modules[0]
    assert sum(e.name == "bench.batch" for e in chip.host) == 2


def test_chip_trace_programs_by_module_name(chip):
    t0, t1 = tr.span_window(chip, "bench.batch")
    decode = tr.program_s(chip, PROGRAMS["decode"], t0, t1)
    prefill = tr.program_s(chip, PROGRAMS["prefill"], t0, t1)
    assert decode > 0 and prefill > 0
    assert decode + prefill <= (t1 - t0) / 1e9


def test_chip_trace_breakdown_accounts_for_the_window(chip):
    t0, t1 = tr.span_window(chip, "bench.batch")
    busy = tr.busy_s(chip, t0, t1)
    assert 0 < busy < (t1 - t0) / 1e9
    gaps = tr.idle_gaps(chip, t0, t1)
    assert 0 < len(tr.top_ops(chip, t0, t1)) <= 10 and 0 < len(gaps) <= 10
    assert sum(s for _, s in gaps) == pytest.approx((t1 - t0) / 1e9 - busy,
                                                   rel=1e-6)
