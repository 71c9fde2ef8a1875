"""Record the small TPU trace of a served engine that `test_bench_spans.py`
reads.

    python3 tests/bench/record_engine_trace.py <out.xplane.pb>

Serves the tiny dense engine of `data/tiny-dense.json` (KV cache on,
greedy) under the profiler: `BATCHES` calls of `generate` for `B` prompts
of `PROMPT_LEN` tokens and `MAX_NEW` new tokens, each inside a host span
`bench.batch` as the harness serves them, after one warm call outside the
trace. Copies the `.xplane.pb` to the given path. Prints each plane's lines
with their event counts, the bounds on how far the device's clock runs
ahead of the host's, and how far the prefill and decode programs' module
events lie outside the engine spans that launched them, before and after
that offset is taken out. Needs a TPU.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.trace import module_name  # noqa: E402

B, PROMPT_LEN, MAX_NEW, BATCHES = 4, 16, 4, 2
# each program's module name (jit names the prefill program, a partial,
# `_unknown`), the host span that launches it, and the engine span that
# should hold it
PROGRAMS = {"jit__unknown": ("PjitFunction(jit(<unknown>))", "engine.prefill"),
            "jit__decode": ("PjitFunction(jit(_decode))", "engine.step")}


def _runs(tr, prog):
    """The program's module events (first chip) and the host launches that
    issued them, in order; a launch's host event holds one of the same name."""
    launch, _ = PROGRAMS[prog]
    outer = []
    for e in (e for e in tr.host if e.name == launch):
        if not (outer and outer[-1].start <= e.start and e.end <= outer[-1].end):
            outer.append(e)
    mods = [m for m in tr.modules[0] if module_name(m.name) == prog]
    assert len(mods) == len(outer), (prog, len(mods), len(outer))
    return mods, outer


def clock_offset_ns(tr) -> tuple[float, float]:
    """Bounds (lo, hi) on how far the device's events run ahead of the host
    clock: a program starts after its launch began (lo) and ends before the
    `engine.wait` that blocks on it returns (hi). Each batch waits once in
    prefill, then once a decode step."""
    waits = [e for e in tr.host if e.name == "engine.wait"]
    (pre, pl), (dec, dl) = _runs(tr, "jit__unknown"), _runs(tr, "jit__decode")
    per = len(dec) // len(pre)
    lo = max(h.start - m.start for m, h in zip(pre + dec, pl + dl))
    hi = min([waits[b * (per + 1)].end - m.end for b, m in enumerate(pre)]
             + [waits[i // per * (per + 1) + 1 + i % per].end - m.end
                for i, m in enumerate(dec)])
    return lo, hi


def skew_ns(tr, offset: float = 0.0) -> dict[str, float]:
    """For each program, the most nanoseconds by which one of its module
    events, moved `offset` later, lies outside every engine span that
    should hold it; 0 when each lies inside one."""
    out = {}
    for prog, (_, holder) in PROGRAMS.items():
        spans = [e for e in tr.host if e.name == holder]
        out[prog] = max(min(max(0.0, s.start - m.start - offset,
                                m.end + offset - s.end) for s in spans)
                        for m in _runs(tr, prog)[0])
    return out


def main(out: str) -> None:
    import jax
    import numpy as np

    from bench import harness, trace
    from repro.models import get_api
    from repro.serving.engine import InferenceEngine

    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    model = json.loads((HERE / "data" / "tiny-dense.json").read_text())
    cfg = harness.model_config(model, "tiny-dense")
    params = get_api(cfg).init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, bucket=16)
    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab_size, (BATCHES, B, PROMPT_LEN)).astype(np.int32)
    eng.generate({"tokens": prompts[0]}, MAX_NEW)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    with jax.profiler.trace(tmp, profiler_options=opts):
        for p in prompts:
            with jax.profiler.TraceAnnotation("bench.batch"):
                eng.generate({"tokens": p}, MAX_NEW)
    path = next(Path(tmp).rglob("*.xplane.pb"))
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    pd = jax.profiler.ProfileData.from_file(out)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs),
                  sorted({e.name for e in evs})[:12])
    print("modules", sorted({trace.module_name(m.name)
                             for m in trace.load(out).modules[0]}))
    tr = trace.load(out)
    lo, hi = clock_offset_ns(tr)
    print("clock_offset_ns", lo, hi)
    print("skew_ns raw", skew_ns(tr), "after the offset", skew_ns(tr, lo))
    print("bytes", Path(out).stat().st_size)


if __name__ == "__main__":
    main(sys.argv[1])
