"""One run of one cell: set-up, the measured window or the traced batches,
the comparison with the reference, and the result line.

Everything that belongs to a cell is data found by name: the cell's entry
in `BENCHMARK.json`, its configuration (`bench/configs/<config>.json`), its
traffic (`bench/traffic/<traffic>.json`), its serving parameters
(`bench/cells/<cell>.json`), and one reader per per-layer metric
(`bench/metrics/<metric>.py`).

The timed path is the program's own: weights from `api.init_params` in one
jitted call, `InferenceEngine(cfg, params, kv_cache=True, bucket=...)` with
greedy sampling, and `engine.generate(batch, max_new)` for one same-length
batch after another.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np

from bench import check, jobs, trace as tracelib
from bench.reference.weights import seed_key

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

class CompileEvents:
    """Counts JAX's tracing and compile events while open."""

    def __enter__(self) -> "CompileEvents":
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, name: str, *_a, **_k) -> None:
        if name.startswith("/jax/core/compile/"):
            self.n += 1

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class Record:
    """One served batch."""
    batch: jobs.Batch
    prompts: np.ndarray        # [B, S0]
    out: np.ndarray            # [B, max_new] served tokens
    t_dispatch: float
    t_return: float
    prefill_s: float
    decode_s: float

    @property
    def ttft_s(self) -> float:
        return self.t_return - self.t_dispatch - self.decode_s


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    bench = root / "bench"
    return SimpleNamespace(
        name=name, entry=entry, spec=spec,
        model=json.loads((bench / "configs" / f"{entry['config']}.json").read_text()),
        traffic=json.loads((bench / "traffic" / f"{entry['traffic']}.json").read_text()),
        params=json.loads((bench / "cells" / f"{name}.json").read_text()))


def model_config(model: dict, name: str):
    from repro.models.common import ModelConfig

    return ModelConfig(name=name, **model["model_config"])


class Server:
    """The system under test, built from the seed and warmed up."""

    def __init__(self, cell: SimpleNamespace, seed: int):
        from repro.models import get_api
        from repro.serving.engine import InferenceEngine
        from repro.serving.sampler import Sampler

        self.cfg = model_config(cell.model, cell.entry["config"])
        api = get_api(self.cfg)
        init = jax.jit(lambda key: api.init_params(self.cfg, key))
        self.params = jax.block_until_ready(init(seed_key(seed)))
        self.engine = InferenceEngine(self.cfg, self.params, kv_cache=True,
                                      sampler=Sampler(),
                                      bucket=cell.params["bucket"])
        self.batches = jobs.job(cell.traffic, cell.params)
        self.prompts = jobs.prompts(self.batches, self.cfg.vocab_size, seed)

    def warm_up(self) -> None:
        """Compile and run once every (prompt length, cache length) program
        the job uses, and the host-side ops of one `generate`."""
        eng, p = self.engine, self.params
        B = len(self.batches[0].requests)
        key = jax.random.PRNGKey(0)
        for s0, cache_len in jobs.shapes(self.batches, eng.bucket):
            inputs = {"tokens": jax.numpy.zeros((B, s0), jax.numpy.int32)}
            prefill = eng._prefill.executable(p, inputs, cache_len=cache_len,
                                              long_context=eng.long_context)
            logits, cache = prefill(p, inputs)
            tok = eng.sampler(logits, key)
            decode = eng._decode.executable(p, cache, tok, key)
            jax.block_until_ready(decode(p, cache, tok, key))
        eng.generate({"tokens": self.prompts[0]}, 1)

    def serve(self, i: int) -> Record:
        b = self.batches[i]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            batch = {"tokens": self.prompts[i]}
            t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch"):
            out, stats = self.engine.generate(batch, b.max_new)
        t1 = time.perf_counter()
        return Record(b, self.prompts[i], out, t0, t1, stats.prefill_s,
                      stats.decode_s)

    def programs(self) -> dict[str, set[str]]:
        """HLO module names of the engine's compiled prefill and decode
        programs, as the device trace names their runs."""
        return {phase: {m.name for exe in fn._executables.values()
                        for m in exe.runtime_executable().hlo_modules()}
                for phase, fn in (("prefill", self.engine._prefill),
                                  ("decode", self.engine._decode))}

    def free(self) -> None:
        del self.engine, self.params
        gc.collect()


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------


def window_metrics(records: list[Record]) -> dict[str, float]:
    """Over all requests of the window's batches; the window runs from the
    first dispatch to the last return."""
    window = records[-1].t_return - records[0].t_dispatch
    tokens = sum(sum(r.batch.out_lens) for r in records)
    ttft = [r.ttft_s * 1e3 for r in records for _ in r.batch.out_lens]
    tpot = [r.decode_s / (n - 1) * 1e3 for r in records
            for n in r.batch.out_lens]
    return {"output_tokens_per_s": tokens / window,
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "tpot_p95_ms": float(np.percentile(tpot, 95))}


def run_window(server: Server, seconds: float) -> list[Record]:
    """Batches back to back until `seconds` have passed since the first
    dispatch; the batch in flight finishes."""
    records = []
    for i in range(len(server.batches)):
        records.append(server.serve(i))
        if records[-1].t_return - records[0].t_dispatch >= seconds:
            return records
    raise RuntimeError(f"the job's {len(server.batches)} batches ran dry "
                       f"before {seconds} s")


# ---------------------------------------------------------------------------
# Per-layer metrics
# ---------------------------------------------------------------------------


def read_metric(name: str, ctx: SimpleNamespace) -> float | None:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def traced(server: Server, n: int) -> tuple[list[Record], tracelib.Trace]:
    """Serve the job's first n batches under the profiler."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            records = [server.serve(i) for i in range(n)]
        path = next(Path(tmp).rglob("*.xplane.pb"))
        tr = tracelib.load(str(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return records, tr


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------


def served(cell: SimpleNamespace, records: list[Record],
           seed: int) -> tuple[list[check.Item], list[check.Item]]:
    """Every finished request with its own served tokens, and the sample of
    them that the reference compares."""
    items = [check.Item(r.prompts[j], r.out[j, :n])
             for r in records for j, n in enumerate(r.batch.out_lens)]
    c = cell.params["check"]
    return items, check.sample(items, seed, c["tokens"], c["max_requests"])


def check_outputs(cell: SimpleNamespace, records: list[Record], seed: int,
                  vocab: int, *, control: bool = False) -> dict[str, dict]:
    """Each number compared, beside its limit. With `control`, the numbers
    of the float8 control put in the program's place."""
    items, sample = served(cell, records, seed)
    limit = cell.params["check"]["limits"]["max_logit_gap"]
    if any(((it.served < 0) | (it.served >= vocab)).any() for it in items):
        return {"max_logit_gap": {"value": 1e9, "limit": limit}}
    ref = check.Reference(cell.model, seed)
    gap = max(ref.max_gaps(sample, control=control))
    return {"max_logit_gap": {"value": gap, "limit": limit}}


def is_correct(numbers: dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def device_info(chips: int) -> dict:
    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def run(cell: SimpleNamespace, seed: int, seconds: float, trace: bool,
        t_start: float, chips: int = 1, peaks: dict | None = None) -> dict:
    server = Server(cell, seed)
    server.warm_up()
    n_compiles = server.engine.compile_count
    kind = jax.devices()[0].device_kind
    if trace:
        peaks = peaks or json.loads((BENCH / "peaks.json").read_text())
        if kind not in peaks:
            raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    with CompileEvents() as jit_events:
        if trace:
            records, tr = traced(server, cell.params["trace_batches"])
        else:
            records = run_window(server, seconds)
            setup_s = records[0].t_dispatch - t_start
    print(f"engine compile_count {n_compiles} before the measured batches, "
          f"{server.engine.compile_count} after; JAX compile events among "
          f"them {jit_events.n}", flush=True)
    if server.engine.compile_count != n_compiles or jit_events.n:
        raise SystemExit("a program compiled inside the measured window")
    device = device_info(chips)
    vocab = server.cfg.vocab_size
    programs = server.programs()
    server.free()

    if trace:
        t0, t1 = tracelib.span_window(tr, "bench.batch")
        ctx = SimpleNamespace(
            records=records, m=cell.model["model_config"], peak=peaks[kind],
            cost=importlib.import_module(f"bench.cost.{cell.model['cost']}"),
            trace=tr, t0=t0, t1=t1, programs=programs)
        values = {m["name"]: read_metric(m["name"], ctx)
                  for m in cell.spec["per_layer"]
                  if cell.name in m.get("workloads", [cell.name])}
        silent = sorted(k for k, v in values.items() if v is None)
        if tr.ops and silent:
            # a TPU trace was read, yet a metric listed for this cell found
            # nothing in it: a renamed line or program, not an absent layer
            raise SystemExit(f"per-layer metrics read nothing from the TPU "
                             f"trace: {', '.join(silent)}")
        units = {m["name"]: m["unit"] for m in cell.spec["per_layer"]}
        device.update(busy_s=tracelib.busy_s(tr, t0, t1), window_s=(t1 - t0) / 1e9)
        breakdown = {"device_ops": tracelib.top_ops(tr, t0, t1),
                     "idle_gaps": tracelib.idle_gaps(tr, t0, t1)}
    else:
        values = {**window_metrics(records), "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in cell.spec["end_to_end"]}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()
               if v is not None}

    numbers = check_outputs(cell, records, seed, vocab)
    correct = is_correct(numbers)
    n = sum(len(r.batch.requests) for r in records)
    result = {"correct": correct, "attempted": n, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["check"] = numbers
    for k, v in numbers.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    return result
