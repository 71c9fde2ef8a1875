"""Serving driver: the paper's system end-to-end.

    PYTHONPATH=src python -m repro.launch.serve --fleet llama2-7b,llama2-13b \
        --queries 64 --zeta 0.5

1. Characterize each hosted model by REAL execution on the local device
   (wall-clock metering, KV cache disabled — the paper's measurement mode).
2. Fit the per-model e_K / r_K workload models (Eq. 6/7).
3. Route an Alpaca-like workload with the offline scheduler at the given
   zeta and serve every batch through the real engines (KV cache ON — the
   production path), reporting measured runtime per model. Joules are
   modelled: measured time times the host power model of WallClockMeter.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import TABLE1, get_config
from repro.core.characterize import (
    CampaignSettings,
    fit_profile_from_trials,
    run_campaign,
)
from repro.core.energy_model import LLMProfile
from repro.data import alpaca_like_workload, token_batches
from repro.data.workloads import WorkloadSpec
from repro.energy.meter import WallClockMeter
from repro.launch.compile_cache import use_compile_cache
from repro.models import get_api
from repro.serving import EnergyAwareRouter, InferenceEngine
from repro.serving.requests import Request


def build_engine(arch: str, *, kv_cache: bool, seed: int = 0) -> InferenceEngine:
    cfg = get_config(arch)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    return InferenceEngine(cfg, params, kv_cache=kv_cache,
                           meter=WallClockMeter(), bucket=16)


def _compiles(eng: InferenceEngine) -> dict:
    return {"compiles": eng.compile_count, "lower_s": eng.lower_s,
            "compile_s": eng.compile_s}


def characterize(arch: str, *, batch: int = 2,
                 max_tokens: int = 64) -> tuple[LLMProfile, dict]:
    """The paper's campaign by real execution on a fresh engine with the KV
    cache off -> (fitted profile, the engine's compiles). Token counts run
    over powers of two from 8 to max_tokens; each new sequence length
    compiles one program in the uncached mode."""
    engine = build_engine(arch, kv_cache=False)
    fixed = min(32, max_tokens)
    settings = CampaignSettings(
        vary_input_range=(8, max_tokens), vary_input_fixed_out=fixed,
        vary_output_range=(8, max_tokens), vary_output_fixed_in=fixed,
        grid_range=(8, max_tokens), max_trials=3, min_trials=2,
        ci_tolerance_s=0.5)
    base = arch.replace("-reduced", "")
    a_k = TABLE1.get(base, {"a_k": get_config(base).accuracy_ak})["a_k"]
    rng = np.random.default_rng(0)
    warmed: set = set()

    def measure(tin, tout):
        toks = rng.integers(1, engine.cfg.vocab_size,
                            (batch, tin)).astype(np.int32)
        # the uncached mode's decode seconds are the wall time of its whole
        # token loop, inside which each new window length compiles its
        # program and the sampler compiles on first use: one untimed run
        # per shape keeps those compiles out of the fitted runtime
        if (tin, tout) not in warmed:
            warmed.add((tin, tout))
            engine.generate({"tokens": toks}, tout)
        _, stats = engine.generate({"tokens": toks}, tout)
        return stats.energy_j, stats.runtime_s

    trials = run_campaign(arch, measure, settings)
    prof = fit_profile_from_trials(arch, a_k, trials)
    print(f"{arch}: energy R2={prof.energy.r_squared:.3f} "
          f"runtime R2={prof.runtime.r_squared:.3f}")
    return prof, _compiles(engine)


def serve(archs: list[str], *, n_queries: int, zeta: float,
          batch_size: int = 4, max_tokens: int = 64) -> dict:
    """Characterize, fit, route and serve. Returns the routing plan and,
    per model, what serving it took: queries and batches, tokens, modelled
    joules, prefill and decode seconds, and the compiles of both engines
    (count, tracing seconds, XLA seconds), kept apart from the run seconds."""
    totals: dict = {}
    profiles = []
    for arch in archs:
        prof, compiles = characterize(arch, max_tokens=max_tokens)
        profiles.append(prof)
        totals[arch] = {"energy_r2": prof.energy.r_squared,
                        "runtime_r2": prof.runtime.r_squared,
                        "characterize": compiles}
    router = EnergyAwareRouter(profiles, zeta=zeta)

    spec = WorkloadSpec(n_queries=n_queries, max_in=48, max_out=32,
                        in_log_mean=2.8, out_log_mean=2.5)
    queries = alpaca_like_workload(spec)
    reqs = [Request(i, np.zeros(q[0], np.int32), q[1])
            for i, q in enumerate(queries)]
    plan = router.route(reqs)

    for arch in archs:
        rs = plan.per_model[arch]
        t = totals[arch]
        t.update(queries=len(rs), batches=0, energy_j=0.0, runtime_s=0.0,
                 tokens=0, prefill_s=0.0, decode_s=0.0, decode_tokens=0,
                 decode_tokens_per_s=0.0)
        if not rs:
            continue
        eng = build_engine(arch, kv_cache=True)
        qs = [(r.tau_in, r.max_new_tokens) for r in rs]
        for b in token_batches(qs, batch_size, eng.cfg.vocab_size):
            max_new = int(b["tau_out"].max())
            _, stats = eng.generate({"tokens": b["tokens"]}, max_new)
            t["batches"] += 1
            t["energy_j"] += stats.energy_j
            t["prefill_s"] += stats.prefill_s
            t["decode_s"] += stats.decode_s
            t["tokens"] += int(b["lengths"].sum()) + max_new * batch_size
            t["decode_tokens"] += max_new * batch_size
        t["runtime_s"] = t["prefill_s"] + t["decode_s"]
        t["decode_tokens_per_s"] = t["decode_tokens"] / t["decode_s"]
        t["serve"] = _compiles(eng)
        del eng   # free its weights before the next model's are made
        print(f"{arch}: {len(rs)} queries in {t['batches']} batches | "
              f"compiles: characterize {t['characterize']}, serve "
              f"{t['serve']} | prefill {t['prefill_s']}s, decode "
              f"{t['decode_s']}s, {t['decode_tokens_per_s']} decode tokens/s"
              f" | {t['energy_j']} J modelled (host power model x measured "
              f"time)")
    return {"plan": plan, "totals": totals}


def main(argv=None) -> int:
    use_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--fleet", default="llama2-7b-reduced,llama2-70b-reduced")
    p.add_argument("--queries", type=int, default=24)
    p.add_argument("--zeta", type=float, default=0.5)
    args = p.parse_args(argv)
    out = serve(args.fleet.split(","), n_queries=args.queries, zeta=args.zeta)
    total_e = sum(t["energy_j"] for t in out["totals"].values())
    print(f"TOTAL modelled energy: {total_e:.1f} J "
          f"(objective={out['plan'].assignment.objective:.3f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
