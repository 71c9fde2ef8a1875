"""Smoke run of the served path on one TPU chip, at published widths.

    python3 chip_smoke.py

Runs in this one process (no subprocess, no fork), from the committed files
alone: weights come from PRNGKey(seed), prompts from the seeded generators.

1. Serves qwen3-1.7b and mamba2-130m through `repro.launch.serve.serve`:
   the paper's campaign with the KV cache off, the Eq. 6/7 fits, the
   energy-aware router over a seeded Alpaca-like workload, and every batch
   through `InferenceEngine` with the KV cache on.
2. For each model, runs the engine's own prefill and decode programs at the
   served batch and checks the logits of prefill followed by cached decode
   steps against one full forward pass, within the model's limit in
   `LIMITS`, and checks that a cache one token behind, and one whose decode
   position is one ahead, both fail that limit.

Prints what it measured, then as its last line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}`.
Exits non-zero, printing no result, when JAX finds no TPU or a phase fails.
Joules are modelled: measured time times a host power model.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODELS = ["qwen3-1.7b", "mamba2-130m"]
SEED = 0
N_QUERIES = 24
BATCH = 4            # the served batch, in serving and in the logit check
MAX_TOKENS = 16      # campaign token counts 8..16: 24 prefill lengths per model
CHECK_S0, CHECK_STEPS = 24, 8

# Relative-L2 limits on the cached-decode logits, per model at published
# widths. bf16 keeps 8 significant bits (unit roundoff 2^-8 ~ 3.9e-3), and
# prefill and decode run different matmul shapes and reduction orders, so
# each layer rounds its activations differently and the drift grows with
# depth. How far depends on the batch: on one v5e qwen3's decode matches
# the full pass bit for bit at most positions at batch 2 and reads about
# 0.035 at every position at batch 4, so the check runs at the served
# batch. Each limit sits a few times above the model's reading there and
# ten times or more below its controls' readings (PERF.md).
LIMITS = {"qwen3-1.7b": 0.1, "mamba2-130m": 0.15}


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def serve_phase() -> None:
    from repro.launch.serve import serve

    t0 = time.perf_counter()
    out = serve(MODELS, n_queries=N_QUERIES, zeta=0.5, batch_size=BATCH,
                max_tokens=MAX_TOKENS)
    print(f"serve phase: {time.perf_counter() - t0}s wall")
    totals = out["totals"]
    served = sum(t["queries"] for t in totals.values())
    if served != N_QUERIES:
        fail(f"served {served} of {N_QUERIES} queries")
    for arch, t in totals.items():
        print(f"{arch}: " + json.dumps(t))
        if not (math.isfinite(t["energy_r2"]) and math.isfinite(t["runtime_r2"])):
            fail(f"{arch}: Eq. 6/7 fit has no finite R2")
        if t["queries"] and not t["decode_tokens_per_s"] > 0:
            fail(f"{arch}: served queries but decoded no tokens")
    print("serve readings are smoke readings of a few batches of toy traffic: "
          "they show the path runs, not what it sustains")


def check_phase() -> None:
    from repro.launch.serve import build_engine
    from repro.serving.decode_check import decode_logit_errors

    for arch in MODELS:
        t0 = time.perf_counter()
        eng = build_engine(arch, kv_cache=True, seed=SEED)
        jax.block_until_ready(eng.params)
        print(f"{arch}: weight init {time.perf_counter() - t0}s "
              f"(its compiles included)")
        tokens = np.random.default_rng(SEED).integers(
            1, eng.cfg.vocab_size, (BATCH, CHECK_S0 + CHECK_STEPS)).astype(np.int32)
        r = decode_logit_errors(eng, tokens, CHECK_S0)
        del eng
        limit = LIMITS[arch]
        print(f"{arch}: cached decode vs full forward, relative L2 logit error "
              f"{r['error']} (limit {limit}); controls: one token behind "
              f"{r['missing_token']}, decode position one ahead "
              f"{r['position_shift']} (None: the decode reads no position)")
        if not r["error"] <= limit:
            fail(f"{arch}: cached decode differs from the full forward by "
                 f"{r['error']}")
        for control in ("missing_token", "position_shift"):
            if r[control] is not None and not r[control] > limit:
                fail(f"{arch}: the {control} control ({r[control]}) passes "
                     f"the limit {limit}")


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    n_cached = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    print(f"device_kind={dev.device_kind} count={jax.device_count()} "
          f"jax={jax.__version__}")
    print(f"compile cache {cache}: {n_cached} entries at start")

    check_phase()
    serve_phase()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
