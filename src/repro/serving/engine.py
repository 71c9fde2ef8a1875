"""Batched inference engine: prefill + decode with explicit KV-cache control.

Two modes, both first-class because the paper *measures* with KV caching
disabled (§3, §5.1) while production serving uses it:

  * kv_cache=True  — prefill once, then one jitted decode_step per token
    (cache donated, so the update is in-place on device).
  * kv_cache=False — the paper's measurement mode: every generated token
    re-runs the full forward pass over the exact growing sequence
    (runtime superlinear in τout — the source of the τin·τout interaction
    term in Eq. 6/7).  Greedy decoding in this mode is bit-identical to
    the cached mode (verified by test_greedy_modes_agree).

An optional meter (repro.energy.meter.EnergyMeter) wraps each phase and
returns joules; GenStats feeds the characterization campaign directly.
Every program is compiled before the meter starts, once per argument
signature, and the engine counts those compiles, with their tracing and
XLA seconds kept apart, outside the metered run time.

For operators: every `generate` call writes host spans into the JAX
profiler's trace, always; with no trace being recorded a span costs about
half a microsecond of host time (on a TPU v5e host). `engine.generate` is
one call (one batch), with its `B`, `prompt_len`, `max_new`, `cache_len`
and `call` (the engine's count of calls, which every span of the batch
shares by nesting). Inside it, `engine.prefill` is everything before the
first decode step and `engine.decode` the decode loop, made of one
`engine.step` per decoded position; in a step, `engine.fetch` is the
device-to-host copy of the token and `engine.wait` the meter's block on
the device. `engine.compile` is a new signature lowered and compiled,
with args `program` and `attn_kernels`: the Mosaic kernel calls in the
compiled program (1 for a dense prefill lowered for TPU, where attention
runs as a flash kernel), counted only while a trace is recorded. Wrapping
a serve in `jax.profiler.trace(dir)` records these spans beside the
chip's op and program events, for TensorBoard or Perfetto; the device's
events may run a millisecond or so ahead of the host's clock there.
"""

from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.energy.meter import WAIT, timed
from repro.models import get_api
from repro.models.common import ModelConfig
from repro.serving.sampler import Sampler

# host span names (WAIT, "engine.wait", is opened by the meters' `timed`)
GENERATE = "engine.generate"
PREFILL = "engine.prefill"
DECODE = "engine.decode"
STEP = "engine.step"
FETCH = "engine.fetch"
COMPILE = "engine.compile"
SPANS = (GENERATE, PREFILL, DECODE, STEP, FETCH, WAIT, COMPILE)


@dataclasses.dataclass
class GenStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_energy_j: float = 0.0
    decode_energy_j: float = 0.0
    tau_in: int = 0
    tau_out: int = 0

    @property
    def runtime_s(self) -> float:
        return self.prefill_s + self.decode_s

    @property
    def energy_j(self) -> float:
        return self.prefill_energy_j + self.decode_energy_j


class _CompiledFn:
    """A jitted function compiled ahead of time once per argument signature
    (pytree structure, avals and static arguments), so that compilation is
    counted and timed apart from the run it precedes. `lower_s` is tracing
    and lowering, which the persistent cache never skips; `seconds` is the
    XLA compile, which it can. `name` is the program's, for the
    `engine.compile` span."""

    def __init__(self, fn: Callable, name: str, **jit_kwargs):
        self._jit = jax.jit(fn, **jit_kwargs)
        self.name = name
        self._executables: dict = {}
        self.count = 0
        self.lower_s = 0.0
        self.seconds = 0.0

    def executable(self, *args, **static):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple(jax.typeof(x) for x in leaves),
               tuple(sorted(static.items())))
        exe = self._executables.get(key)
        if exe is None:
            with jax.profiler.TraceAnnotation(COMPILE,
                                              program=self.name) as span:
                t0 = time.perf_counter()
                lowered = self._jit.lower(*args, **static)
                t1 = time.perf_counter()
                exe = lowered.compile()
                self.lower_s += t1 - t0
                self.seconds += time.perf_counter() - t1
                if span.is_enabled():
                    span.set_metadata(attn_kernels=exe.as_text().count(
                        'custom_call_target="tpu_custom_call"'))
            self.count += 1
            self._executables[key] = exe
        return exe


class _NullMeter:
    """Measures wall time only; energy reported as 0."""

    def measure(self, fn):
        out, dt = timed(fn)
        return out, dt, 0.0


class InferenceEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        *,
        kv_cache: bool = True,
        sampler: Sampler = Sampler(),
        bucket: int = 32,
        long_context: bool = False,
        meter: Any = None,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.params = params
        self.api = get_api(cfg)
        self.kv_cache = kv_cache
        self.sampler = sampler
        self.bucket = bucket
        self.long_context = long_context
        self.meter = meter or _NullMeter()
        self.key = jax.random.PRNGKey(seed)
        self.calls = 0

        self._prefill = _CompiledFn(
            partial(self.api.prefill, cfg), "prefill",
            static_argnames=("cache_len", "long_context"))

        # closes over locals, not self: a cycle through self would keep the
        # weights on the device after the engine is dropped, until a gc pass
        api = self.api

        # returns the logits beside the sampled token, so that a check can
        # hold the served program's logits against a full forward pass
        def _decode(params, cache, token, key):
            logits, cache = api.decode_step(cfg, params, cache,
                                            {"token": token})
            nxt = sampler(logits, key)
            return logits, nxt, cache

        self._decode = _CompiledFn(_decode, "decode", donate_argnums=(1,))

    @property
    def compile_count(self) -> int:
        return self._prefill.count + self._decode.count

    @property
    def compile_s(self) -> float:
        """XLA compile seconds, without tracing and lowering."""
        return self._prefill.seconds + self._decode.seconds

    @property
    def lower_s(self) -> float:
        """Tracing and lowering seconds of the compiled programs."""
        return self._prefill.lower_s + self._decode.lower_s

    # ------------------------------------------------------------------
    def _pad_len(self, n: int) -> int:
        return max(self.bucket, int(math.ceil(n / self.bucket)) * self.bucket)

    def _extra_inputs(self, batch: dict) -> dict:
        return {k: v for k, v in batch.items()
                if k in ("patches", "frames")}

    # ------------------------------------------------------------------
    def generate(self, batch: dict, max_new_tokens: int) -> tuple[np.ndarray, GenStats]:
        """batch: {"tokens": [B, S0] int32, (+"patches"/"frames")}.
        Returns (generated [B, max_new_tokens] int32, stats)."""
        B, S0 = np.shape(batch["tokens"])
        if self.kv_cache:
            span = S0 + max_new_tokens + (
                self.cfg.n_patches if self.cfg.family == "vlm" else 0)
            cache_len = self._pad_len(span)
        else:
            cache_len = S0 + max_new_tokens - 1      # the longest re-forward
        self.calls += 1
        with jax.profiler.TraceAnnotation(
                GENERATE, B=B, prompt_len=S0, max_new=max_new_tokens,
                cache_len=cache_len, call=self.calls):
            if self.kv_cache:
                return self._generate_cached(batch, max_new_tokens, cache_len)
            return self._generate_uncached(batch, max_new_tokens)

    def _generate_cached(self, batch, max_new, cache_len):
        with jax.profiler.TraceAnnotation(PREFILL):
            tokens = jnp.asarray(batch["tokens"], jnp.int32)
            B, S0 = tokens.shape
            inputs = {"tokens": tokens, **self._extra_inputs(batch)}
            prefill = self._prefill.executable(
                self.params, inputs, cache_len=cache_len,
                long_context=self.long_context)
            (logits, cache), t_prefill, e_prefill = self.meter.measure(
                lambda: prefill(self.params, inputs))

            stats = GenStats(prefill_s=t_prefill, prefill_energy_j=e_prefill,
                             tau_in=S0, tau_out=max_new)
            out = np.zeros((B, max_new), np.int32)
            self.key, k0 = jax.random.split(self.key)
            token = self.sampler(logits, k0)
            decode = self._decode.executable(self.params, cache, token, k0)

        with jax.profiler.TraceAnnotation(DECODE):
            t0 = time.perf_counter()
            e_total = 0.0
            for t in range(max_new):
                with jax.profiler.TraceAnnotation(STEP):
                    with jax.profiler.TraceAnnotation(FETCH):
                        out[:, t] = np.asarray(token)
                    self.key, kt = jax.random.split(self.key)
                    (_, token, cache), dt, de = self.meter.measure(
                        lambda: decode(self.params, cache, token, kt))
                    e_total += de
            stats.decode_s = time.perf_counter() - t0
        stats.decode_energy_j = e_total
        return out, stats

    def _generate_uncached(self, batch, max_new):
        def step(t):
            """One full re-forward over the exact prefix — the paper's mode —
            and its sampled token -> (seconds, joules) of the forward."""
            L = S0 + t
            window = np.asarray(buf[:, :L], np.int32)
            inputs = {"tokens": jnp.asarray(window), **extra}
            prefill = self._prefill.executable(
                self.params, inputs, cache_len=L,
                long_context=self.long_context)
            (logits, _cache), dt, de = self.meter.measure(
                lambda: prefill(self.params, inputs))
            self.key, kt = jax.random.split(self.key)
            token = self.sampler(logits, kt)
            with jax.profiler.TraceAnnotation(FETCH):
                token = np.asarray(token)
            out[:, t] = token
            buf[:, L] = token
            return dt, de

        # the first full-prefix pass is "prefill", the rest decode
        with jax.profiler.TraceAnnotation(PREFILL):
            tokens = np.asarray(batch["tokens"], np.int32)
            B, S0 = tokens.shape
            extra = self._extra_inputs(batch)
            buf = np.zeros((B, S0 + max_new), np.int32)
            buf[:, :S0] = tokens
            out = np.zeros((B, max_new), np.int32)
            stats = GenStats(tau_in=S0, tau_out=max_new)
            t_start = time.perf_counter()
            e_total = 0.0
            if max_new:
                stats.prefill_s, e_total = step(0)
        with jax.profiler.TraceAnnotation(DECODE):
            for t in range(1, max_new):
                with jax.profiler.TraceAnnotation(STEP):
                    e_total += step(t)[1]
            stats.decode_s = time.perf_counter() - t_start - stats.prefill_s
        stats.decode_energy_j = e_total
        return out, stats


def measure_fn(engine_factory: Callable[[], InferenceEngine], batch_size: int,
               vocab_size: int, *, seed: int = 0):
    """Adapter: (tau_in, tau_out) -> (energy_j, runtime_s), the callback the
    characterization campaign (repro.core.characterize) consumes.  Runs a
    real generation of the requested shape on the engine."""
    engine = engine_factory()
    rng = np.random.default_rng(seed)

    def measure(tau_in: int, tau_out: int) -> tuple[float, float]:
        toks = rng.integers(1, vocab_size, size=(batch_size, tau_in), dtype=np.int64)
        batch = {"tokens": toks.astype(np.int32)}
        if engine.cfg.family == "vlm":
            from repro.models.vlm import VISION_DIM
            batch["patches"] = np.zeros((batch_size, engine.cfg.n_patches, VISION_DIM), np.float32)
        if engine.cfg.family == "encdec":
            batch["frames"] = np.zeros((batch_size, engine.cfg.n_frames, engine.cfg.d_model), np.float32)
        _, stats = engine.generate(batch, tau_out)
        return stats.energy_j, stats.runtime_s

    return measure
