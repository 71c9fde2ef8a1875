"""The served programs' cached decoding checked against one full forward
pass, as logits.

`decode_logit_errors` runs an engine's own compiled prefill and decode
programs, the executables `InferenceEngine.generate` serves with, at the
cache length `generate` pads to. It prefills `tokens[:, :s0]` and feeds the
remaining k tokens one at a time through the decode program (teacher-forced),
so it yields logits at positions s0-1 … s0+k-1. One forward pass over all
s0+k tokens gives the reference logits at the same positions. Each reading is
the largest relative L2 distance between matching logit vectors.

Two negative controls run the same programs with the cache wrong by one
position. A limit that does not reject both cannot catch a cache bug:

- missing token: the prefill stops one token short, so the cache (KV or
  recurrent state) is one token behind the tokens decoded after it;
- position shift: the same cache with its decode position one ahead, so
  every decoded token is written and position-encoded one slot late. A model
  whose decode reads no position (a state space model) gives the same
  logits, and this reading is None.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import InferenceEngine


def _rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative L2 distance over the leading axes."""
    num = np.linalg.norm(got - ref, axis=-1)
    den = np.linalg.norm(ref, axis=-1)
    return float(np.max(num / den))


def decode_logit_errors(engine: InferenceEngine, tokens: np.ndarray,
                        s0: int) -> dict[str, float | None]:
    """Returns {"error", "missing_token", "position_shift"} against the full
    pass; tokens [B, S] with 1 < s0 < S, for a family whose API gives
    `full_logits`."""
    cfg, params = engine.cfg, engine.params
    S = tokens.shape[1]
    V = cfg.vocab_size
    full = jax.jit(partial(engine.api.full_logits, cfg))
    ref = np.asarray(full(params, tokens))[..., :V]
    cache_len = engine._pad_len(S)      # as generate() pads s0 + k
    key = jax.random.PRNGKey(0)

    def cached(n_prefill: int, shift: int = 0) -> np.ndarray:
        inputs = {"tokens": jnp.asarray(tokens[:, :n_prefill])}
        prefill = engine._prefill.executable(params, inputs, cache_len=cache_len,
                                             long_context=engine.long_context)
        logits, cache = prefill(params, inputs)
        cache = dataclasses.replace(cache, pos=cache.pos + shift)
        out = [logits]
        for t in range(s0, S):
            tok = jnp.asarray(tokens[:, t])
            decode = engine._decode.executable(params, cache, tok, key)
            logits, _, cache = decode(params, cache, tok, key)
            out.append(logits)
        return np.stack([np.asarray(o) for o in out], axis=1)[..., :V]

    got = cached(s0)
    shifted = cached(s0, shift=1)
    return {
        "error": _rel_err(got, ref[:, s0 - 1:]),
        "missing_token": _rel_err(cached(s0 - 1)[:, 1:], ref[:, s0:]),
        "position_shift": (None if np.array_equal(shifted, got)
                           else _rel_err(shifted, ref[:, s0 - 1:])),
    }
