"""Decoder-only dense transformer (Llama/Qwen/Falcon/Mistral family).

Covers the assigned dense archs (qwen2.5-14b, deepseek-67b, llama3.2-3b,
qwen3-1.7b) and the paper's own zoo (Falcon 7/40B, Llama-2 7/13/70B,
Mistral 7B).  GQA with optional QKV bias (Qwen2.5), qk-norm (Qwen3) and
sliding-window attention (long-context decode mode for dense archs).

Layers are stacked and scanned; decode threads the KV cache through the
layer scan as carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import shard
from repro.models import attention as attn
from repro.models import cache as cachelib
from repro.models.common import (
    ModelConfig,
    padded_vocab,
    ParamDef,
    cross_entropy,
    embed_tokens,
    lm_logits,
    maybe_remat,
    mlp_defs,
    rmsnorm,
    rope,
    swiglu,
)


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig, n_layers: int) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    L = (n_layers,)
    A = ("layers",)
    defs = {
        "wq": ParamDef(L + (d, hq, hd), A + ("embed_w", "heads", None)),
        "wk": ParamDef(L + (d, hkv, hd), A + ("embed_w", "kv_heads", None)),
        "wv": ParamDef(L + (d, hkv, hd), A + ("embed_w", "kv_heads", None)),
        "wo": ParamDef(L + (hq, hd, d), A + ("heads", None, "embed_w"),
                       scale=0.02 / max(1, (2 * cfg.n_layers) ** 0.5)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef(L + (hq, hd), A + ("heads", None), init="zeros")
        defs["bk"] = ParamDef(L + (hkv, hd), A + ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef(L + (hkv, hd), A + ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef(L + (hd,), A + (None,), init="zeros")
        defs["k_norm"] = ParamDef(L + (hd,), A + (None,), init="zeros")
    return defs


def layer_defs(cfg: ModelConfig) -> dict:
    L = (cfg.n_layers,)
    A = ("layers",)
    return {
        "attn": attn_defs(cfg, cfg.n_layers),
        "mlp": mlp_defs(cfg.d_model, cfg.d_ff, cfg.n_layers),
        "ln_attn": {"w": ParamDef(L + (cfg.d_model,), A + (None,), init="zeros")},
        "ln_mlp": {"w": ParamDef(L + (cfg.d_model,), A + (None,), init="zeros")},
    }


def param_defs(cfg: ModelConfig) -> dict:
    defs = {
        "embed": ParamDef((padded_vocab(cfg.vocab_size), cfg.d_model), ("vocab", "embed_w")),
        "blocks": layer_defs(cfg),
        "final_norm": {"w": ParamDef((cfg.d_model,), (None,), init="zeros")},
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, padded_vocab(cfg.vocab_size)),
                                ("embed_w", "vocab"))
    return defs


def head_matrix(cfg: ModelConfig, params: dict) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["head"]


# ---------------------------------------------------------------------------
# Attention sublayer
# ---------------------------------------------------------------------------


def _project_qkv(cfg: ModelConfig, pl: dict, x: jax.Array, *,
                 head_major: bool = False):
    """x [..., d] -> q [..., Hq, Dh], k/v [..., Hkv, Dh] (roped by caller).

    head_major (x [B, S, d]): the same values, projected in [B, H, S, Dh]
    order and viewed [B, S, H, Dh]; XLA then lays them out as the flash
    kernel of `attention.full_attention` reads them, instead of copying
    them into that layout."""
    if head_major:
        q, k, v = (jnp.einsum("bsd,dhe->bhse", x, pl[w]).swapaxes(1, 2)
                   for w in ("wq", "wk", "wv"))
    else:
        q, k, v = (jnp.einsum("...d,dhe->...he", x, pl[w])
                   for w in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, k, v = q + pl["bq"], k + pl["bk"], v + pl["bv"]
    if cfg.qk_norm:
        q = rmsnorm(q, pl["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, pl["k_norm"], cfg.rmsnorm_eps)
    return q, k, v


def attention_full(cfg: ModelConfig, pl: dict, x: jax.Array, *,
                   q_offset: int = 0, window: int = 0, causal: bool = True):
    """Full-sequence attention sublayer.  Returns (y, k, v) — roped k and raw
    v for the cache."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, pl, x, head_major=True)
    positions = q_offset + jnp.arange(S)
    q = rope(q, jnp.broadcast_to(positions, (B, S)), cfg.rope_theta)
    k = rope(k, jnp.broadcast_to(positions, (B, S)), cfg.rope_theta)
    o = attn.full_attention(q, k, v, causal=causal, window=window,
                            q_offset=q_offset, softcap=cfg.attn_logit_softcap)
    y = jnp.einsum("...he,hed->...d", o, pl["wo"])
    return y, k, v


def attention_decode(cfg: ModelConfig, pl: dict, x: jax.Array,
                     k_cache_l: jax.Array, v_cache_l: jax.Array,
                     pos: jax.Array, *, ring: bool):
    """One-token attention.  x [B, d]; k_cache_l [B, S, Hkv, Dh] — already
    containing this token's K/V (written by the caller).  Returns y [B, d]."""
    q, _, _ = _project_qkv(cfg, pl, x)
    q = rope(q[:, None], jnp.full((x.shape[0], 1), pos), cfg.rope_theta)[:, 0]
    o = attn.decode_attention(q, k_cache_l, v_cache_l, pos, ring=ring,
                              softcap=cfg.attn_logit_softcap)
    return jnp.einsum("bhe,hed->bd", o, pl["wo"])


def project_kv_token(cfg: ModelConfig, pl: dict, x: jax.Array, pos: jax.Array):
    """K/V for one token [B, d] -> roped k, v [B, Hkv, Dh]."""
    _, k, v = _project_qkv(cfg, pl, x)
    k = rope(k[:, None], jnp.full((x.shape[0], 1), pos), cfg.rope_theta)[:, 0]
    return k, v


# ---------------------------------------------------------------------------
# Transformer stack
# ---------------------------------------------------------------------------


def forward_full(cfg: ModelConfig, blocks: dict, x: jax.Array, *,
                 q_offset: int = 0, window: int = 0, collect_kv: bool = False):
    """Run the scanned layer stack over embeddings x [B, S, d].
    Returns (hidden, (ks, vs) | None); ks [L, B, S, Hkv, Dh]."""

    def body(h, pl):
        h = shard.constrain(h, "batch", "seq", None)
        a, k, v = attention_full(cfg, pl["attn"], rmsnorm(h, pl["ln_attn"]["w"], cfg.rmsnorm_eps),
                                 q_offset=q_offset, window=window)
        h = h + a
        m = swiglu(rmsnorm(h, pl["ln_mlp"]["w"], cfg.rmsnorm_eps),
                   pl["mlp"]["w_gate"], pl["mlp"]["w_up"], pl["mlp"]["w_down"])
        h = h + m
        out = (k, v) if collect_kv else None
        return h, out

    body = maybe_remat(body, cfg.remat)
    h, kv = jax.lax.scan(body, x, blocks)
    return h, kv


def decode_pass(cfg: ModelConfig, blocks: dict, x: jax.Array,
                k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array, *,
                ring: bool):
    """One-token pass.  x [B, d]; k_cache [L, B, S, Hkv, Dh].

    Per-layer cache slices flow through the scan as xs and the updated
    layers come back as ys — NOT as carry, which would double-buffer the
    multi-GB cache inside the loop (measured 4x cache bytes of temp).
    Returns (hidden, k_cache, v_cache)."""
    S = k_cache.shape[2]
    slot = jnp.where(jnp.asarray(ring), pos % S, jnp.minimum(pos, S - 1))

    def body(h, inp):
        pl, k_l, v_l = inp          # k_l [B, S, Hkv, Dh] — this layer's cache
        xin = rmsnorm(h, pl["ln_attn"]["w"], cfg.rmsnorm_eps)
        k_new, v_new = project_kv_token(cfg, pl["attn"], xin, pos)
        k_l = cachelib.onehot_write(k_l, k_new, slot)
        v_l = cachelib.onehot_write(v_l, v_new, slot)
        a = attention_decode(cfg, pl["attn"], xin, k_l, v_l, pos, ring=ring)
        h = h + a
        m = swiglu(rmsnorm(h, pl["ln_mlp"]["w"], cfg.rmsnorm_eps),
                   pl["mlp"]["w_gate"], pl["mlp"]["w_up"], pl["mlp"]["w_down"])
        h = h + m
        return h, (k_l, v_l)

    h, (k_cache, v_cache) = jax.lax.scan(body, x, (blocks, k_cache, v_cache))
    return h, k_cache, v_cache


# ---------------------------------------------------------------------------
# Registry API
# ---------------------------------------------------------------------------


def full_logits(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    """Logits at every position [B, S, V] from one forward pass."""
    x = embed_tokens(params["embed"], tokens)
    h, _ = forward_full(cfg, params["blocks"], x, window=cfg.window)
    h = rmsnorm(h, params["final_norm"]["w"], cfg.rmsnorm_eps)
    return lm_logits(h, head_matrix(cfg, params), cfg.vocab_size)


def train_loss(cfg: ModelConfig, params: dict, batch: dict):
    logits = full_logits(cfg, params, batch["tokens"])
    loss, _ = cross_entropy(logits, batch["labels"])
    return loss, {}


def _finish_cache(cfg, ks, vs, cache_len, window, pos_end):
    """Stacked per-layer K/V [L,B,S,...] -> cache object sized cache_len or
    ring-packed into `window` slots."""
    ks = ks.astype(cfg.kv_dtype)
    vs = vs.astype(cfg.kv_dtype)
    if window:
        k, v = cachelib.ring_pack(ks, vs, window, pos_end)
        return cachelib.WindowKVCache(k, v, jnp.asarray(pos_end, jnp.int32))
    S = ks.shape[2]
    pad = [(0, 0), (0, 0), (0, cache_len - S), (0, 0), (0, 0)]
    return cachelib.KVCache(jnp.pad(ks, pad), jnp.pad(vs, pad),
                            jnp.asarray(pos_end, jnp.int32))


def prefill(cfg: ModelConfig, params: dict, batch: dict, *,
            cache_len: int, long_context: bool = False):
    tokens = batch["tokens"]
    S = tokens.shape[1]
    window = cfg.long_context_window if long_context else cfg.window
    x = embed_tokens(params["embed"], tokens)
    h, (ks, vs) = forward_full(cfg, params["blocks"], x, window=window,
                               collect_kv=True)
    h = rmsnorm(h[:, -1], params["final_norm"]["w"], cfg.rmsnorm_eps)
    logits = lm_logits(h, head_matrix(cfg, params), cfg.vocab_size)
    cache = _finish_cache(cfg, ks, vs, cache_len, window, S)
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, *,
               long_context: bool = False, dtype=None):
    dtype = dtype or cfg.kv_dtype
    window = cfg.long_context_window if long_context else cfg.window
    if window:
        return cachelib.WindowKVCache.init(
            cfg.n_layers, batch, min(window, cache_len), cfg.n_kv_heads,
            cfg.head_dim_, dtype)
    return cachelib.KVCache.init(cfg.n_layers, batch, cache_len,
                                 cfg.n_kv_heads, cfg.head_dim_, dtype)


def decode_step(cfg: ModelConfig, params: dict, cache, batch: dict):
    """batch: {"token": [B] int32}.  Uses cache.pos as the write position."""
    token = batch["token"]
    pos = cache.pos
    ring = isinstance(cache, cachelib.WindowKVCache)
    x = jnp.take(params["embed"], token, axis=0)
    h, kc, vc = decode_pass(cfg, params["blocks"], x, cache.k, cache.v, pos,
                            ring=ring)
    h = rmsnorm(h, params["final_norm"]["w"], cfg.rmsnorm_eps)
    logits = lm_logits(h, head_matrix(cfg, params), cfg.vocab_size)
    new_cache = type(cache)(kc, vc, pos + 1)
    return logits, new_cache
