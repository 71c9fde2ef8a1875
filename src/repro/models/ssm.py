"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060).

Pure-jnp chunked SSD for train/prefill (quadratic intra-chunk + linear
inter-chunk recurrence) and a constant-state decode step.  The Pallas
kernel in repro.kernels.ssd_scan targets the intra-chunk block; this module
is its oracle and the portable path.

No attention, no KV cache: decode cost is position-independent, which is
exactly the workload-model contrast this arch contributes to the paper's
e_K(τin, τout) study (no τin·τout interaction from cache reads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import shard
from repro.models import cache as cachelib
from repro.models.common import (
    ModelConfig,
    padded_vocab,
    ParamDef,
    cross_entropy,
    embed_tokens,
    lm_logits,
    maybe_remat,
    rmsnorm,
)


def conv_channels(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def layer_defs(cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    G, N, H = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    cc = conv_channels(cfg)
    L = (cfg.n_layers,)
    A = ("layers",)
    proj_out = 2 * di + 2 * G * N + H
    return {
        "in_proj": ParamDef(L + (d, proj_out), A + ("embed_w", "mlp")),
        "conv_w": ParamDef(L + (cfg.conv_kernel, cc), A + (None, "mlp"), scale=0.1),
        "conv_b": ParamDef(L + (cc,), A + ("mlp",), init="zeros"),
        "A_log": ParamDef(L + (H,), A + (None,), init="zeros"),   # A = -exp(A_log) ~ -1
        "D": ParamDef(L + (H,), A + (None,), init="ones"),
        "dt_bias": ParamDef(L + (H,), A + (None,), init="zeros"),
        "norm_w": ParamDef(L + (di,), A + ("mlp",), init="zeros"),
        "out_proj": ParamDef(L + (di, d), A + ("mlp", "embed_w"),
                             scale=0.02 / max(1, (2 * cfg.n_layers) ** 0.5)),
        "ln": {"w": ParamDef(L + (d,), A + (None,), init="zeros")},
    }


def param_defs(cfg: ModelConfig) -> dict:
    return {
        "embed": ParamDef((padded_vocab(cfg.vocab_size), cfg.d_model), ("vocab", "embed_w")),
        "blocks": layer_defs(cfg),
        "final_norm": {"w": ParamDef((cfg.d_model,), (None,), init="zeros")},
        "head": ParamDef((cfg.d_model, padded_vocab(cfg.vocab_size)), ("embed_w", "vocab")),
    }


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """x [..., T] -> lower-triangular segment sums [..., T, T]:
    out[..., i, j] = sum(x[..., j+1 : i+1]) for i >= j, -inf above."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), k=0)
    return jnp.where(mask, ss, -jnp.inf)


def ssd_chunked(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array,
                chunk: int, h0: jax.Array | None = None):
    """Chunked SSD.

    xdt [b,s,h,p] (x pre-multiplied by dt), dA [b,s,h] (dt * A, negative),
    B, C [b,s,h,n] (groups already broadcast to heads).
    Returns (y [b,s,h,p], final_state [b,h,p,n]).
    """
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc, cl = s // chunk, chunk

    f32 = jnp.float32
    xdt_c = xdt.reshape(b, nc, cl, h, p)
    dA_c = dA.reshape(b, nc, cl, h).astype(f32)
    B_c = B.reshape(b, nc, cl, h, n)
    C_c = C.reshape(b, nc, cl, h, n)

    dA_cs = jnp.cumsum(dA_c, axis=2)                         # [b,nc,cl,h]
    # intra-chunk (quadratic) term
    Lmat = jnp.exp(_segsum(dA_c.transpose(0, 1, 3, 2)))      # [b,nc,h,cl,cl]
    scores = jnp.einsum("bclhn,bcshn->bchls", C_c, B_c,
                        preferred_element_type=f32)
    scores = scores * Lmat
    y_diag = jnp.einsum("bchls,bcshp->bclhp", scores.astype(xdt.dtype), xdt_c)

    # per-chunk input states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)      # [b,nc,cl,h]
    states = jnp.einsum("bcshn,bcsh,bcshp->bchpn", B_c,
                        decay_states.astype(B_c.dtype), xdt_c)

    # inter-chunk linear recurrence
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :]).astype(f32)    # [b,nc,h]

    def scan_body(carry, inp):
        st, dec = inp
        prev = carry
        new = prev * dec[:, :, None, None] + st.astype(f32)
        return new, prev

    init = jnp.zeros((b, h, p, n), f32) if h0 is None else h0.astype(f32)
    final, prev_states = jax.lax.scan(
        scan_body, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)       # [b,nc,h,p,n]

    state_decay = jnp.exp(dA_cs)                             # [b,nc,cl,h]
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", C_c,
                       prev_states.astype(C_c.dtype),
                       state_decay.astype(C_c.dtype))
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None):
    """Depthwise causal conv, kernel K.  x [B,S,C], w [K,C], b [C].
    state [B,K-1,C] holds the trailing context (decode).  Returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                   # [B, S+K-1, C]
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):, :]
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), new_state


def _split_proj(cfg: ModelConfig, z: jax.Array):
    di, G, N, H = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    zg = z[..., :di]
    xbc = z[..., di : di + di + 2 * G * N]
    dt = z[..., -H:]
    return zg, xbc, dt


def _ssm_params(cfg: ModelConfig, pl: dict, dt_raw: jax.Array):
    A = -jnp.exp(pl["A_log"].astype(jnp.float32))            # [H]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + pl["dt_bias"].astype(jnp.float32))
    return A, dt


def _broadcast_groups(cfg: ModelConfig, bc: jax.Array):
    """[..., G*N] -> B, C each [..., H, N] with groups broadcast to heads."""
    G, N, H = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    B_, C_ = jnp.split(bc, 2, axis=-1)
    rep = H // G
    def expand(t):
        t = t.reshape(t.shape[:-1] + (G, N))
        return jnp.repeat(t, rep, axis=-2)
    return expand(B_), expand(C_)


def mamba_block_full(cfg: ModelConfig, pl: dict, x: jax.Array):
    """Full-sequence Mamba-2 block.  x [B,S,d] -> (y [B,S,d], final_state,
    conv_state)."""
    Bsz, S, _ = x.shape
    H, P = cfg.ssm_nheads, cfg.ssm_headdim
    z = jnp.einsum("bsd,dk->bsk", x, pl["in_proj"])
    zg, xbc, dt_raw = _split_proj(cfg, z)
    xbc, conv_state = _causal_conv(xbc, pl["conv_w"], pl["conv_b"])
    x_ssm = xbc[..., : cfg.d_inner].reshape(Bsz, S, H, P)
    x_ssm = shard.constrain(x_ssm, "batch", "seq", "ssm_heads", None)
    B_, C_ = _broadcast_groups(cfg, xbc[..., cfg.d_inner:])
    A, dt = _ssm_params(cfg, pl, dt_raw)                     # [H], [B,S,H]
    dA = dt * A
    xdt = x_ssm * dt[..., None].astype(x_ssm.dtype)
    chunk = min(cfg.ssm_chunk, S)
    y, final = ssd_chunked(xdt, dA, B_, C_, chunk)
    y = y + pl["D"].astype(y.dtype)[None, None, :, None] * x_ssm
    y = y.reshape(Bsz, S, cfg.d_inner)
    y = y * jax.nn.silu(zg.astype(jnp.float32)).astype(y.dtype)
    y = rmsnorm(y, pl["norm_w"], cfg.rmsnorm_eps)
    return jnp.einsum("bsk,kd->bsd", y, pl["out_proj"]), final, conv_state


def mamba_block_decode(cfg: ModelConfig, pl: dict, x: jax.Array,
                       states: jax.Array, convs: jax.Array, layer: jax.Array):
    """One-token Mamba-2 step of `layer`.  x [B,d]; states [L,B,H,P,N] f32
    and convs [L,B,K-1,cc] are the whole stacks, whose `layer` slices are
    updated in place.  The output reads the new state back from the stack:
    computed beside the update, it would make XLA stage the new state in a
    buffer of its own (see `decode_step`).  Returns (y, states, convs)."""
    Bsz = x.shape[0]
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    z = jnp.einsum("bd,dk->bk", x, pl["in_proj"])
    zg, xbc, dt_raw = _split_proj(cfg, z)
    xbc, conv_state = _causal_conv(
        xbc[:, None], pl["conv_w"], pl["conv_b"],
        state=jax.lax.dynamic_index_in_dim(convs, layer, keepdims=False))
    convs = jax.lax.dynamic_update_index_in_dim(convs, conv_state, layer, 0)
    xbc = xbc[:, 0]
    x_ssm = xbc[..., : cfg.d_inner].reshape(Bsz, H, P)
    B_, C_ = _broadcast_groups(cfg, xbc[..., cfg.d_inner:])  # [B,H,N]
    A, dt = _ssm_params(cfg, pl, dt_raw)                     # [H], [B,H]
    decay = jnp.exp(dt * A)                                  # [B,H]
    upd = jnp.einsum("bhp,bhn->bhpn", (x_ssm * dt[..., None].astype(x_ssm.dtype)).astype(jnp.float32),
                     B_.astype(jnp.float32))
    state = jax.lax.dynamic_index_in_dim(states, layer, keepdims=False)
    states = jax.lax.dynamic_update_index_in_dim(
        states, state * decay[:, :, None, None] + upd, layer, 0)
    state = jax.lax.dynamic_index_in_dim(states, layer, keepdims=False)
    y = jnp.einsum("bhpn,bhn->bhp", state, C_.astype(jnp.float32)).astype(x.dtype)
    y = y + pl["D"].astype(y.dtype)[None, :, None] * x_ssm
    y = y.reshape(Bsz, cfg.d_inner)
    y = y * jax.nn.silu(zg.astype(jnp.float32)).astype(y.dtype)
    y = rmsnorm(y, pl["norm_w"], cfg.rmsnorm_eps)
    return jnp.einsum("bk,kd->bd", y, pl["out_proj"]), states, convs


# ---------------------------------------------------------------------------
# Registry API
# ---------------------------------------------------------------------------


def forward_full(cfg: ModelConfig, params: dict, x: jax.Array, *,
                 collect: bool = False):
    def body(h, pl):
        h = shard.constrain(h, "batch", "seq", None)
        y, final, conv = mamba_block_full(cfg, pl, rmsnorm(h, pl["ln"]["w"], cfg.rmsnorm_eps))
        out = (final, conv) if collect else None
        return h + y, out

    body = maybe_remat(body, cfg.remat)
    h, states = jax.lax.scan(body, x, params["blocks"])
    return h, states


def full_logits(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    """Logits at every position [B, S, V] from one forward pass."""
    x = embed_tokens(params["embed"], tokens)
    h, _ = forward_full(cfg, params, x)
    h = rmsnorm(h, params["final_norm"]["w"], cfg.rmsnorm_eps)
    return lm_logits(h, params["head"], cfg.vocab_size)


def train_loss(cfg: ModelConfig, params: dict, batch: dict):
    logits = full_logits(cfg, params, batch["tokens"])
    loss, _ = cross_entropy(logits, batch["labels"])
    return loss, {}


def prefill(cfg: ModelConfig, params: dict, batch: dict, *,
            cache_len: int = 0, long_context: bool = False):
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens)
    h, (finals, convs) = forward_full(cfg, params, x, collect=True)
    h = rmsnorm(h[:, -1], params["final_norm"]["w"], cfg.rmsnorm_eps)
    logits = lm_logits(h, params["head"], cfg.vocab_size)
    cache = cachelib.SSMCache(convs, finals,
                              jnp.asarray(tokens.shape[1], jnp.int32))
    return logits, cache


def init_cache(cfg: ModelConfig, batch: int, cache_len: int = 0, *,
               long_context: bool = False, dtype=None):
    dtype = dtype or cfg.dtype
    return cachelib.SSMCache.init(cfg.n_layers, batch, cfg.conv_kernel,
                                  conv_channels(cfg), cfg.ssm_nheads,
                                  cfg.ssm_headdim, cfg.ssm_state, dtype)


def decode_step(cfg: ModelConfig, params: dict, cache, batch: dict):
    """One token through every layer, updating the donated cache in place.

    The f32 state stack is updated in place, one layer slice at a time, in
    a fully unrolled layer loop: with each layer's index a constant, XLA
    fuses the read, the update and the write of a layer's state into one
    pass. As scan xs/ys (what the dense `decode_pass` does with its KV
    cache), XLA builds the new stack in a fresh buffer and then copies all
    of it into the donated output; in a rolled loop it stages each layer's
    new state in a buffer of its own. Either costs one more read and write
    of the whole state a step. A write at a layer index would make GSPMD
    gather a stack sharded over "layers"; no served path shards them."""
    token = batch["token"]
    h = jnp.take(params["embed"], token, axis=0)

    def body(carry, pl):
        h, states, convs, layer = carry
        y, states, convs = mamba_block_decode(
            cfg, pl, rmsnorm(h, pl["ln"]["w"], cfg.rmsnorm_eps), states, convs,
            layer)
        return (h + y, states, convs, layer + 1), None

    (h, states, convs, _), _ = jax.lax.scan(
        body, (h, cache.state, cache.conv, jnp.zeros((), jnp.int32)),
        params["blocks"], unroll=True)
    h = rmsnorm(h, params["final_norm"]["w"], cfg.rmsnorm_eps)
    logits = lm_logits(h, params["head"], cfg.vocab_size)
    return logits, cachelib.SSMCache(convs, states, cache.pos + 1)
