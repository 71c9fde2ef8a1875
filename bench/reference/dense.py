"""Plain float32 reference of a dense GQA decoder (Qwen3): embedding, per
block RMSNorm, q/k/v projections, per-head RMSNorm of q and k, rotary
embedding (rotate-half), causal softmax attention with each key/value head
shared by n_heads / n_kv_heads query heads, output projection, residual,
RMSNorm, SwiGLU MLP, residual; final RMSNorm. No cache, no kernel, no
batching trick: one full forward pass over each whole sequence.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference.numerics import F32, MATMULS, rmsnorm
from bench.reference.weights import dense_leaves as leaves  # noqa: F401


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [n, S, H, D] at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs          # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "matmul"))
def hidden(m, w: dict, tokens: jax.Array, matmul: str = "exact") -> jax.Array:
    """m: the configuration as a hashable tuple of items; tokens [n, S].
    Returns the final normed hidden states [n, S, d] in float32."""
    m = dict(m)
    mm = MATMULS[matmul]
    eps, theta = m["rmsnorm_eps"], m["rope_theta"]
    hd = m["head_dim"]
    G = m["n_heads"] // m["n_kv_heads"]
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    blocks = {k[len("blocks/"):]: v for k, v in w.items() if k.startswith("blocks/")}

    def layer(x, lw):
        a = rmsnorm(x, lw["ln_attn/w"], eps)
        q = mm("nsd,dhe->nshe", a, lw["attn/wq"])
        k = mm("nsd,dhe->nshe", a, lw["attn/wk"])
        v = mm("nsd,dhe->nshe", a, lw["attn/wv"])
        if m.get("qk_norm"):
            q = rmsnorm(q, lw["attn/q_norm"], eps)
            k = rmsnorm(k, lw["attn/k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        s = mm("nqhe,nkhe->nhqk", q, k) / hd ** 0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("nhqk,nkhe->nqhe", p, v)
        x = x + mm("nqhe,hed->nqd", o, lw["attn/wo"])
        a = rmsnorm(x, lw["ln_mlp/w"], eps)
        g = mm("nsd,df->nsf", a, lw["mlp/w_gate"])
        u = mm("nsd,df->nsf", a, lw["mlp/w_up"])
        x = x + mm("nsf,fd->nsd", jax.nn.silu(g) * u, lw["mlp/w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, blocks)
    return rmsnorm(x, w["final_norm/w"], eps)


def head(m: dict, w: dict) -> jax.Array:
    """[d, vocab] output matrix (tied: the embedding's transpose)."""
    h = w["embed"].T if m.get("tie_embeddings") else w["head"]
    return h[:, : m["vocab_size"]]
