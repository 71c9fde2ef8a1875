"""The served weights, drawn again from the run seed for the reference.

The reference takes nothing the program made. It draws the same leaves
from the same seed: each leaf's key is the seed's key folded with the CRC32
of its path, its values a float32 standard normal times the leaf's scale,
rounded to the served dtype. The shapes and scales are the configuration's
(written out here per family), not read from the program.
"""

from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (PRNGKey alone keeps 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(v: int) -> int:
    return -(-v // 128) * 128


def dense_leaves(m: dict) -> dict:
    """path -> (shape, init, scale) for a dense GQA decoder."""
    L, d, hq, hkv = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, ff, vp = m["head_dim"], m["d_ff"], padded_vocab(m["vocab_size"])
    w = 0.02
    leaves = {
        "embed": ((vp, d), "normal", w),
        "blocks/attn/wq": ((L, d, hq, hd), "normal", w),
        "blocks/attn/wk": ((L, d, hkv, hd), "normal", w),
        "blocks/attn/wv": ((L, d, hkv, hd), "normal", w),
        "blocks/attn/wo": ((L, hq, hd, d), "normal", w / max(1, (2 * L) ** 0.5)),
        "blocks/mlp/w_gate": ((L, d, ff), "normal", w),
        "blocks/mlp/w_up": ((L, d, ff), "normal", w),
        "blocks/mlp/w_down": ((L, ff, d), "normal", w),
        "blocks/ln_attn/w": ((L, d), "zeros", 0),
        "blocks/ln_mlp/w": ((L, d), "zeros", 0),
        "final_norm/w": ((d,), "zeros", 0),
    }
    if m.get("qk_norm"):
        leaves["blocks/attn/q_norm"] = ((L, hd), "zeros", 0)
        leaves["blocks/attn/k_norm"] = ((L, hd), "zeros", 0)
    if not m.get("tie_embeddings"):
        leaves["head"] = ((d, vp), "normal", w)
    return leaves


def ssm_leaves(m: dict) -> dict:
    """path -> (shape, init, scale) for a Mamba-2 stack."""
    L, d = m["n_layers"], m["d_model"]
    di = m["ssm_expand"] * d
    G, N, P, K = m["ssm_ngroups"], m["ssm_state"], m["ssm_headdim"], m["conv_kernel"]
    H = di // P
    cc = di + 2 * G * N
    vp = padded_vocab(m["vocab_size"])
    w = 0.02
    return {
        "embed": ((vp, d), "normal", w),
        "blocks/in_proj": ((L, d, 2 * di + 2 * G * N + H), "normal", w),
        "blocks/conv_w": ((L, K, cc), "normal", 0.1),
        "blocks/conv_b": ((L, cc), "zeros", 0),
        "blocks/A_log": ((L, H), "zeros", 0),
        "blocks/D": ((L, H), "ones", 0),
        "blocks/dt_bias": ((L, H), "zeros", 0),
        "blocks/norm_w": ((L, di), "zeros", 0),
        "blocks/out_proj": ((L, di, d), "normal", w / max(1, (2 * L) ** 0.5)),
        "blocks/ln/w": ((L, d), "zeros", 0),
        "final_norm/w": ((d,), "zeros", 0),
        "head": ((d, vp), "normal", w),
    }


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, scale, dtype):
    x = jax.lax.optimization_barrier(jax.random.normal(key, shape, jnp.float32))
    return (x * scale).astype(dtype)


def draw(leaves: dict, seed: int, dtype) -> dict:
    """Flat {path: array} of the leaves, in the served dtype."""
    key = seed_key(seed)
    out = {}
    for path, (shape, init, scale) in leaves.items():
        if init == "normal":
            sub = jax.random.fold_in(key, zlib.crc32(path.encode()))
            out[path] = _draw(sub, shape, scale, jnp.dtype(dtype))
        else:
            fill = 1.0 if init == "ones" else 0.0
            out[path] = jnp.full(shape, fill, dtype)
    return out
