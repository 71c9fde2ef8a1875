"""Attention primitives: full-sequence attention and single-token decode
attention over a cache.

Full-sequence attention has two implementations of one algorithm. The jnp
one scans over query chunks of `chunk_q` where the query length divides
into them, and forms each chunk's f32 scores [chunk_q, Sk] against every
key (the whole [Sq, Sk] otherwise); it is the path on every platform but
TPU and for every call the kernel does not take, and the kernel's oracle.
On TPU, causal self-attention whose length and head size divide into
128 runs as a flash kernel (the splash attention kernel bundled with JAX):
scores stay in the core's memory, key blocks above the diagonal are
skipped, and the softmax is online.

The decode path is the serving hot spot the paper measures; the Pallas
flash-decode kernel in repro.kernels targets it on TPU, while this module
provides the portable jnp implementation (also the kernel's oracle).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro import shard

NEG_INF = -1e30


def _softcap(s: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return jnp.tanh(s / cap) * cap
    return s


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,Sq,Hkv,G,D], k [B,Sk,Hkv,D] -> [B,Hkv,G,Sq,Sk] (f32)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)


def _gqa_out(w: jax.Array, v: jax.Array) -> jax.Array:
    """w [B,Hkv,G,Sq,Sk], v [B,Sk,Hkv,D] -> [B,Sq,Hkv,G,D]."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)


def _attend_block(q, k, v, mask, scale, softcap):
    """One (q-chunk × full-K) attention block.
    q [B,Cq,Hkv,G,D]; k,v [B,Sk,Hkv,D]; mask [Cq,Sk] or broadcastable."""
    s = _gqa_scores(q, k) * scale
    s = _softcap(s, softcap)
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return _attend_out_cast(w, v, q.dtype)


def _attend_out_cast(w, v, dtype):
    return _gqa_out(w, v).astype(dtype)


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    chunk_q: int = 512,
    softcap: float = 0.0,
) -> jax.Array:
    """Full-sequence attention.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] (Hq % Hkv == 0).
    Returns [B, Sq, Hq, D].  Lowered for TPU, causal self-attention with no
    window, soft cap or offset, whose length and head size divide into 128,
    runs as the flash kernel (`causal_flash_attention`); everything else,
    and every other platform, runs the jnp path (`chunked_attention`).
    """
    Sq, D = q.shape[1], q.shape[3]
    jnp_path = partial(chunked_attention, causal=causal, window=window,
                       q_offset=q_offset, chunk_q=chunk_q, softcap=softcap)
    if (causal and not window and not softcap and q_offset == 0
            and Sq == k.shape[1] and Sq % 128 == 0 and D % 128 == 0):
        return jax.lax.platform_dependent(
            q, k, v, tpu=causal_flash_attention, default=jnp_path)
    return jnp_path(q, k, v)


def chunked_attention(q, k, v, *, causal, window, q_offset, chunk_q,
                      softcap):
    """The jnp path of `full_attention` (same arguments).  When Sq > chunk_q
    and divisible, scans over query chunks, so the f32 scores of one step
    are [B, Hq, chunk_q, Sk]; otherwise they are [B, Hq, Sq, Sk] whole."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, Sq, Hkv, G, D)
    qg = shard.constrain(qg, "batch", "seq", "kv_heads", None, None)
    k = shard.constrain(k, "batch", "seq", "kv_heads", None)
    v = shard.constrain(v, "batch", "seq", "kv_heads", None)

    k_pos = jnp.arange(Sk)

    def mask_for(q_pos):
        m = jnp.ones((len(q_pos), Sk), bool)
        if causal:
            m &= k_pos[None, :] <= q_pos[:, None]
        if window and window > 0:
            m &= k_pos[None, :] > q_pos[:, None] - window
        return m

    if Sq <= chunk_q or Sq % chunk_q != 0:
        q_pos = q_offset + jnp.arange(Sq)
        out = _attend_block(qg, k, v, mask_for(q_pos), scale, softcap)
        return out.reshape(B, Sq, Hq, D)

    n_chunks = Sq // chunk_q
    qc = qg.reshape(B, n_chunks, chunk_q, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)

    def body(carry, inp):
        i, q_chunk = inp
        q_pos = q_offset + i * chunk_q + jnp.arange(chunk_q)
        o = _attend_block(q_chunk, k, v, mask_for(q_pos), scale, softcap)
        return carry, o

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qc))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hq, D)
    return out


def flash_block(S: int) -> int:
    """The kernel's query and key block: the largest of 512, 256, 128 that
    divides the sequence length S."""
    return next(b for b in (512, 256, 128) if S % b == 0)


def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """Causal GQA self-attention as a flash kernel.

    q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] -> [B, S, Hq, D], as
    `full_attention`.  Each (batch, KV head) is one MQA call of the splash
    kernel over its Hq / Hkv query heads, so K/V are never repeated per
    query head; blocks of `flash_block(S)`, f32 scores and accumulation,
    the 1/sqrt(D) scale folded into q.  `interpret=True` runs it on the
    CPU (tests)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    blk = flash_block(S)
    sizes = splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * G)
    kernel = splash.make_splash_mqa_single_device(
        mask, block_sizes=sizes, interpret=interpret)
    q = q * jnp.asarray(1.0 / (D ** 0.5), q.dtype)
    qh = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    o = jax.vmap(jax.vmap(kernel))(qh, k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    ring: bool = False,
    softcap: float = 0.0,
) -> jax.Array:
    """One-token attention over a cache.

    q: [B, Hq, D]; k_cache, v_cache: [B, S, Hkv, D]; pos: scalar int32 —
    absolute position of the current token (already written into the cache).

    ring=False: entries with index > pos are masked (cache longer than
    generated prefix).  ring=True: sliding-window ring buffer — every slot
    is valid once pos+1 >= S, else slots > pos are masked.
    """
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, G, D)

    k_cache = shard.constrain(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = shard.constrain(v_cache, "batch", "kv_seq", "kv_heads", None)
    k_cache = k_cache.astype(q.dtype)   # fp8 caches compute in model dtype
    v_cache = v_cache.astype(q.dtype)

    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    idx = jnp.arange(S)
    valid = idx <= pos  # same rule for ring: until full, slots [0..pos] valid;
    if ring:            # once full (pos >= S-1), everything is valid.
        valid = valid | (pos >= S - 1)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w.astype(v_cache.dtype), v_cache)
    return o.reshape(B, Hq, D)


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V3)
# ---------------------------------------------------------------------------


def mla_full_attention(
    q_nope: jax.Array,   # [B,S,H,Dn]
    q_rope: jax.Array,   # [B,S,H,Dr]
    k_nope: jax.Array,   # [B,S,H,Dn]
    k_rope: jax.Array,   # [B,S,Dr] (shared across heads)
    value: jax.Array,    # [B,S,H,Dv]
    *,
    causal: bool = True,
    window: int = 0,
    chunk_q: int = 512,
) -> jax.Array:
    """Full-sequence MLA attention (decoupled rope scores)."""
    B, Sq, H, Dn = q_nope.shape
    Dr = q_rope.shape[-1]
    Sk = k_nope.shape[1]
    scale = 1.0 / ((Dn + Dr) ** 0.5)
    k_pos = jnp.arange(Sk)

    def block(q_n, q_r, q_pos):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_n, k_nope,
                       preferred_element_type=jnp.float32)
        s += jnp.einsum("bqhr,bkr->bhqk", q_r, k_rope,
                        preferred_element_type=jnp.float32)
        s *= scale
        m = jnp.ones((q_n.shape[1], Sk), bool)
        if causal:
            m &= k_pos[None, :] <= q_pos[:, None]
        if window:
            m &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(m[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(value.dtype), value)

    if Sq <= chunk_q or Sq % chunk_q != 0:
        return block(q_nope, q_rope, jnp.arange(Sq))

    n = Sq // chunk_q
    qn = q_nope.reshape(B, n, chunk_q, H, Dn).transpose(1, 0, 2, 3, 4)
    qr = q_rope.reshape(B, n, chunk_q, H, Dr).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        i, a, b = inp
        o = block(a, b, i * chunk_q + jnp.arange(chunk_q))
        return carry, o

    _, outs = jax.lax.scan(body, None, (jnp.arange(n), qn, qr))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, -1)


def mla_decode_absorbed(
    q_latent: jax.Array,  # [B,H,Ckv]  (q_nope absorbed through W_uk)
    q_rope: jax.Array,    # [B,H,Dr]
    c_kv: jax.Array,      # [B,S,Ckv]  latent cache (already rms-normed)
    k_rope: jax.Array,    # [B,S,Dr]
    w_uv: jax.Array,      # [H, Ckv, Dv] (up-projection for V)
    pos: jax.Array,
    scale: float,
) -> jax.Array:
    """Absorbed-matmul MLA decode: scores and values computed in latent
    space — O(S·Ckv) cache traffic instead of O(S·H·Dn) expansion.
    Returns [B, H, Dv]."""
    c_kv = c_kv.astype(q_latent.dtype)
    k_rope = k_rope.astype(q_rope.dtype)
    s = jnp.einsum("bhc,bkc->bhk", q_latent, c_kv,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bhr,bkr->bhk", q_rope, k_rope,
                    preferred_element_type=jnp.float32)
    s *= scale
    S = c_kv.shape[1]
    valid = jnp.arange(S) <= pos
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_latent = jnp.einsum("bhk,bkc->bhc", w.astype(c_kv.dtype), c_kv)
    return jnp.einsum("bhc,hcd->bhd", o_latent, w_uv)
