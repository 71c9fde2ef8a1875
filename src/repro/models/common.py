"""Shared model substrate: config, parameter definitions, norms, RoPE,
embeddings, losses.

Every architecture is a pure-functional JAX model: params are nested dicts
of arrays, layer stacks are stacked along a leading `layers` axis and run
under `jax.lax.scan` (keeps HLO size and compile time flat in depth, which
matters for the 95-layer dry-runs).
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro import shard


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config object drives every family; family-specific fields default
    to 'off'.  Instances live in repro.configs.<arch>."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads

    # attention options
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    window: int = 0                # 0 = full causal attention
    long_context_window: int = 8192  # sliding window used in long_500k mode
    attn_logit_softcap: float = 0.0

    # norm / misc
    rmsnorm_eps: float = 1e-5
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_expert: int = 0              # expert FFN width (d_ff used if 0)
    n_dense_layers: int = 0        # leading dense layers (DeepSeek-V3)
    dense_d_ff: int = 0            # FFN width of those dense layers
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_token_chunk: int = 32768   # dispatch in token chunks: bounds the
                                   # [T*K, d] pair intermediates at 1M-token
                                   # prefill scale
    expert_shard_axes: tuple[str, ...] = ("model",)  # mesh axes for "expert"

    # MLA (DeepSeek-V3)
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_absorb: bool = False       # absorbed-matmul decode (beyond-paper opt)
    mtp: bool = False              # multi-token-prediction aux head (train)

    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_ngroups: int = 1
    ssm_chunk: int = 256
    conv_kernel: int = 4

    # hybrid (RecurrentGemma / Griffin)
    block_pattern: tuple[str, ...] = ()   # e.g. ("rec", "rec", "attn")
    lru_width: int = 0
    local_window: int = 0

    # encoder-decoder (Seamless)
    enc_layers: int = 0
    dec_layers: int = 0
    n_frames: int = 4096           # stubbed audio frontend output length

    # VLM (InternVL2)
    n_patches: int = 0             # stubbed vision frontend output length

    # numerics
    param_dtype: str = "float32"
    cache_dtype: str = ""          # "" = param dtype; "float8_e4m3fn" halves
                                   # KV-cache bytes (beyond-paper decode opt)
    # training
    microbatch: int = 0            # 0 = single step, else gradient accumulation
    grad_accum_dtype: str = "float32"  # bfloat16 for the 671B config (memory)
    optimizer: str = "adamw"
    remat: bool = True
    # metadata
    n_params_note: str = ""
    source: str = ""
    accuracy_ak: float = 0.0       # A_K for the paper's accuracy model

    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def d_inner(self) -> int:       # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def kv_dtype(self):
        return jnp.dtype(self.cache_dtype) if self.cache_dtype else self.dtype

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Parameter definitions — one code path builds shapes, specs and values
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]   # logical axes, same rank as shape
    init: str = "normal"           # normal | zeros | ones
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


ParamTree = Mapping[str, object]   # nested dict: str -> ParamDef | ParamTree


def _flatten_defs(defs: ParamTree, prefix: str = "") -> list[tuple[str, ParamDef]]:
    out = []
    for k in sorted(defs):
        v = defs[k]
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, ParamDef):
            out.append((path, v))
        else:
            out.extend(_flatten_defs(v, path))
    return out


def _set_path(tree: dict, path: str, value) -> None:
    keys = path.split("/")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_leaf(key: jax.Array, shape: tuple, scale: float, dtype) -> jax.Array:
    # One program per leaf, so the f32 draw is freed inside it. Op by op,
    # dispatch runs ahead of the device and the f32 arrays of many leaves
    # are alive at once. The barrier stops XLA from folding the scale into
    # the draw, which would change the weights in the last bit.
    x = jax.lax.optimization_barrier(jax.random.normal(key, shape, jnp.float32))
    return (x * scale).astype(dtype)


def init_params(defs: ParamTree, key: jax.Array, dtype) -> dict:
    """Materialize parameters from defs (deterministic per path)."""
    params: dict = {}
    for path, d in _flatten_defs(defs):
        sub = jax.random.fold_in(key, zlib.crc32(path.encode()))
        if d.init == "zeros":
            val = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            val = jnp.ones(d.shape, dtype)
        else:
            val = _normal_leaf(sub, tuple(d.shape), d.scale, jnp.dtype(dtype))
        _set_path(params, path, val)
    return params


def param_specs(defs: ParamTree, rules=None) -> dict:
    """PartitionSpec pytree matching init_params' structure."""
    specs: dict = {}
    for path, d in _flatten_defs(defs):
        _set_path(specs, path, shard.resolve(d.axes, rules))
    return specs


def param_shapes(defs: ParamTree, dtype) -> dict:
    out: dict = {}
    for path, d in _flatten_defs(defs):
        _set_path(out, path, jax.ShapeDtypeStruct(d.shape, dtype))
    return out


def count_params(defs: ParamTree) -> int:
    return int(sum(np.prod(d.shape) for _, d in _flatten_defs(defs)))


# ---------------------------------------------------------------------------
# Numerics building blocks
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: [..., S, H, D] (D even), positions broadcastable
    to [..., S]."""
    d = x.shape[-1]
    assert d % 2 == 0, "rope head dim must be even"
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [..., S, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(max_len: int, d: int) -> jax.Array:
    """Classic transformer sinusoidal position table [max_len, d]."""
    pos = np.arange(max_len)[:, None]
    dim = np.arange(0, d, 2)[None, :]
    inv = 1.0 / (10000.0 ** (dim / d))
    tab = np.zeros((max_len, d), dtype=np.float32)
    tab[:, 0::2] = np.sin(pos * inv)
    tab[:, 1::2] = np.cos(pos * inv)
    return jnp.asarray(tab)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard.constrain(h, "batch", None, "mlp") if h.ndim == 3 else h
    return jnp.einsum("...f,fd->...d", h, w_down)


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up, w_down: jax.Array, b_down) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, w_up) + b_up
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard.constrain(h, "batch", None, "mlp") if h.ndim == 3 else h
    return jnp.einsum("...f,fd->...d", h, w_down) + b_down


def mlp_defs(d_model: int, d_ff: int, n_layers: int | None = None, *, scale: float = 0.02) -> dict:
    """SwiGLU MLP ParamDefs, optionally stacked over layers."""
    lead = () if n_layers is None else (n_layers,)
    lax_ = () if n_layers is None else ("layers",)
    return {
        "w_gate": ParamDef(lead + (d_model, d_ff), lax_ + ("embed_w", "mlp"), scale=scale),
        "w_up": ParamDef(lead + (d_model, d_ff), lax_ + ("embed_w", "mlp"), scale=scale),
        "w_down": ParamDef(lead + (d_ff, d_model), lax_ + ("mlp", "embed_w"), scale=scale),
    }


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------


def padded_vocab(v: int, multiple: int = 128) -> int:
    """Vocabulary rows padded so the vocab dim shards evenly on any mesh
    axis (the standard production fix for odd vocab sizes like 92553).
    Padded logit columns are masked to -inf in lm_logits."""
    return ((v + multiple - 1) // multiple) * multiple


def embed_tokens(emb: jax.Array, tokens: jax.Array) -> jax.Array:
    x = jnp.take(emb, tokens, axis=0)
    return shard.constrain(x, "batch", "seq", None)


def lm_logits(x: jax.Array, head: jax.Array, n_valid: int | None = None) -> jax.Array:
    """x [..., d] @ head [d, Vp] -> f32 logits (vocab sharded); columns
    >= n_valid (padding) are masked to -inf."""
    logits = jnp.einsum("...d,dv->...v", x, head).astype(jnp.float32)
    if n_valid is not None and n_valid < head.shape[-1]:
        col = jnp.arange(head.shape[-1])
        logits = jnp.where(col < n_valid, logits, -1e30)
    if logits.ndim == 3:
        logits = shard.constrain(logits, "batch", "seq", "vocab")
    else:
        logits = shard.constrain(logits, "batch", "vocab")
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Masked mean CE.  labels: int32, -1 = ignore.  Returns (loss, n_valid)."""
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    n = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / n, n


def maybe_remat(fn: Callable, enabled: bool) -> Callable:
    if not enabled:
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
