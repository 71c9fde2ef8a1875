"""Matrix products for the reference and for its control.

`exact` computes in float32 at `highest` precision, which a TPU otherwise
rounds to bfloat16 passes. `fp8` rounds both operands to float8 (e4m3) with
one scale per tensor, the precision below the served bfloat16, and then
multiplies in float32: the control that a correct limit must reject.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def exact(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def to_fp8(t: jax.Array) -> jax.Array:
    t = t.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / E4M3_MAX
    return (t / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def fp8(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return exact(spec, to_fp8(a), to_fp8(b))


MATMULS = {"exact": exact, "fp8": fp8}


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Norm weights are stored as the offset from 1."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w.astype(F32))
