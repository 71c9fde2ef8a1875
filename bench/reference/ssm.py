"""Plain float32 reference of a Mamba-2 stack (arXiv:2405.21060): per block
RMSNorm, input projection into gate z, conv channels (x, B, C) and dt;
depthwise causal conv of width K with SiLU; dt = softplus(dt + dt_bias),
A = -exp(A_log); the selective state recurrence run token by token,
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t; gate by
SiLU(z), RMSNorm, output projection, residual; final RMSNorm. It uses the
recurrence itself, not the chunked dual form the program computes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference.numerics import F32, MATMULS, rmsnorm
from bench.reference.weights import ssm_leaves as leaves  # noqa: F401


@partial(jax.jit, static_argnames=("m", "matmul"))
def hidden(m, w: dict, tokens: jax.Array, matmul: str = "exact") -> jax.Array:
    """m: the configuration as a hashable tuple of items; tokens [n, S].
    Returns the final normed hidden states [n, S, d] in float32."""
    m = dict(m)
    mm = MATMULS[matmul]
    eps = m["rmsnorm_eps"]
    d = m["d_model"]
    di = m["ssm_expand"] * d
    G, N, P, K = m["ssm_ngroups"], m["ssm_state"], m["ssm_headdim"], m["conv_kernel"]
    H = di // P
    n, S = tokens.shape
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    blocks = {k[len("blocks/"):]: v for k, v in w.items() if k.startswith("blocks/")}

    def layer(x, lw):
        a = rmsnorm(x, lw["ln/w"], eps)
        proj = mm("nsd,dk->nsk", a, lw["in_proj"])
        z = proj[..., :di]
        xbc = proj[..., di: 2 * di + 2 * G * N]
        dt = proj[..., 2 * di + 2 * G * N:]
        cw, cb = lw["conv_w"].astype(F32), lw["conv_b"].astype(F32)
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(xp[:, i: i + S] * cw[i] for i in range(K)) + cb)
        xs = xbc[..., :di].reshape(n, S, H, P)
        Bm = jnp.repeat(xbc[..., di: di + G * N].reshape(n, S, G, N), H // G, axis=2)
        Cm = jnp.repeat(xbc[..., di + G * N:].reshape(n, S, G, N), H // G, axis=2)
        A = -jnp.exp(lw["A_log"].astype(F32))
        dt = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))            # [n,S,H]

        def step(h, inp):
            x_t, B_t, C_t, dt_t = inp                                   # [n,H,P] [n,H,N] [n,H,N] [n,H]
            h = (h * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
            return h, jnp.sum(h * C_t[:, :, None, :], axis=-1)

        seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, Bm, Cm, dt))
        _, y = jax.lax.scan(step, jnp.zeros((n, H, P, N), F32), seq)
        y = jnp.moveaxis(y, 0, 1) + lw["D"].astype(F32)[:, None] * xs
        y = y.reshape(n, S, di) * jax.nn.silu(z)
        y = rmsnorm(y, lw["norm_w"], eps)
        return x + mm("nsk,kd->nsd", y, lw["out_proj"]), None

    x, _ = jax.lax.scan(layer, x, blocks)
    return rmsnorm(x, w["final_norm/w"], eps)


def head(m: dict, w: dict) -> jax.Array:
    return w["head"][:, : m["vocab_size"]]
