"""Operations and bytes a Mamba-2 stack's serving steps need.

Counted from the configuration's shapes, as the least work the algorithm
needs, so a roofline share built on them cannot pass 100%:
- FLOPs: 2 x matmul parameters x tokens, the depthwise conv (2 K per
  channel and token) and the state recurrence in its linear form (decay,
  outer-product update and readout, 5 H P N per token), not the chunked
  dual form's quadratic intra-chunk work; logits only where formed;
- bytes: every weight read once, the embedding's rows looked up, the f32
  SSD state and the conv state read and written (decode) or written
  (prefill), and the float32 logits written. Activations are left out.
"""

from __future__ import annotations

import numpy as np


def _sizes(m: dict):
    L, d, V = m["n_layers"], m["d_model"], m["vocab_size"]
    di = m["ssm_expand"] * d
    G, N, P, K = m["ssm_ngroups"], m["ssm_state"], m["ssm_headdim"], m["conv_kernel"]
    H = di // P
    cc = di + 2 * G * N
    matmul = d * (2 * di + 2 * G * N + H) + di * d
    small = K * cc + cc + 3 * H + di + d         # conv, biases, A, D, dt, norms
    wb = np.dtype(m["param_dtype"]).itemsize
    per_token = 2 * K * cc + 5 * H * P * N       # conv + recurrence, one layer
    state = L * H * P * N * 4                    # f32 SSD state, one sequence
    conv = L * (K - 1) * cc * wb                 # conv state, one sequence
    return L, d, V, matmul, small, wb, per_token, state, conv


def _weight_bytes(m: dict, embed_rows: int) -> float:
    L, d, V, matmul, small, wb, *_ = _sizes(m)
    return wb * (L * (matmul + small) + d + V * d + embed_rows * d)


def decode_step(m: dict, B: int, pos: int) -> tuple[float, float]:
    """One decode step of B sequences (independent of the position)."""
    L, d, V, matmul, _, _, per_token, state, conv = _sizes(m)
    flops = 2 * B * (L * matmul + d * V) + L * B * per_token
    byts = _weight_bytes(m, B) + 2 * B * (state + conv) + B * V * 4
    return float(flops), float(byts)


def prefill(m: dict, B: int, S: int) -> tuple[float, float]:
    """Prefill of B prompts of S tokens; logits at the last position."""
    L, d, V, matmul, _, _, per_token, state, conv = _sizes(m)
    flops = 2 * B * S * L * matmul + 2 * B * d * V + L * B * S * per_token
    byts = _weight_bytes(m, B * S) + B * (state + conv) + B * V * 4
    return float(flops), float(byts)
