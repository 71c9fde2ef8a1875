"""Operations and bytes a dense GQA decoder's serving steps need.

Counted from the configuration's shapes, as the least work the algorithm
needs, so a roofline share built on them cannot pass 100%:
- FLOPs: 2 x matmul parameters x tokens, plus causal attention (QK and PV)
  over the live positions only; logits only where the step forms them;
- bytes: every weight read once (a tied head is the embedding, counted
  once), KV of the live positions read and the new positions' KV written,
  and the float32 logits written. Activations are left out.
"""

from __future__ import annotations

import numpy as np


def _sizes(m: dict):
    L, d, hq, hkv = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, ff, V = m["head_dim"], m["d_ff"], m["vocab_size"]
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff
    norms = L * (2 * d + (2 * hd if m.get("qk_norm") else 0)) + d
    wb = np.dtype(m["param_dtype"]).itemsize
    kv_token = L * 2 * hkv * hd * wb            # one position, all layers
    return L, d, hq, hd, V, per_layer, norms, wb, kv_token


def _weight_bytes(m: dict, embed_rows: int) -> float:
    L, d, hq, hd, V, per_layer, norms, wb, _ = _sizes(m)
    head = V * d if m.get("tie_embeddings") else V * d + embed_rows * d
    return wb * (L * per_layer + norms + head)


def decode_step(m: dict, B: int, pos: int) -> tuple[float, float]:
    """One decode step of B sequences writing position `pos` (so pos cached
    positions are read and pos + 1 attended)."""
    L, d, hq, hd, V, per_layer, _, _, kv_token = _sizes(m)
    flops = 2 * B * (L * per_layer + d * V) + L * B * 4 * hq * hd * (pos + 1)
    byts = (_weight_bytes(m, B) + B * kv_token * (pos + 1) + B * V * 4)
    return float(flops), float(byts)


def prefill(m: dict, B: int, S: int) -> tuple[float, float]:
    """Prefill of B prompts of S tokens; logits at the last position."""
    L, d, hq, hd, V, per_layer, _, _, kv_token = _sizes(m)
    flops = (2 * B * S * L * per_layer + 2 * B * d * V
             + L * B * 4 * hq * hd * S * (S + 1) / 2)
    byts = _weight_bytes(m, B * S) + B * S * kv_token + B * V * 4
    return float(flops), float(byts)
