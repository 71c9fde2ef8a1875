"""The comparison that decides `correct` for a served model.

Once the window has closed, a sample of the finished requests, drawn from
the run seed and always holding the request with the most served tokens,
goes through the plain float32 reference once, each prompt followed by its
served tokens. At every served position the reference's logits give the
gap by which the served token's logit lies below the reference's best; the
number compared is the widest such gap. Greedy decoding serves the
program's own best token, so a sound program reads a gap only where its
bfloat16 rounding reorders near-ties.

The control puts the reference in the program's place, computed with
float8 matrix products: at the same positions it reads the gap of the
token that float8 puts first.
"""

from __future__ import annotations

import dataclasses
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import weights
from bench.reference.numerics import MATMULS

GRID = 256          # reference sequence lengths are padded to this grid
CHUNK = 128         # positions whose logits are formed at once


@dataclasses.dataclass
class Item:
    prompt: np.ndarray      # [S0] int32
    served: np.ndarray      # [tau] int32, the request's own output length


def sample(items: list[Item], seed: int, target_tokens: int,
           max_items: int) -> list[Item]:
    """The item with most served tokens, then others in an order drawn from
    the seed, until `target_tokens` served tokens or `max_items` items."""
    longest = max(range(len(items)),
                  key=lambda i: (len(items[i].served), len(items[i].prompt)))
    rest = [i for i in np.random.default_rng(seed).permutation(len(items))
            if i != longest]
    out, n = [items[longest]], len(items[longest].served)
    for i in rest:
        if n >= target_tokens or len(out) >= max_items:
            break
        out.append(items[i])
        n += len(items[i].served)
    return out


@partial(jax.jit, static_argnames=("control",))
def _gaps(h, ctrl_h, head, served, control: bool):
    """h, ctrl_h [C, d] f32; head [d, V]; served [C] -> gaps [C]."""
    ref = MATMULS["exact"]("cd,dv->cv", h, head)
    if control:
        tok = jnp.argmax(MATMULS["fp8"]("cd,dv->cv", ctrl_h, head), axis=-1)
    else:
        tok = served
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]


class Reference:
    """The configuration's reference over weights drawn again from the seed."""

    def __init__(self, model_file: dict, seed: int):
        self.m = model_file["model_config"]
        self.key = tuple(sorted(self.m.items()))
        self.mod = importlib.import_module(f"bench.reference.{model_file['reference']}")
        self.w = weights.draw(self.mod.leaves(self.m), seed, self.m["param_dtype"])
        self.head = self.mod.head(self.m, self.w)

    def max_gaps(self, items: list[Item], *, control: bool = False) -> list[float]:
        """Widest gap of each item (the control's, where `control`). The
        sequences run a few at a time: attention's scores grow with the
        square of the length, a recurrence's state does not."""
        rows = 8 if self.mod.__name__.endswith("ssm") else 2
        by_len: dict[int, list[int]] = {}
        for i, it in enumerate(items):
            L = -(-(len(it.prompt) + len(it.served)) // GRID) * GRID
            by_len.setdefault(L, []).append(i)
        out = [0.0] * len(items)
        for L, idx in sorted(by_len.items()):
            for j in range(0, len(idx), rows):
                part = idx[j: j + rows]
                toks = np.zeros((rows, L), np.int32)   # one shape per length
                for r, i in enumerate(part):
                    seq = np.concatenate([items[i].prompt, items[i].served])
                    toks[r, : len(seq)] = seq
                toks = jnp.asarray(toks)
                h = self.mod.hidden(self.key, self.w, toks, "exact")
                ch = self.mod.hidden(self.key, self.w, toks, "fp8") if control else h
                for r, i in enumerate(part):
                    out[i] = self._item_gap(h[r], ch[r], items[i], control)
                del h, ch
        return out

    def _item_gap(self, h, ch, it: Item, control: bool) -> float:
        s0, tau = len(it.prompt), len(it.served)
        pos = np.arange(s0 - 1, s0 + tau - 1)
        n = -(-tau // CHUNK) * CHUNK
        pos_p = np.pad(pos, (0, n - tau), constant_values=s0 - 1)
        srv = np.pad(it.served, (0, n - tau))
        gaps = []
        for c in range(0, n, CHUNK):
            p = jnp.asarray(pos_p[c: c + CHUNK])
            gaps.append(np.asarray(_gaps(h[p], ch[p], self.head,
                                         jnp.asarray(srv[c: c + CHUNK]),
                                         control)))
        return float(np.max(np.concatenate(gaps)[:tau]))
