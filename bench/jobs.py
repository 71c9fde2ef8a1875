"""One generator for every traffic file under `bench/traffic/`.

A traffic file is data: seeded lognormal prompt and output lengths, each
clipped to a range, prompts rounded up to a grid. The lengths and their
order come from the file's own `length_seed`, so every run seed serves the
same job; the run seed draws only the prompts' token ids (and the weights).

An offline job is served as static batches of one prompt length: requests
are taken in draw order and grouped by prompt length, and a batch leaves
as soon as its length holds `batch` requests. The draw copies
`repro.data.workloads.alpaca_like_workload` (lognormal, clipped).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Batch:
    prompt_len: int
    requests: tuple[int, ...]       # indices into the job's requests
    out_lens: tuple[int, ...]       # each request's own output length

    @property
    def max_new(self) -> int:
        return max(self.out_lens)


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(x, spec["min"], spec["max"])


def draw_lengths(traffic: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of the job, int arrays."""
    rng = np.random.default_rng(traffic["length_seed"])
    n = traffic["n_requests"]
    tin = _lognormal(rng, traffic["prompt"], n)
    tout = _lognormal(rng, traffic["output"], n)
    grid = traffic["grid"]
    tin = (np.ceil(tin / grid) * grid).astype(int)
    tout = np.rint(tout).astype(int)
    return tin, tout


def make_batches(tin: np.ndarray, tout: np.ndarray, batch: int) -> list[Batch]:
    """Static same-length batches, in the order each fills."""
    pending: dict[int, list[int]] = {}
    out = []
    for i, s in enumerate(tin):
        group = pending.setdefault(int(s), [])
        group.append(i)
        if len(group) == batch:
            out.append(Batch(int(s), tuple(group),
                             tuple(int(tout[j]) for j in group)))
            pending[int(s)] = []
    return out


def job(traffic: dict, cell: dict) -> list[Batch]:
    """The first `job_batches` batches of the traffic at the cell's batch."""
    tin, tout = draw_lengths(traffic)
    batches = make_batches(tin, tout, cell["batch"])
    n = cell["job_batches"]
    if len(batches) < n:
        raise ValueError(f"traffic yields {len(batches)} batches of "
                         f"{cell['batch']}, the cell needs {n}")
    return batches[:n]


def cache_len(prompt_len: int, max_new: int, bucket: int) -> int:
    """The cache length `InferenceEngine` pads a batch to."""
    return max(bucket, math.ceil((prompt_len + max_new) / bucket) * bucket)


def shapes(batches: list[Batch], bucket: int) -> list[tuple[int, int]]:
    """Distinct (prompt length, cache length) pairs the job compiles."""
    return sorted({(b.prompt_len, cache_len(b.prompt_len, b.max_new, bucket))
                   for b in batches})


def prompts(batches: list[Batch], vocab: int, seed: int) -> list[np.ndarray]:
    """Token ids [B, S0] int32 for each batch, drawn from the run seed."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (len(b.requests), b.prompt_len),
                         dtype=np.int32) for b in batches]
