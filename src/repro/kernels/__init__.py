"""Pallas TPU kernels for the perf-critical compute layers, each validated
in interpret mode against the pure-jnp oracles in repro.kernels.ref:

  * decode_attention — flash-decode GQA (the serving hot spot the paper
    measures; online softmax over streamed KV blocks)
  * ssd_scan         — Mamba-2 SSD chunk scan (quadratic-in-VMEM,
    linear-across-chunks)
  * rglru_scan       — RG-LRU linear recurrence (doubling scan per block)
"""

from repro.kernels import ops  # noqa: F401
