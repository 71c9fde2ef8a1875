"""Energy meters — the PyJoules/uProf adaptation layer (paper §3.2).

`WallClockMeter` measures real wall time of JAX computations on this host
and converts to joules with the host power model (the AMD-uProf method:
power-per-active-core x time).  `ModeledMeter` instead charges an analytic
roofline energy for a declared cost, for use where wall time on CPU is not
representative of the target accelerator.

Both expose  measure(fn) -> (result, seconds, joules)  — the engine's
metering contract — and time `fn` through `timed`, which opens the
`engine.wait` span where the host blocks on the device.
"""

from __future__ import annotations

import time

import jax

from repro.energy.hardware import GENERIC_HOST, HostSpec, Node

WAIT = "engine.wait"


def timed(fn):
    """Runs `fn` and blocks until its outputs are ready on the device, the
    block inside the `engine.wait` span -> (outputs, wall seconds)."""
    t0 = time.perf_counter()
    out = fn()
    with jax.profiler.TraceAnnotation(WAIT):
        out = jax.block_until_ready(out)
    return out, time.perf_counter() - t0


class WallClockMeter:
    """E = P·t with P from the host spec (cores actively serving)."""

    def __init__(self, host: HostSpec = GENERIC_HOST):
        self.host = host
        self.total_s = 0.0
        self.total_j = 0.0

    @property
    def power_w(self) -> float:
        return self.host.idle_w / 4.0 + self.host.active_w_per_core * self.host.serving_cores

    def measure(self, fn):
        out, dt = timed(fn)
        joules = self.power_w * dt
        self.total_s += dt
        self.total_j += joules
        return out, dt, joules


class ModeledMeter:
    """Wall time measured; energy charged from a per-call cost estimate
    produced by `cost_fn() -> (flops, bytes)` against a Node power model."""

    def __init__(self, node: Node, cost_fn):
        self.node = node
        self.cost_fn = cost_fn
        self.total_s = 0.0
        self.total_j = 0.0

    def measure(self, fn):
        out, dt = timed(fn)
        flops, bytes_ = self.cost_fn()
        a = self.node.accel
        joules = (a.idle_w * self.node.n_accel * dt
                  + a.j_per_flop * flops + a.j_per_byte_hbm * bytes_)
        self.total_s += dt
        self.total_j += joules
        return out, dt, joules
