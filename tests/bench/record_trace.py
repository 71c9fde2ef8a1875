"""Record the small TPU trace that `test_bench_trace.py` reduces.

    python3 tests/bench/record_trace.py <out.xplane.pb>

Runs, under the profiler, two host spans `bench.batch`, each around a
jitted `prefill` and three jitted `_decode` calls on small arrays (the
names the serving programs carry), and copies the `.xplane.pb` to the
given path. Prints each plane's lines with their event counts, and each
module event on the device. Needs a TPU.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def prefill(x):
    return jnp.tanh(x @ x).sum(0)


def _decode(x, v):
    return jnp.tanh(x @ v)


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    pf, dc = jax.jit(prefill), jax.jit(_decode)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    v = pf(x)
    jax.block_until_ready(dc(x, v))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    with jax.profiler.trace(tmp, profiler_options=opts):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.batch"):
                v = pf(x)
                for _ in range(3):
                    v = dc(x, v).astype(jnp.bfloat16)
                    jax.block_until_ready(v)
                    time.sleep(0.002)
    path = next(Path(tmp).rglob("*.xplane.pb"))
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    pd = jax.profiler.ProfileData.from_file(out)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs),
                  sorted({e.name for e in evs})[:12])
    print("bytes", Path(out).stat().st_size)


if __name__ == "__main__":
    main(sys.argv[1])
