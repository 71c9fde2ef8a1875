"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (and `breakdown`
with `--trace 1`), and as the last lines of standard error each number
compared with the reference beside its limit. Exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    cell = harness.load_cell(args.workload)
    chips = cell.entry["chips"]
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"bench: needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform!r} device(s)")
    use_compile_cache()
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, chips)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
