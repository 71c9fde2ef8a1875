"""Readings that the limits of `correct` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds <n,...>

For each seed, in this one process: serves the cell's job through the
timed path for a short window at the cell's own batch and lengths, frees
the program, and on the same sample of served requests that a run compares
judges the program and the float8 control put in its place by the run's
own comparison (`harness.check_outputs`): each number beside its limit, and
`correct`, which the control has to read false. Prints one JSON line per
seed. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    from bench import harness

    server = harness.Server(cell, seed)
    server.warm_up()
    records = harness.run_window(server, seconds)
    vocab = server.cfg.vocab_size
    server.free()
    _, sample = harness.served(cell, records, seed)
    out = {"seed": seed, "requests": len(sample),
           "tokens": sum(len(it.served) for it in sample)}
    for side, control in (("program", False), ("control", True)):
        numbers = harness.check_outputs(cell, records, seed, vocab,
                                        control=control)
        out[side] = {"correct": harness.is_correct(numbers), **numbers}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import jax

    from bench import harness
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: needs a TPU")
    use_compile_cache()
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"cell": args.workload, **readings(cell, seed, args.seconds)}),
              flush=True)


if __name__ == "__main__":
    main()
