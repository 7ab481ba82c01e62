"""The readings a cell's comparison limit is set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload binomial-ranks \\
        --seeds 101-112 --control-seeds 201-203 --seconds 3

For every seed of ``--seeds`` it makes one run of the cell as
``run.py`` does (the timed path, a short window) and prints the numbers
compared.  For every seed of ``--control-seeds`` it prints the control's
reading: the reference one precision step lower (three bf16 passes for
each f32 product) in the program's place, on the inputs of as many steps
as a run compares, against the reference.  The lower reading of the
limit is the largest of the first, the upper the smallest of the second
(``PERF.md`` gives both).  Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def control_reading(cell: dict, forward, seed: int) -> float:
    """The control's widest gap over the inputs of as many steps as a run
    compares; ``forward`` is the cell's architecture's with its
    configuration bound, one object for every seed."""
    import numpy as np

    import reference
    config, traffic, arch = cell["config"], cell["traffic"], cell["arch"]
    model = arch.make_weights(config, seed)
    inputs = arch.make_inputs(config, traffic, seed)
    x = [np.concatenate([np.asarray(a) for a in xs])
         for xs in inputs[:int(traffic["sampled_steps"])]]
    ref = reference.run(forward, model, x)
    ctl = reference.run(forward, model, x, precision="3pass")
    # on the rows the reference defines, as a run compares them
    gap, _, _ = reference.compare(
        (reference.split(c)[0], r) for c, r in zip(ctl, ref))
    return gap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        ROOT / "artifacts" / "jax-cache")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.find_cell(args.workload, harness.load_benchmark(ROOT))
    devices = harness.require_devices(int(cell["workload"]["chips"]))
    enable_compile_cache()
    for seed in seed_list(args.seeds) if args.seeds else []:
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, t_start=time.perf_counter(),
                               devices=devices)
        print(json.dumps({"program_seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "steps": out["window"]["steps"]}), flush=True)
    forward = functools.partial(cell["arch"].forward, cell["config"])
    for seed in seed_list(args.control_seeds) if args.control_seeds else []:
        print(json.dumps({"control_seed": seed, "max_rel_err":
                          control_reading(cell, forward, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
