#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not part of a
benchmark run.

    python bench/calibrate.py --workload <name> --seeds 11 12 13 \
        --controls 3 --out calib.jsonl

For each seed, in one process: the program's first round (the run's own
set-up, no window), then the float32 reference over it, and the numbers
of ``compare.numbers``. For the first ``--controls`` seeds also the
control (the reference computed with float8_e4m3 matmul operands, the
precision below the configuration's bfloat16, put in the program's place)
and two planted faults, each the reference with it: the cohort means
taken over half of the sampled clients, and every c_i read as zero. A
round that returns its state unchanged reads 1 on ``dx`` and ``dc`` by
construction and needs no run. One JSON line per seed, also appended to
``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import compare
    from repro.util import use_repo_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    use_repo_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, config, traffic, _ = harness.resolve(args.workload)
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        cell = harness.build(config, traffic, seed)
        snap = harness.first_round(cell, traffic, seed)
        shapes = SimpleNamespace(base_shapes=cell.base_shapes,
                                 delta_shapes=cell.delta_shapes)
        cell.trainer = None
        del cell
        gc.collect()
        t1 = time.perf_counter()
        state0, ref = harness.reference_round(config, traffic, seed, shapes)
        t2 = time.perf_counter()
        row = {"workload": args.workload, "seed": seed,
               "program": compare.numbers(state0, snap, ref),
               "program_s": t1 - t0, "reference_s": t2 - t1}
        if i < args.controls:
            for name, kw in (("control", {"quant": jnp.float8_e4m3fn}),
                             ("half_cohort", {"fault": "drop_half"}),
                             ("zero_ci", {"fault": "zero_ci"})):
                _, other = harness.reference_round(config, traffic, seed, shapes, **kw)
                row[name] = compare.numbers(state0, compare.as_program(other), ref)
                del other
        del ref, snap
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
