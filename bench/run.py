#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It exits non-zero, printing no result, unless JAX's devices
are TPUs, at least as many as the cell asks for. Set-up (timed as
``setup_s`` from process start) builds the cell's trainer, gives it the
benchmark's weights and resumed state, and runs its first round through
the window's call,
so every program the window runs is compiled and cached before it. The
window then runs whole rounds until ``--seconds`` have passed:
``round_s`` is its time over its rounds. With ``--trace 1`` the window is
profiled and the per-layer metrics are read instead of the end-to-end
ones. After the window, with the program freed, the plain reference
follows the first round, and ``correct`` is its verdict.

The last line of standard output is one JSON object; its last key,
``checks``, gives each number compared with its limit, and the same
lines end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_report(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "device_kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": peak}


def traced_window(jax, trainer, seconds: float):
    """The window under the profiler; its result and the trace summary,
    with the span reduction under ``"spans"``."""
    import spans
    import trace_reduce

    out_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # A process's first profiler session can miss the device's first
        # seconds of ops; a short session first starts the device tracer.
        with jax.profiler.trace(out_dir, profiler_options=opts):
            jax.block_until_ready(jax.numpy.zeros(8) + 1)
        shutil.rmtree(out_dir)
        with jax.profiler.trace(out_dir, profiler_options=opts):
            win = harness.window(trainer, seconds)
        summary = trace_reduce.summarize(*trace_reduce.load(out_dir))
        if summary:
            summary["spans"] = spans.reduce(*spans.load(out_dir))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return win, summary


def read_per_layer(metrics, ctx) -> dict:
    out = {}
    for m in metrics:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, benchmark: dict,
             cell_files=None, t_start: float = T_START) -> dict:
    """Set up, measure and check one cell; the result line as a dict.
    ``cell_files`` is ``harness.resolve``'s tuple, found by name if None."""
    import jax

    import compare

    wl, config, traffic, limits = cell_files or harness.resolve(name, benchmark)
    cell = harness.build(config, traffic, seed)
    snap = harness.first_round(cell, traffic, seed)
    setup_s = time.perf_counter() - t_start

    if trace:
        win, summary = traced_window(jax, cell.trainer, seconds)
    else:
        win, summary = harness.window(cell.trainer, seconds), None
    device = device_report(jax, wl["chips"])

    if trace:
        tr = cell.trainer
        first = harness.batches_fn(tr.dataset, traffic, seed)(
            harness.cohort(traffic, seed, 0), 0)
        program = SimpleNamespace(cfg=cell.cfg, spec=cell.spec, base=tr.base_params,
                                  x=tr.x, space=tr.update_space,
                                  batch=jax.tree.map(lambda a: a[0, 0], first))
        cell.trainer = tr = None
        gc.collect()
        ctx = SimpleNamespace(trace=summary or {}, window_s=win["seconds"],
                              rounds=win["rounds"], shapes=config["shapes"],
                              traffic=traffic, device_kind=device["kind"],
                              chips=wl["chips"], peak_bytes=device["memory_peak_bytes"],
                              program=program, time_calls=harness.time_calls)
        metrics = read_per_layer(harness.per_layer_metrics(name, benchmark), ctx)
        del ctx, program
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"},
                   "round_s": {"value": win["seconds"] / win["rounds"], "unit": "s"}}
    shapes = SimpleNamespace(base_shapes=cell.base_shapes,
                             delta_shapes=cell.delta_shapes)
    cell.trainer = None
    del cell
    gc.collect()

    state0, ref = harness.reference_round(config, traffic, seed, shapes)
    nums = compare.numbers(state0, snap, ref)
    correct = win["failed"] == 0 and compare.verdict(nums, limits)
    result = {"correct": bool(correct), "attempted": win["rounds"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace and summary:
        result["breakdown"] = {"device_ops": summary["top_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    benchmark = harness.load_json(harness.REPO / "BENCHMARK.json")
    files = harness.resolve(args.workload, benchmark)
    chips = files[0]["chips"]

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    from repro.util import use_repo_compile_cache

    use_repo_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      benchmark, files)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
