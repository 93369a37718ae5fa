"""Benchmark entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus human-readable tables) and
writes one machine-readable ``BENCH_<name>.json`` per section through
``benchmarks.common.write_bench_json`` (schema ``scaffold-bench/v1`` —
the same files the CI bench job uploads as the perf-trajectory artifact).
``--full`` runs paper-scale settings; default is the fast CI-sized pass.
"""
from __future__ import annotations

import time

from benchmarks.common import bench_argparser, write_bench_json


def _timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def main(argv=None) -> None:
    ap = bench_argparser(__doc__.splitlines()[0])
    ap.add_argument("--only", default="",
                    help="comma list: fig3,table3,table4,table5,ablation,"
                         "round")
    args, _ = ap.parse_known_args(argv)
    if args.out_json not in ("", "-"):
        ap.error("run.py writes one BENCH_<section>.json per section "
                 "(fixed names, shared with the standalone scripts); "
                 "pass --out-json - to disable, or run a single script "
                 "directly to choose a path")
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None
    emit_json = args.out_json != "-"

    csv_rows = []

    def emit(name, us, derived, rows=None, json_name=None):
        csv_rows.append(f"{name},{us:.0f},{derived}")
        if emit_json and rows is not None:
            print("wrote", write_bench_json(json_name or name, rows))

    if only is None or "fig3" in only:
        from benchmarks import fig3_quadratics

        rows, us = _timed(fig3_quadratics.main, fast=fast)
        sc = min(r["suboptimality"] for r in rows
                 if r["algo"] == "scaffold" and r["G"] == 100.0)
        fa = min(r["suboptimality"] for r in rows
                 if r["algo"] == "fedavg" and r["G"] == 100.0)
        emit("fig3_quadratics", us,
             f"subopt_ratio_fedavg_over_scaffold={fa/max(sc,1e-30):.2e}",
             rows)

    if only is None or "table3" in only:
        from benchmarks import table3_epochs

        rows, us = _timed(table3_epochs.main, fast=fast)
        sc = min(r["rounds"] for r in rows if r["algo"] == "scaffold")
        fa = min(r["rounds"] for r in rows if r["algo"] == "fedavg")
        emit("table3_epochs", us, f"best_rounds_scaffold={sc};fedavg={fa}",
             rows)

    if only is None or "table4" in only:
        from benchmarks import table4_sampling

        rows, us = _timed(table4_sampling.main, fast=fast)
        worst = max(r["slowdown"] for r in rows if r["algo"] == "scaffold")
        emit("table4_sampling", us,
             f"scaffold_worst_sampling_slowdown={worst:.2f}x", rows)

    if only is None or "table5" in only:
        from benchmarks import table5_nn

        rows, us = _timed(table5_nn.main, fast=fast)
        sc = max(r["accuracy"] for r in rows if r["algo"] == "scaffold")
        emit("table5_nn", us, f"scaffold_best_mlp_acc={sc:.3f}", rows)

    if only is None or "ablation" in only:
        from benchmarks import ablation_server

        rows, us = _timed(ablation_server.main, fast=fast)
        fa = [r for r in rows if r["ablation"] == "server_momentum"
              and r["algo"] == "fedavg"]
        gain = fa[0]["suboptimality"] / max(fa[1]["suboptimality"], 1e-30)
        # json name matches the standalone script / CI artifact
        emit("ablation_server_momentum", us,
             f"fedavgM_gain={gain:.2f}x_scaffold_unaffected", rows,
             json_name="ablation_server")

    if only is None or "round" in only:
        from benchmarks import bench_round

        rows, us = _timed(bench_round.main, fast=fast)
        by_arch = {}
        for r in rows:
            by_arch.setdefault(r["arch"], {})[r["mode"]] = r
        for arch, modes in by_arch.items():
            # NOTE: full trainer wall time (host sampling + data loading +
            # device round), not device-only round time
            emit(f"round_{arch}", modes["sync"]["us_per_round"],
                 f"scaffold_trainer_sync_cpu;"
                 f"pipelined_us={modes['pipelined']['us_per_round']:.0f};"
                 f"scanned_us={modes['scanned']['us_per_round']:.0f};"
                 f"scanned_speedup="
                 f"{modes['scanned']['speedup_vs_sync']:.2f}x")
        if emit_json:
            print("wrote", write_bench_json("round", rows))

    print("\n=== CSV (name,us_per_call,derived) ===")
    for line in csv_rows:
        print(line)


if __name__ == "__main__":
    main()
