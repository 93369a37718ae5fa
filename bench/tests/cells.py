"""Drive ``run.run_cell`` on a tiny cell on the CPU: the whole run but
the look for a chip."""
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import harness  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402


def run_tiny(config: str, traffic: str, seed: int = 2**31 + 11,
             limits_of: str = "hymba-silo-2k") -> dict:
    benchmark = harness.load_json(harness.REPO / "BENCHMARK.json")
    wl = {"name": f"tiny-{config}-{traffic}", "chips": 1}
    limits = harness.resolve(limits_of, benchmark)[3]
    files = (wl, tiny.config(config), tiny.traffic(traffic), limits)
    return run.run_cell(wl["name"], seed, 0.05, False, benchmark, files,
                        t_start=time.perf_counter())
