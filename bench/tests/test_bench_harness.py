"""The harness is driven by data: every cell resolves its files by name,
a new cell is new files and entries, and a run without a chip stops
before it measures anything."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import compare  # noqa: E402
import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def benchmark():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_benchmark_file_shape(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert benchmark["paths"] == ["bench"]
    assert 1 <= benchmark["run_seconds"] <= 51
    for c in benchmark["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in benchmark["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = benchmark["end_to_end"] + benchmark["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in benchmark["end_to_end"]}
    assert "setup_s" in e2e
    for m in benchmark["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in benchmark["per_layer"]:
        assert m["moves"] in e2e and m["layer"]


@pytest.mark.parametrize("cell", ["hymba-silo-2k", "mamba2-silo-2k"])
def test_cell_resolves_by_name(cell, benchmark):
    wl, config, traffic, limits = harness.resolve(cell, benchmark)
    assert wl["name"] == cell
    assert {"dx", "dc", "ci"} <= set(limits) <= set(compare.NAMES)
    assert (BENCH / "traffic" / f"{traffic['generator']}.py").exists()
    cfg = harness.program_model(config)  # shapes agree with the program
    assert cfg.num_layers == config["shapes"]["layers"]
    per_layer = harness.per_layer_metrics(cell, benchmark)
    assert per_layer, "every cell reports a per-layer metric"
    for m in per_layer:
        assert hasattr(harness.load_module(BENCH / "metrics" / f"{m['name']}.py"), "read")


def test_new_cell_is_new_files_and_entries(tmp_path, benchmark):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    traffic = json.loads((BENCH / "traffic" / "short-20.json").read_text())
    traffic["seq_len"] = 64
    (root / "bench" / "traffic" / "short-64.json").write_text(json.dumps(traffic))
    (root / "bench" / "limits" / "mamba2-short-64.json").write_text(
        (BENCH / "limits" / "mamba2-silo-2k.json").read_text())
    b = json.loads(json.dumps(benchmark))
    b["workloads"].append({"name": "mamba2-short-64", "config": "mamba2-2.7b",
                           "traffic": "short-64", "chips": 1, "why": "test"})
    for m in b["per_layer"]:
        m["workloads"].append("mamba2-short-64")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    wl, config, traffic2, _ = harness.resolve("mamba2-short-64", root=root)
    assert traffic2["seq_len"] == 64 and config["name"] == "mamba2-2.7b"
    assert len(harness.per_layer_metrics("mamba2-short-64", b)) == len(b["per_layer"])


def test_code_names_no_cell(benchmark):
    names = ({w["name"] for w in benchmark["workloads"]}
             | {w["traffic"] for w in benchmark["workloads"]}
             | {c["name"] for c in benchmark["configs"]})
    for path in list(BENCH.glob("*.py")) + list(BENCH.glob("metrics/*.py")):
        text = path.read_text()
        assert not [n for n in names if n in text], path


def test_run_without_a_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "hymba-silo-2k",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_readers_leave_out_what_they_cannot_read():
    ctx = SimpleNamespace(trace={}, rounds=2, window_s=10.0, chips=1,
                          device_kind="TPU v5 lite", peak_bytes=3 * 2**30,
                          shapes=json.loads((BENCH / "configs" / "hymba-1.5b.json")
                                            .read_text())["shapes"],
                          traffic=json.loads((BENCH / "traffic" / "silo-2k.json")
                                             .read_text()))
    read = lambda name: harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)  # noqa: E731
    assert read("idle_share") is None and read("chunk_gap_ms") is None
    assert read("peak_hbm_gib") == 3.0
    import flops
    want = 100 * 2 * flops.required_per_round(ctx.shapes, ctx.traffic) / (10.0 * 197e12)
    assert read("mfu") == pytest.approx(want)
    ctx.device_kind = "cpu"
    with pytest.raises(KeyError):
        read("mfu")
