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

    # a configuration whose stack mixes kinds, as groups: a file and entries
    hybrid = json.loads((BENCH / "configs" / "mamba2-2.7b.json").read_text())
    hybrid["name"] = "hybrid-mf"
    hybrid["program"]["overrides"].update(
        layer_pattern="MMMF", num_layers=8, num_heads=20, num_kv_heads=4, head_dim=128,
        d_ff=6912)
    attn = {"n_heads": 20, "n_kv_heads": 4, "head_dim": 128, "d_ff": 6912,
            "rope_theta": 10000.0}
    s = hybrid["shapes"]
    del s["layer_kind"], s["layers"]
    s.update(attn, groups=[{"kind": "M", "count": 3}, {"kind": "F", "count": 1}] * 2)
    (root / "bench" / "configs" / "hybrid-mf.json").write_text(json.dumps(hybrid))
    (root / "bench" / "limits" / "hybrid-mf-short-64.json").write_text(
        (BENCH / "limits" / "mamba2-silo-2k.json").read_text())
    b["configs"].append({"name": "hybrid-mf", "source": "test",
                         "file": "bench/configs/hybrid-mf.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "hybrid-mf-short-64", "config": "hybrid-mf",
                           "traffic": "short-64", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    _, config, traffic3, _ = harness.resolve("hybrid-mf-short-64", root=root)
    cfg = harness.program_model(config)
    assert cfg.pattern_for_layers() == "MMMFMMMF"
    import flops
    seq = traffic3["seq_len"]
    one_group = {k: v for k, v in config["shapes"].items() if k != "groups"}
    alone = lambda kind, n: flops.forward_per_sequence(  # noqa: E731
        dict(one_group, layer_kind=kind, layers=n), seq)
    head = 2 * s["d_model"] * s["vocab"] * seq
    assert flops.forward_per_sequence(config["shapes"], seq) == (
        alone("M", 6) + alone("F", 2) - head)
    assert flops.required_per_round(config["shapes"], traffic3) > 0
    # a group that disagrees with the program's stack is refused
    config["shapes"]["groups"] = [{"kind": "M", "count": 6}, {"kind": "F", "count": 2}]
    with pytest.raises(ValueError, match="layer groups"):
        harness.program_model(config)


def test_code_names_no_cell(benchmark):
    names = ({w["name"] for w in benchmark["workloads"]}
             | {w["traffic"] for w in benchmark["workloads"]}
             | {c["name"] for c in benchmark["configs"]})
    for path in list(BENCH.glob("*.py")) + list(BENCH.glob("metrics/*.py")):
        text = path.read_text()
        assert not [n for n in names if n in text], path


def test_nested_overrides_replace_fields():
    """A dict override replaces the fields it names of the arch's nested
    config, or makes one where there is none; MoE layers then no longer
    match kinds that hold none."""
    config = json.loads((BENCH / "configs" / "mamba2-2.7b.json").read_text())
    config["program"]["overrides"]["ssm"] = {"chunk_size": 128}
    cfg = harness.program_model(config)
    assert (cfg.ssm.chunk_size, cfg.ssm.d_state, cfg.ssm.head_dim) == (128, 128, 64)
    config["program"]["overrides"]["moe"] = {"num_experts": 9, "top_k": 2,
                                             "expert_d_ff": 768}
    with pytest.raises(ValueError, match="layer groups"):
        harness.program_model(config)


def test_code_names_no_layer_kind():
    """The harness, the reference and the FLOP count find each kind by the
    name a configuration's groups give: none names a kind itself."""
    kinds = [p.stem for p in (BENCH / "kinds").glob("*.py")]
    assert {"Y", "M", "F"} <= set(kinds)
    for path in (BENCH / "harness.py", BENCH / "reference" / "lm.py", BENCH / "flops.py"):
        text = path.read_text()
        named = [k for k in kinds if re.search(rf"""(["']){re.escape(k)}\1""", text)]
        assert not named, (path, named)


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
    spans = [m["name"] for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]
             if m["source"] in ("device_trace", "program_span")
             and m["name"] not in ("idle_share", "chunk_gap_ms")]
    assert len(spans) == 10
    assert all(read(name) is None for name in spans)
    assert read("peak_hbm_gib") == 3.0
    import flops
    want = 100 * 2 * flops.required_per_round(ctx.shapes, ctx.traffic) / (10.0 * 197e12)
    assert read("mfu") == pytest.approx(want)
    ctx.device_kind = "cpu"
    with pytest.raises(KeyError):
        read("mfu")
