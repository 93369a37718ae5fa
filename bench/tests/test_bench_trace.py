"""The trace reduction, on a small trace recorded here on the CPU."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

HOST_ONLY = ("ThreadpoolListener", "ThunkExecutor", "SlinkyThreadPool")


def cpu_op_line(plane, line):
    """On the CPU the XLA ops run on the host's XLA threads."""
    return plane == "/host:CPU" and line.startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen"))


def test_union_merges_and_clips():
    assert trace_reduce.union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10) == [(1, 3), (5, 10)]
    assert trace_reduce.union([(0, 1)], 2, 3) == []


def test_nested_ops_count_their_leaves_only():
    ops = [("%while.1 = (f32[2]) while(...)", 0, 100),
           ("%fusion.2 = bf16[8,128]{1,0} fusion(...)", 10, 40),
           ("%fusion.2 = bf16[8,128]{1,0} fusion(...)", 50, 80),
           ("%copy.3 = f32[4]{0} copy(...)", 120, 130)]
    leaves = trace_reduce.self_times(ops)
    assert [(n, own) for n, _, _, own in leaves] == [
        (ops[1][0], 30), (ops[2][0], 30), (ops[3][0], 10)]
    assert trace_reduce.op_label(ops[1][0]) == "fusion.2 bf16[8,128]"
    host = [(trace_reduce.ROUND, 0, 60), (trace_reduce.ROUND, 60, 130)]
    out = trace_reduce.summarize({"d": ops}, host)
    assert out["busy_s"] == pytest.approx(70e-9)
    assert out["idle_share"] == pytest.approx(1 - 70 / 130)
    # round 1's last op ends at 80, round 2's first starts at 120
    assert out["chunk_gap_ms"] == pytest.approx(40e-6)
    assert out["top_ops"][0] == ["fusion.2 bf16[8,128]", pytest.approx(60e-9)]


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as bench/run.py traces
    with jax.profiler.trace(str(out), profiler_options=opts):
        for _ in range(4):
            with jax.profiler.TraceAnnotation(trace_reduce.ROUND):
                f(x).block_until_ready()
                time.sleep(0.05)
    devices, host = trace_reduce.load(str(out), cpu_op_line)
    devices = {k: [e for e in v if not e[0].startswith(HOST_ONLY)]
               for k, v in devices.items()}
    return trace_reduce.summarize(devices, host)


def test_window_busy_and_idle(summary):
    assert summary["rounds"] == 4
    assert 0.2 <= summary["window_s"] < 2.0
    assert 0 < summary["busy_s"] < summary["window_s"]
    assert summary["idle_share"] == pytest.approx(
        1 - summary["busy_s"] / summary["window_s"])
    assert summary["idle_share"] > 0.5  # the rounds sleep 50 ms each


def test_chunk_gaps_and_breakdown(summary):
    # the device is idle through each round's 50 ms sleep
    assert summary["chunk_gap_ms"] >= 40
    names = [n for n, _ in summary["top_ops"]]
    assert any("dot" in n for n in names), names
    assert all(v > 0 for _, v in summary["top_ops"])
    longest = summary["idle_gaps"][0]
    assert longest[1] >= 0.04
    # the gap falls in the sleep, inside the round's own annotation
    assert longest[0] == trace_reduce.ROUND
    assert len(summary["idle_gaps"]) <= 10 and len(summary["top_ops"]) <= 10
