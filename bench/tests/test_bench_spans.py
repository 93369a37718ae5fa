"""The span reduction (``bench/spans.py``), on synthetic events and on a
small trace recorded here on the CPU."""
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import spans  # noqa: E402
import trace_reduce  # noqa: E402
from repro.core import spans as names  # noqa: E402

STEP = "jit(chunk_fn)/while/body/scaffold.client/while/body/scaffold.local_step"


@pytest.mark.parametrize("op_name,chain", [
    (f"{STEP}/jvp(model.blocks)/dot_general",
     (names.CLIENT, names.LOCAL_STEP, names.MODEL_BLOCKS)),
    (f"{STEP}/transpose(jvp(model.blocks))/while/body/checkpoint/dot_general",
     (names.CLIENT, names.LOCAL_STEP, names.MODEL_BLOCKS)),
    # a remat replay repeats the path under the transpose
    (f"{STEP}/transpose(jvp(model.head))/scaffold.local_step/jvp(model.head)/mul",
     (names.CLIENT, names.LOCAL_STEP, names.MODEL_HEAD)),
    (f"{STEP}/update_space.grad_project/dot_general",
     (names.CLIENT, names.LOCAL_STEP, names.GRAD_PROJECT)),
    ("jit(chunk_fn)/while/body/scaffold.aggregate/add", (names.AGGREGATE,)),
    ("jit(chunk_fn)/while/body/dynamic_slice", ()),
    ("", ()),
])
def test_scope_chain_unwraps_transforms(op_name, chain):
    assert spans.scope_chain(op_name, names.DEVICE_SCOPES) == chain


def _synthetic():
    """Two one-round chunks of a window of 200 ns; one device."""
    step = lambda leaf: f"{STEP}/{leaf}"  # noqa: E731
    ops = [
        ("jit(chunk_fn)/while", 10, 190),  # a parent: not a leaf
        (f"jit(chunk_fn)/while/body/{names.SAMPLE}/iota", 12, 14),
        (step(f"{names.APPLY}/add"), 20, 30),
        (step("jvp(model.blocks)/dot"), 30, 50),
        (step("transpose(jvp(model.blocks))/dot"), 52, 70),  # 2 ns idle in the step
        (step(f"{names.GRAD_PROJECT}/dot"), 70, 75),
        (f"jit(chunk_fn)/while/body/{names.SERVER}/sub", 75, 80),
        ("", 80, 85),  # unscoped
        (step(f"{names.APPLY}/add"), 120, 125),
        (step("jvp(model.blocks)/dot"), 125, 160),
        (f"jit(chunk_fn)/while/body/{names.GATHER}/gather", 170, 171),
    ]
    host = [(trace_reduce.ROUND, 0, 100), (trace_reduce.ROUND, 100, 200),
            (names.CHUNK, 2, 98), (names.DISPATCH, 3, 8), (names.FETCH, 9, 96),
            (names.CHUNK, 102, 198), (names.DISPATCH, 103, 110),
            (names.FETCH, 111, 180)]
    return {"d": ops}, host


def test_scope_buckets_sum_to_busy():
    devices, host = _synthetic()
    red = spans.reduce(devices, host)
    leaves = trace_reduce.self_times(devices["d"])
    union = trace_reduce.union([(s, e) for _, s, e, _ in leaves], 0, 200)
    assert red["busy_s"] == pytest.approx(sum(e - s for s, e in union) * 1e-9)
    assert sum(red["scopes"].values()) == pytest.approx(red["busy_s"])
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    assert red["scopes"]["unscoped"] == pytest.approx(5e-9)
    assert red["scopes"][names.SERVER] == pytest.approx(5e-9)


def test_backward_counts_with_its_scope():
    red = spans.reduce(*_synthetic())
    assert red["scopes"][names.MODEL_BLOCKS] == pytest.approx((20 + 18 + 35) * 1e-9)
    assert red["under"][names.LOCAL_STEP] == pytest.approx((10 + 20 + 18 + 5 + 5 + 35) * 1e-9)
    assert red["under"][names.CLIENT] == red["under"][names.LOCAL_STEP]


def test_idle_by_neighbours_and_by_host_span():
    red = spans.reduce(*_synthetic())
    between = red["idle_between"]
    assert between[f"{names.MODEL_BLOCKS} | {names.MODEL_BLOCKS}"] == pytest.approx(2e-9)
    assert between[f"window | {names.SAMPLE}"] == pytest.approx(12e-9)
    assert between[f"{names.GATHER} | window"] == pytest.approx(29e-9)
    assert red["idle_under"] == {n: pytest.approx(2e-9) for n in
                                 (names.CLIENT, names.LOCAL_STEP, names.MODEL_BLOCKS)}
    host = red["idle_in_host_span"]
    assert sum(host.values()) == pytest.approx(red["idle_s"])
    # e.g. the gap 85..120: fetch to 96, the chunk's own time to 98, none
    # to 102, the next chunk's own time to 103, its dispatch to 110, its
    # own time to 111, its fetch to 120
    assert host[names.FETCH] == pytest.approx((3 + 6 + 2 + 11 + 9 + 10 + 9) * 1e-9)
    assert host[names.DISPATCH] == pytest.approx((5 + 7) * 1e-9)
    assert host["none"] == pytest.approx((2 + 4 + 2) * 1e-9)
    assert host[names.CHUNK] == pytest.approx((1 + 1 + 2 + 1 + 1 + 18) * 1e-9)


def test_chunks_and_per_step_normalisation():
    red = spans.reduce(*_synthetic())
    first, second = red["chunks"]
    assert first["dispatch_ms"] == pytest.approx(5e-6)
    assert first["self_ms"] == pytest.approx((96 - 5 - 87) * 1e-6)
    assert first["first_op_after_dispatch_ms"] == pytest.approx(9e-6)
    assert first["last_op_before_fetch_end_ms"] == pytest.approx(11e-6)
    assert second["fetch_after_last_op_ms"] == pytest.approx(9e-6)
    m = spans.layer_metrics(red, rounds=2, steps=4)
    assert m["local_step_dev_ms"] == pytest.approx(93e-6 / 4)
    assert m["step_idle_ms"] == pytest.approx(2e-6 / 4)
    assert m["merge_dev_ms"] == pytest.approx(15e-6 / 4)
    assert m["project_dev_ms"] == pytest.approx(5e-6 / 4)
    assert m["store_dev_ms"] == pytest.approx(1e-6 / 2)
    assert m["agg_dev_ms"] == pytest.approx(5e-6 / 2)
    assert m["data_dev_ms"] == pytest.approx(2e-6 / 2)
    assert m["dispatch_ms"] == pytest.approx(12e-6 / 2)
    assert m["fetch_ms"] == pytest.approx((11 + 9) * 1e-6 / 2)


def test_attention_only_where_its_scope_ran():
    devices, host = _synthetic()
    assert "attention_dev_ms" not in spans.layer_metrics(spans.reduce(devices, host), 2, 4)
    devices["d"].insert(-1, (f"{STEP}/jvp(model.blocks)/{names.ATTENTION}/dot", 160, 166))
    m = spans.layer_metrics(spans.reduce(devices, host), rounds=2, steps=4)
    assert m["attention_dev_ms"] == pytest.approx(6e-6 / 4)
    assert m["local_step_dev_ms"] == pytest.approx(99e-6 / 4)


def test_readers_read_the_window_reduction():
    """Each span metric's reader gives ``layer_metrics``' number of the
    traced window that ``bench/run.py`` hands it under ``trace["spans"]``."""
    import harness

    devices, host = _synthetic()
    devices["d"].insert(-1, (f"{STEP}/jvp(model.blocks)/{names.ATTENTION}/dot", 160, 166))
    red = spans.reduce(devices, host)
    ctx = SimpleNamespace(trace={"busy_s": red["busy_s"], "spans": red}, rounds=2,
                          traffic={"num_sampled": 2, "local_steps": 1})
    want = spans.layer_metrics(red, rounds=2, steps=4)
    assert len(want) == 10
    for name, value in want.items():
        assert harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx) == value
    ctx.trace = {"busy_s": red["busy_s"]}
    assert all(harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx) is None
               for name in want)


def test_run_as_a_script_finds_the_program_names():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    code = "import runpy; m = runpy.run_path('bench/spans.py'); print(m['names'].CHUNK)"
    out = subprocess.run([sys.executable, "-c", code], cwd=BENCH.parent, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == [names.CHUNK], out.stderr


def test_no_program_spans_reduce_to_nothing():
    devices, host = _synthetic()
    assert spans.reduce(devices, [ev for ev in host if ev[0] != names.CHUNK]) == {}
    assert spans.layer_metrics({}, rounds=2, steps=4) == {}


def cpu_op_line(plane, line):
    """On the CPU the XLA ops run on the host's XLA threads."""
    return plane == "/host:CPU" and line.startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen"))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace")

    def f(w, x):
        with jax.named_scope(names.MODEL_BLOCKS):
            h = jnp.tanh(x @ w)
        with jax.named_scope(names.MODEL_HEAD):
            return (h @ h.T).sum()

    jf = jax.jit(f)
    w = jnp.ones((384, 384))
    jf(w, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as bench/run.py traces
    with jax.profiler.trace(str(out), profiler_options=opts):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(trace_reduce.ROUND), \
                    jax.profiler.TraceAnnotation(names.CHUNK):
                with jax.profiler.TraceAnnotation(names.DISPATCH):
                    y = jf(w, w)
                with jax.profiler.TraceAnnotation(names.FETCH):
                    y.block_until_ready()
                time.sleep(0.02)
    devices, host = spans.load(str(out), cpu_op_line)
    return devices, host, spans.reduce(devices, host)


def test_recorded_trace_names_both_scopes(recorded):
    devices, _, red = recorded
    assert red["scopes"][names.MODEL_BLOCKS] > 0 and red["scopes"][names.MODEL_HEAD] > 0
    # the CPU's reduce-window fusions carry no op_name: they take their
    # fused computation's
    assert "unscoped" not in red["scopes"]
    assert sum(red["scopes"].values()) == pytest.approx(red["busy_s"])
    assert 0 < red["busy_s"] < red["window_s"]
    # the ops of the jitted function carry their scope paths
    assert any(op.startswith("jit(f)/model.blocks/") for v in devices.values()
               for op, _, _ in v)


def test_recorded_trace_shares_one_clock(recorded):
    _, _, red = recorded
    assert len(red["chunks"]) == 2
    for c in red["chunks"]:
        assert c["first_op_after_dispatch_ms"] >= 0
        assert c["last_op_before_fetch_end_ms"] >= 0
    # the device idles through each round's sleep, in the chunk's own time
    assert red["idle_in_host_span"][names.CHUNK] >= 0.03
