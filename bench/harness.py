"""One benchmark cell: build it from its files, set it up, measure it, and
check what its timed path produced against the plain reference.

Everything a cell needs is found by name: the workload in
``BENCHMARK.json``, its configuration file, ``bench/traffic/<mix>.json``
(read by the generator it names), ``bench/limits/<workload>.json`` and a
reader ``bench/metrics/<metric>.py`` per per-layer metric. A new cell is
new files and entries, never an edit here.

The timed object is the program's ``FederatedTrainer`` on its scanned
engine and dense store, built as users build it. Set-up gives it the
benchmark's weights and a resumed state (adapter, c and every client's
c_i), drives its first round through the window's own call
(``trainer.run(1)``), and copies what it produced to the host; the window
then keeps calling the same object. Only after the window, with the
program freed, does the reference follow that first round.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from functools import partial
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
ROUND = "bench.round"

for _p in (REPO / "src", BENCH):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import weights  # noqa: E402


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str, benchmark: Optional[Dict] = None, root: Path = REPO):
    """``(workload, config, traffic, limits)`` of the named cell, its files
    under ``root`` (the checkout)."""
    b = benchmark or load_json(root / "BENCHMARK.json")
    bench = root / BENCH.name
    matches = [w for w in b["workloads"] if w["name"] == name]
    if not matches:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wl = matches[0]
    (entry,) = [c for c in b["configs"] if c["name"] == wl["config"]]
    return (wl, load_json(root / entry["file"]),
            load_json(bench / "traffic" / f"{wl['traffic']}.json"),
            load_json(bench / "limits" / f"{name}.json"))


def per_layer_metrics(name: str, benchmark: Dict) -> List[Dict]:
    """The per-layer metrics that the named cell reports."""
    wl = next(w for w in benchmark["workloads"] if w["name"] == name)
    reported = {m["name"] for m in benchmark["end_to_end"]
                if name in m.get("workloads", [name])}
    return [m for m in benchmark["per_layer"]
            if name in m.get("workloads", [wl["name"]])
            and m["moves"] in reported]


# --------------------------------------------------------------- program


def program_model(config: Dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the file's ``shapes``."""
    from repro.configs import SSMConfig, get_config

    cfg = get_config(config["program"]["arch"])
    over = dict(config["program"].get("overrides", {}))
    if isinstance(over.get("ssm"), dict):
        over["ssm"] = SSMConfig(**over["ssm"])
    cfg = dataclasses.replace(cfg, **over)
    s = config["shapes"]
    want = {"layers": cfg.num_layers, "d_model": cfg.d_model,
            "vocab": cfg.vocab_size, "ssm_d_state": cfg.ssm.d_state,
            "ssm_head_dim": cfg.ssm.head_dim, "ssm_expand": cfg.ssm.expand,
            "ssm_conv": cfg.ssm.conv_kernel}
    if s["layer_kind"] == "Y":
        want.update(n_heads=cfg.num_heads, n_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.head_dim, d_ff=cfg.d_ff,
                    window=cfg.sliding_window, rope_theta=cfg.rope_theta)
    bad = {k: (s.get(k), v) for k, v in want.items() if s.get(k) != v}
    if bad or cfg.pattern_for_layers() != s["layer_kind"] * s["layers"]:
        raise ValueError(f"configuration shapes disagree with the program: {bad}")
    return cfg


def round_spec(config: Dict, traffic: Dict):
    from repro.configs import FedRoundSpec

    return FedRoundSpec(
        algorithm=traffic["algorithm"], num_clients=traffic["num_clients"],
        num_sampled=traffic["num_sampled"], local_steps=traffic["local_steps"],
        local_batch=traffic["local_batch"], eta_l=traffic["eta_l"],
        strategy=traffic["strategy"], local_solver=traffic["local_solver"],
        update_space="lora", lora_rank=traffic["lora_rank"],
        lora_alpha=traffic["lora_alpha"], update_targets=config["lora_targets"])


def dataset(config: Dict, traffic: Dict, seed: int):
    gen = load_module(BENCH / "traffic" / f"{traffic['generator']}.py")
    return gen.SyntheticLMFederated(
        traffic["num_clients"], config["shapes"]["vocab"], traffic["seq_len"],
        heterogeneity=traffic["heterogeneity"], seed=seed)


def cohort(traffic: Dict, seed: int, t: int):
    """Round t's client ids under the scanned engine's documented stream:
    the first S of a permutation of the N clients drawn from
    ``fold_in(key(seed), t)``."""
    import jax

    perm = jax.random.permutation(jax.random.fold_in(jax.random.key(seed), t),
                                  traffic["num_clients"])
    return perm[:traffic["num_sampled"]]


def batches_fn(data, traffic: Dict, seed: int):
    """``(ids, t) -> batches`` of round t, leaves (S, K, b, T): the
    generator's device batches under ``fold_in(key(seed + 1), t)``,
    jitted as the program's scan runs them (eagerly, the categorical draw
    would hold a (S, K, b, T, vocab) array)."""
    import jax

    fn = jax.jit(data.device_batch_fn(traffic["local_steps"], traffic["local_batch"]))
    dev = data.device_data()
    return lambda ids, t: fn(dev, ids, jax.random.fold_in(jax.random.key(seed + 1), t))


def base_shapes(cfg):
    import jax
    from repro.models import model as M

    return jax.eval_shape(partial(M.init_params, cfg), jax.random.key(0))


def resumed_state(delta_shapes, traffic: Dict, seed: int):
    """The benchmark's resumed adapter ``x`` and SCAFFOLD state ``c``,
    ``store`` (the clients' c_i), made on the device from the seed."""
    x = weights.make_adapter(delta_shapes, seed, traffic["adapter_b_std"])
    c, store = weights.make_state(delta_shapes, traffic["num_clients"], seed,
                                  traffic["ci_std"])
    return {"x": x, "c": c, "store": store}


def build(config: Dict, traffic: Dict, seed: int):
    """The trainer of the cell, holding the benchmark's base and resumed
    state."""
    import jax
    from repro.core import FederatedTrainer
    from repro.models import model as M

    cfg = program_model(config)
    spec = round_spec(config, traffic)
    shapes = base_shapes(cfg)
    base = weights.make_base(shapes, seed)
    trainer = FederatedTrainer(partial(M.loss_fn, cfg), lambda key: base, spec,
                               dataset(config, traffic, seed), seed=seed,
                               scan_rounds=traffic["rounds_per_chunk"])
    assert trainer.scan_active, trainer.scan_fallback_reason
    delta_shapes = jax.eval_shape(lambda: trainer.x)
    state = resumed_state(delta_shapes, traffic, seed)
    trainer.x, trainer.c = state["x"], state["c"]
    trainer.device_store = state["store"]
    jax.block_until_ready((trainer.base_params, trainer.server, trainer.device_store))
    return SimpleNamespace(trainer=trainer, cfg=cfg, spec=spec,
                           base_shapes=shapes, delta_shapes=delta_shapes)


def one_round(trainer):
    """The window's call: one round, ended on the device."""
    import jax

    with jax.profiler.TraceAnnotation(ROUND):
        trainer.run(1)
        jax.block_until_ready(trainer.x)


def first_round(cell, traffic: Dict, seed: int) -> Dict:
    """Drive the trainer through its first round with the window's call;
    host copies of what it produced: the round's mean local loss, the new
    ``x`` and ``c``, and the new c_i of its cohort."""
    import jax

    tr = cell.trainer
    one_round(tr)
    ids = cohort(traffic, seed, 0)
    rows = jax.tree.map(lambda a: a[ids], tr.device_store)
    snap = jax.device_get({"x": tr.x, "c": tr.c, "rows": rows})
    return {"loss": float(tr.history[-1]["loss"]), "x": snap["x"], "c": snap["c"],
            "c_i": [jax.tree.map(lambda a: a[j], snap["rows"])
                    for j in range(len(ids))]}


def window(trainer, seconds: float) -> Dict:
    """Whole rounds until ``seconds`` have passed: their count, the time
    they took, and how many had a non-finite loss."""
    rounds, failed = 0, 0
    t0 = time.perf_counter()
    while True:
        one_round(trainer)
        rounds += 1
        failed += not math.isfinite(trainer.history[-1]["loss"])
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return {"rounds": rounds, "seconds": elapsed, "failed": failed}


def time_calls(fn, *args) -> float:
    """Mean seconds of a call of ``fn`` ending on the device, over calls
    adding up to at least 0.25 s, at most 50 (after one warm call)."""
    import jax

    jax.block_until_ready(fn(*args))
    total, n = 0.0, 0
    while total < 0.25 and n < 50:
        t = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        total += time.perf_counter() - t
        n += 1
        del out
    return total / n


# ------------------------------------------------------------- reference


def reference_round(config: Dict, traffic: Dict, seed: int, cell_shapes,
                    quant=None, fault: str = ""):
    """The plain reference (or, with ``quant``, the control; with
    ``fault``, "drop_half" or "zero_ci", a planted fault) over the cell's
    first round, from the benchmark's own weights, state and feed:
    ``(state before, state after)`` on the host."""
    import jax

    from reference import lm, scaffold

    s = config["shapes"]
    base = weights.make_base(cell_shapes.base_shapes, seed)
    st = resumed_state(cell_shapes.delta_shapes, traffic, seed)
    scale = traffic["lora_alpha"] / traffic["lora_rank"]
    data = dataset(config, traffic, seed)
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(
            lambda d, b, batch: lm.loss(s, b, d, batch, scale, quant)))
        (out,) = scaffold.run_rounds(
            lambda y, batch: vg(y, base, batch), st["x"], st["c"], st["store"], 1,
            cohort=lambda t: cohort(traffic, seed, t),
            batches=batches_fn(data, traffic, seed),
            num_clients=traffic["num_clients"], local_steps=traffic["local_steps"],
            eta_l=traffic["eta_l"], drop_half=fault == "drop_half",
            zero_ci=fault == "zero_ci")
    host = jax.device_get({"out": out, "x": st["x"], "c": st["c"]})
    del base, out, st
    gc.collect()
    return {"x": host["x"], "c": host["c"]}, host["out"]
