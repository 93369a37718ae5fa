"""Tiny cells for the CPU tests: the benchmark's configurations and
traffic mixes at sizes a test run holds, float32 throughout."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

SHAPES = {
    "Y": {"layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
          "head_dim": 16, "d_ff": 96, "vocab": 101, "window": 8,
          "ssm_d_state": 8, "ssm_head_dim": 16},
    "M": {"layers": 2, "d_model": 64, "vocab": 101, "ssm_d_state": 8,
          "ssm_head_dim": 16},
}


def config(name: str) -> dict:
    """A benchmark configuration file cut to a tiny, float32 model."""
    c = copy.deepcopy(json.loads((BENCH / "configs" / f"{name}.json").read_text()))
    s = c["shapes"]
    s.update(SHAPES[s["layer_kind"]])
    over = {"num_layers": s["layers"], "d_model": s["d_model"],
            "vocab_size": s["vocab"], "param_dtype": "float32",
            "compute_dtype": "float32",
            "ssm": {"d_state": s["ssm_d_state"], "head_dim": s["ssm_head_dim"],
                    "expand": 2, "conv_kernel": 4, "chunk_size": 8}}
    if s["layer_kind"] == "Y":
        over.update(num_heads=s["n_heads"], num_kv_heads=s["n_kv_heads"],
                    head_dim=s["head_dim"], d_ff=s["d_ff"],
                    sliding_window=s["window"])
    c["program"]["overrides"] = over
    return c


def traffic(name: str, **kw) -> dict:
    """A traffic mix cut to a few clients, steps and tokens."""
    t = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    t.update(num_clients=4, num_sampled=2, local_steps=2,
             local_batch=min(t["local_batch"], 2),
             seq_len=16 if t["seq_len"] > 100 else 6, lora_rank=4, lora_alpha=8.0,
             # c_i of the size of the tiny models' larger gradients
             ci_std={"A": 1e-3, "B": 5e-3})
    t.update(kw)
    return t
