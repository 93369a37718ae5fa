"""Tiny cells for the CPU tests: the benchmark's configurations and
traffic mixes at sizes a test run holds, float32 throughout."""
import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

# every size a configuration gives is cut to this one
SHAPES = {"layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
          "d_ff": 96, "vocab": 101, "window": 8, "ssm_d_state": 8, "ssm_head_dim": 16}


def config(name: str) -> dict:
    """A benchmark configuration file cut to a tiny, float32 model."""
    return cut(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def cut(c: dict) -> dict:
    """Configuration ``c`` cut to a tiny, float32 model: each size of
    ``SHAPES`` that its ``shapes`` give, and the program overridden to
    match, its layer pattern spelled out from the groups."""
    c = copy.deepcopy(c)
    s = c["shapes"]
    s.update({k: v for k, v in SHAPES.items() if k in s})
    pattern = "".join(harness.layer_kind(g["kind"]).LETTER * g["count"]
                      for g in harness.layer_groups(s))
    over = {"num_layers": len(pattern), "layer_pattern": pattern,
            "d_model": s["d_model"], "vocab_size": s["vocab"],
            "param_dtype": "float32", "compute_dtype": "float32"}
    if "ssm_d_state" in s:
        over["ssm"] = {"d_state": s["ssm_d_state"], "head_dim": s["ssm_head_dim"],
                       "expand": 2, "conv_kernel": 4, "chunk_size": 8}
    if "n_heads" in s:
        over.update(num_heads=s["n_heads"], num_kv_heads=s["n_kv_heads"],
                    head_dim=s["head_dim"], d_ff=s["d_ff"])
    if "window" in s:
        over["sliding_window"] = s["window"]
    c["program"]["overrides"] = over
    return c


def mixed(groups, targets: str) -> dict:
    """A tiny float32 configuration of the program's mamba2 arch with the
    given layer ``groups`` (kinds M and F) and LoRA ``targets``."""
    c = json.loads((BENCH / "configs" / "mamba2-2.7b.json").read_text())
    c["name"] = "tiny-mixed"
    c["lora_targets"] = targets
    sizes = {k: v for k, v in SHAPES.items() if k not in ("layers", "window")}
    c["shapes"] = {"groups": groups, **sizes, "rope_theta": 10000.0, "ssm_expand": 2,
                   "ssm_conv": 4, "lora_targets": targets.split(",")}
    return cut(c)


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
