"""Required FLOPs of one federated LoRA round, from configuration shapes.

Required means what the job needs, not what the program happens to
compute. Per token of every local step, forward:

  matmuls     2 * in * out for each projection, the MLP and the tied LM
              head; the embedding gather is a lookup and counts nothing;
  attention   4 * n_heads * head_dim for each key in the band the token
              attends (min(position + 1, window) keys: QK^T and PV);
  SSD         4 * d_inner * d_state (state update and readout of the
              linear recurrence) plus 2 * conv * conv_channels.

The backward pass adds the same again for the activation gradient, plus
the LoRA factor gradients, 4 * r * (in + out) per token and target. The
dense gradient of the frozen weights (which the program forms and then
projects) and remat recomputation are not required and not counted.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A per-chip peak for ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][key])


def layer_matmuls(s: Dict) -> List[Tuple[str, int, int]]:
    """(leaf name, in, out) of one layer's weight matmuls."""
    d = s["d_model"]
    out = []
    if s["layer_kind"] == "Y":
        nh, nkv, hd, ff = s["n_heads"], s["n_kv_heads"], s["head_dim"], s["d_ff"]
        out += [("wq", d, nh * hd), ("wk", d, nkv * hd), ("wv", d, nkv * hd),
                ("wo", nh * hd, d), ("w_gate", d, ff), ("w_up", d, ff),
                ("w_down", ff, d)]
    di = s["ssm_expand"] * d
    nheads = di // s["ssm_head_dim"]
    out += [("w_in", d, 2 * di + 2 * s["ssm_d_state"] + nheads), ("w_out", di, d)]
    return out


def attended_keys(seq: int, window: int) -> int:
    """Keys summed over the positions of one sequence under a causal
    sliding window."""
    return sum(min(t + 1, window) for t in range(seq))


def forward_per_sequence(s: Dict, seq: int) -> float:
    """Forward FLOPs of one sequence of ``seq`` tokens."""
    d = s["d_model"]
    per_token = sum(2 * i * o for _, i, o in layer_matmuls(s))
    di = s["ssm_expand"] * d
    per_token += 4 * di * s["ssm_d_state"]
    per_token += 2 * s["ssm_conv"] * (di + 2 * s["ssm_d_state"])
    total = s["layers"] * per_token * seq + 2 * d * s["vocab"] * seq
    if s["layer_kind"] == "Y":
        total += (s["layers"] * 4 * s["n_heads"] * s["head_dim"]
                  * attended_keys(seq, s["window"]))
    return float(total)


def lora_grad_per_sequence(s: Dict, seq: int, rank: int) -> float:
    """FLOPs of the LoRA factor gradients for one sequence."""
    targets = set(s["lora_targets"])
    per_token = sum(4 * rank * (i + o) for name, i, o in layer_matmuls(s)
                    if name in targets)
    return float(s["layers"] * per_token * seq)


def required_per_step(s: Dict, traffic: Dict) -> float:
    """Required FLOPs of one local step (one client batch)."""
    seq, b = traffic["seq_len"], traffic["local_batch"]
    return b * (2 * forward_per_sequence(s, seq)
                + lora_grad_per_sequence(s, seq, traffic["lora_rank"]))


def required_per_round(s: Dict, traffic: Dict) -> float:
    """Required FLOPs of one round: S clients times K local steps."""
    return (traffic["num_sampled"] * traffic["local_steps"]
            * required_per_step(s, traffic))
