"""Required FLOPs of one federated LoRA round, from configuration shapes.

Required means what the job needs, not what the program happens to
compute. Per token of every local step, forward:

  matmuls     2 * in * out for each projection, the MLP and the tied LM
              head; the embedding gather is a lookup and counts nothing;
  attention   4 * n_heads * head_dim for each key in the band the token
              attends (min(position + 1, window) keys: QK^T and PV);
  SSD         4 * d_inner * d_state (state update and readout of the
              linear recurrence) plus 2 * conv * conv_channels.

Each kind of layer (``bench/kinds/<kind>.py``) lists its weight matmuls
and its other forward FLOPs, built from the helpers here; the count sums
them over the configuration's layer groups (``harness.layer_groups``).

The backward pass adds the same again for the activation gradient, plus
the LoRA factor gradients, 4 * r * (in + out) per token and target. The
dense gradient of the frozen weights (which the program forms and then
projects) and remat recomputation are not required and not counted.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

import harness

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A per-chip peak for ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return float(table[device_kind][key])


def attention_matmuls(s: Dict) -> List[Tuple[str, int, int]]:
    """(leaf name, in, out) of a GQA attention block's projections."""
    d, nh, nkv, hd = s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"]
    return [("wq", d, nh * hd), ("wk", d, nkv * hd), ("wv", d, nkv * hd),
            ("wo", nh * hd, d)]


def mlp_matmuls(s: Dict) -> List[Tuple[str, int, int]]:
    """(leaf name, in, out) of a SwiGLU MLP."""
    d, ff = s["d_model"], s["d_ff"]
    return [("w_gate", d, ff), ("w_up", d, ff), ("w_down", ff, d)]


def ssd_matmuls(s: Dict) -> List[Tuple[str, int, int]]:
    """(leaf name, in, out) of a Mamba-2 block's projections."""
    d = s["d_model"]
    di = s["ssm_expand"] * d
    nheads = di // s["ssm_head_dim"]
    return [("w_in", d, 2 * di + 2 * s["ssm_d_state"] + nheads), ("w_out", di, d)]


def ssd_per_token(s: Dict) -> int:
    """A Mamba-2 block's forward FLOPs per token besides its projections:
    the recurrence's state update and readout, and the causal conv."""
    di = s["ssm_expand"] * s["d_model"]
    return 4 * di * s["ssm_d_state"] + 2 * s["ssm_conv"] * (di + 2 * s["ssm_d_state"])


def attended_keys(seq: int, window: int) -> int:
    """Keys summed over the positions of one sequence under a causal
    sliding window."""
    return sum(min(t + 1, window) for t in range(seq))


def attention_per_sequence(s: Dict, seq: int, window: int) -> int:
    """Scores and their weighted sum of one attention block on one
    sequence, each position attending its last ``window`` keys."""
    return 4 * s["n_heads"] * s["head_dim"] * attended_keys(seq, window)


def _kinds(s: Dict):
    return [(harness.layer_kind(g["kind"]), g["count"]) for g in harness.layer_groups(s)]


def forward_per_sequence(s: Dict, seq: int) -> float:
    """Forward FLOPs of one sequence of ``seq`` tokens."""
    total = 2 * s["d_model"] * s["vocab"] * seq
    for kind, count in _kinds(s):
        per_token = sum(2 * i * o for _, i, o in kind.matmuls(s))
        total += count * (per_token * seq + kind.other_flops(s, seq))
    return float(total)


def lora_grad_per_sequence(s: Dict, seq: int, rank: int) -> float:
    """FLOPs of the LoRA factor gradients for one sequence."""
    targets = set(s["lora_targets"])
    total = 0
    for kind, count in _kinds(s):
        per_token = sum(4 * rank * (i + o) for name, i, o in kind.matmuls(s)
                        if name in targets)
        total += count * per_token * seq
    return float(total)


def required_per_step(s: Dict, traffic: Dict) -> float:
    """Required FLOPs of one local step (one client batch)."""
    seq, b = traffic["seq_len"], traffic["local_batch"]
    return b * (2 * forward_per_sequence(s, seq)
                + lora_grad_per_sequence(s, seq, traffic["lora_rank"]))


def required_per_round(s: Dict, traffic: Dict) -> float:
    """Required FLOPs of one round: S clients times K local steps."""
    return (traffic["num_sampled"] * traffic["local_steps"]
            * required_per_step(s, traffic))
