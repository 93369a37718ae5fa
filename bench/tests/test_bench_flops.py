"""The required-FLOP count and the peaks table."""
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import flops  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402
import weights  # noqa: E402

HAND = {"layer_kind": "Y", "layers": 1, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 2, "d_ff": 6, "vocab": 5, "window": 2, "ssm_d_state": 2,
        "ssm_head_dim": 2, "ssm_expand": 2, "ssm_conv": 4, "lora_targets": ["wq"]}


def test_hand_count():
    # per token: projections 32+16+16+32, MLP 3*48, w_in 4x24 = 192,
    # w_out 8x4 = 64 -> 496; SSD 4*8*2 = 64; conv 2*4*12 = 96 -> 656.
    # 3 tokens: 1968 + head 2*4*5*3 = 120 + attention 16 * (1+2+2) = 80
    # -> forward 2168; LoRA on wq: 4*1*(4+4)*3 = 96
    traffic = {"seq_len": 3, "local_batch": 1, "lora_rank": 1,
               "num_sampled": 1, "local_steps": 1}
    assert flops.forward_per_sequence(HAND, 3) == 2168
    assert flops.required_per_step(HAND, traffic) == 2 * 2168 + 96
    traffic.update(num_sampled=2, local_steps=5, local_batch=3)
    assert flops.required_per_round(HAND, traffic) == 10 * 3 * (2 * 2168 + 96)


def test_hand_count_full_attention():
    # F layer, per token: projections 32+16+16+32, MLP 3*48 -> 240;
    # 3 tokens: 720 + head 2*4*5*3 = 120 + attention 16 * (1+2+3) = 96
    # -> forward 936; LoRA on wq and w_down: 4*1*(4+4 + 6+4)*3 = 216
    s = dict(HAND, layer_kind="F")
    traffic = {"seq_len": 3, "local_batch": 1, "lora_rank": 1,
               "num_sampled": 1, "local_steps": 1}
    s["lora_targets"] = ["wq", "w_down"]
    assert flops.forward_per_sequence(s, 3) == 936
    assert flops.required_per_step(s, traffic) == 2 * 936 + 216


def test_groups_sum_their_kinds():
    """Two groups count as their kinds' layers each, with one LM head."""
    s = dict(HAND, groups=[{"kind": "Y", "count": 2}, {"kind": "F", "count": 1},
                           {"kind": "Y", "count": 1}])
    del s["layer_kind"], s["layers"]
    head = 2 * 4 * 5 * 3
    y = flops.forward_per_sequence(dict(HAND, layers=3), 3)
    f = flops.forward_per_sequence(dict(HAND, layer_kind="F"), 3)
    assert flops.forward_per_sequence(s, 3) == y + f - head
    assert flops.lora_grad_per_sequence(s, 3, 1) == 4 * 96


# the counts of the configurations as committed, fixed so that ``mfu``
# moves only with the window
PINNED = {"hymba-1.5b": 279119617064960, "mamba2-2.7b": 242237497671680}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_required_per_round_is_pinned(name):
    shapes = harness.load_json(BENCH / "configs" / f"{name}.json")["shapes"]
    traffic = harness.load_json(BENCH / "traffic" / "silo-2k.json")
    assert flops.required_per_round(shapes, traffic) == PINNED[name]


@pytest.mark.parametrize("name", ["hymba-1.5b", "mamba2-2.7b"])
def test_required_within_compiled_step(name):
    """The program computes more than is required (dense dW, remat), so
    the compiled local step's FLOPs, scans unrolled so that XLA counts
    every iteration, bound the required count from above."""
    from repro.core.controller import make_grad_fn
    from repro.core.update_space import get_update_space
    from repro.models import model as M
    from repro.util import set_unroll

    config = tiny.config(name)
    traffic = tiny.traffic("silo-2k")
    cfg = harness.program_model(config)
    spec = harness.round_spec(config, traffic)
    space = get_update_space("lora")
    base = weights.make_base(harness.base_shapes(cfg), seed=1)
    deltas = space.init_deltas(spec, base, jax.random.key(1))
    batch = {"tokens": jnp.zeros((traffic["local_batch"], traffic["seq_len"]), jnp.int32)}
    batch["labels"] = batch["tokens"]

    def step(base, y, batch):
        g, _ = make_grad_fn(partial(M.loss_fn, cfg), space=space, spec=spec,
                            base_params=base)(y, batch)
        return jax.tree.map(lambda a, b: a - spec.eta_l * b, y, g)

    set_unroll(True)
    try:
        cost = jax.jit(step).lower(base, deltas, batch).compile().cost_analysis()
    finally:
        set_unroll(False)
    cost = cost[0] if isinstance(cost, list) else cost
    required = flops.required_per_step(config["shapes"], traffic)
    assert 0 < required <= cost["flops"], (required, cost["flops"])


def test_peaks_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v4")
