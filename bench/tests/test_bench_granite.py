"""granite-4.0-h-small in the benchmark, on the CPU: its cell resolves
against the program, its two kinds of layer (``M_moe``, ``F_moe``) match
the program's loss and LoRA gradients at a tiny size, their FLOPs match
hand counts, and a whole tiny run of the cell is correct."""
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import flops  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402
from test_bench_reference import _match_program  # noqa: E402

CELL = "granite-silo-2k"
CONFIG = "granite-4.0-h-small"
# required FLOPs of one round of the cell (about 14.14 TFLOP a local step)
PINNED = 282811684618240


def tiny_granite(groups=(("M_moe", 2), ("F_moe", 1), ("M_moe", 1)), targets=None):
    """The configuration cut by ``tiny.cut`` to the stack ``groups``, with
    8 experts of which 3 are held (experts 2-4), top-4 routing and a
    shared expert of 32; the multipliers as published, the attention
    scale 1/16 for heads of 16."""
    c = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    c["shapes"]["groups"] = [{"kind": k, "count": n} for k, n in groups]
    if targets is not None:
        c["lora_targets"] = ",".join("*." + t for t in targets)
        c["shapes"]["lora_targets"] = list(targets)
    c = tiny.cut(c)
    s = c["shapes"]
    s.update(n_experts=8, held_experts=3, first_held_expert=2, top_k=4, shared_d_ff=32,
             attention_multiplier=1 / 16)
    c["program"]["overrides"].update(
        attention_multiplier=1 / 16,
        moe={"num_experts": 8, "held_experts": 3, "first_held_expert": 2, "top_k": 4,
             "expert_d_ff": s["d_ff"], "shared_d_ff": 32, "router_aux_coef": 0.0})
    return c


def test_cell_resolves_and_matches_program_groups():
    from repro.models import model as M
    from repro.models.transformer import layer_groups

    benchmark = harness.load_json(harness.REPO / "BENCHMARK.json")
    wl, config, traffic, limits = harness.resolve(CELL, benchmark)
    assert (wl["chips"], wl["traffic"]) == (1, "silo-2k")
    cfg = harness.program_model(config)
    assert [(g.kind, g.uses_moe, g.count) for g in layer_groups(cfg)] == [
        ("M", True, 5), ("F", True, 1), ("M", True, 4)]
    assert (cfg.moe.num_experts, cfg.moe.held(), cfg.moe.top_k) == (72, 9, 10)
    assert cfg.rope_theta is None and cfg.moe.router_aux_coef == 0.0
    # 9 Mamba-2 layers, 1 attention layer, 10 MoE tails of 9 experts, the
    # tied embedding (model-configs section 4: 2.41 B, 4.83 GB in bf16)
    assert M.count_params_analytic(cfg) == 2_414_692_992
    assert {"dx", "dc", "ci"} <= set(limits)
    per_layer = harness.per_layer_metrics(CELL, benchmark)
    assert {"mfu", "idle_share", "peak_hbm_gib", "attention_dev_ms"} <= {
        m["name"] for m in per_layer}
    for m in per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_lora_targets_leave_routed_experts_frozen():
    """The path-qualified patterns adapt the shared expert and not the
    routed ones, whose leaves end in the same names."""
    from repro.core.update_space import get_update_space

    config = tiny_granite()
    cfg = harness.program_model(config)
    spec = harness.round_spec(config, tiny.traffic("silo-2k"))
    base = harness.base_shapes(cfg)
    paths = {p.split(".", 2)[2] for p, _ in get_update_space("lora").targets(spec, base)}
    assert paths == set(config["shapes"]["lora_targets"])
    assert base["layers"][0]["moe"]["w_gate"].shape[1] == 3  # (layers, held, in, out)


@pytest.mark.parametrize("targets", [None, ("mamba.w_in", "mamba.w_out")])
def test_loss_and_lora_grad_match_program(targets):
    """The program's loss and LoRA gradient against the reference's through
    M_moe and F_moe, stack M M | F | M; with the second targets the F
    group holds no factors."""
    grads = _match_program(tiny_granite(targets=targets))
    groups = {path.split(".")[1] for path in grads}
    assert groups == ({"0", "1", "2"} if targets is None else {"0", "2"}), sorted(grads)


# a tiny granite layer: d 4, 2 heads of 2 (1 kv head), SSD state 2 over
# heads of 2 (d_inner 8, 4 heads), 8 experts of width 3, 2 held, top-2,
# a shared expert of 6, vocabulary 5
HAND = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2, "vocab": 5,
        "ssm_d_state": 2, "ssm_head_dim": 2, "ssm_expand": 2, "ssm_conv": 4,
        "d_ff": 3, "n_experts": 8, "held_experts": 2, "first_held_expert": 0,
        "top_k": 2, "shared_d_ff": 6, "lora_targets": ["mamba.w_in", "moe.shared.w_down"]}
TRAFFIC = {"seq_len": 4, "local_batch": 1, "lora_rank": 1, "num_sampled": 1,
           "local_steps": 1}


def test_hand_count_mamba_moe():
    # per token: w_in 4x(16+4+4) -> 192, w_out 8x4 -> 64, router 4x8 -> 64,
    # shared 4x6 twice -> 96 and 6x4 -> 48: 464; SSD 4*8*2 = 64, conv
    # 2*4*12 = 96: 160. 4 tokens: 2496 + routed rows 4*2*2/8 = 2, each
    # 3 * 2*4*3 = 72: 144 -> 2640; head 2*4*5*4 = 160 -> forward 2800.
    # LoRA on w_in and the shared w_down: 4*1*((4+24) + (6+4))*4 = 608
    s = dict(HAND, groups=[{"kind": "M_moe", "count": 1}])
    assert flops.forward_per_sequence(s, 4) == 2800
    assert flops.required_per_step(s, TRAFFIC) == 2 * 2800 + 608


def test_hand_count_attention_moe():
    # per token: wq 4x4, wk 4x2, wv 4x2, wo 4x4 -> 96; router and shared
    # 64 + 96 + 48 -> 304; 4 tokens: 1216 + attention 4*2*2 * (1+2+3+4)
    # = 160 + routed 144 -> 1520; head 160 -> forward 1680. LoRA on the
    # shared w_down: 4*1*(6+4)*4 = 160
    s = dict(HAND, groups=[{"kind": "F_moe", "count": 1}])
    assert flops.forward_per_sequence(s, 4) == 1680
    assert flops.required_per_step(s, TRAFFIC) == 2 * 1680 + 160
    # two groups count as their kinds' layers, with one head
    both = dict(HAND, groups=[{"kind": "M_moe", "count": 2}, {"kind": "F_moe", "count": 1}])
    assert flops.forward_per_sequence(both, 4) == 2 * 2640 + 1520 + 160


def test_required_per_round_is_pinned():
    """The count of the configuration as committed, fixed so that ``mfu``
    moves only with the window."""
    shapes = harness.load_json(BENCH / "configs" / f"{CONFIG}.json")["shapes"]
    traffic = harness.load_json(BENCH / "traffic" / "silo-2k.json")
    assert flops.required_per_round(shapes, traffic) == PINNED


def test_tiny_granite_cell_is_correct():
    """A whole run of the tiny cell. Its c_i are of the size that silo-2k
    gives them: at ``tiny.traffic``'s (ten times that) they outweigh this
    model's smaller gradients (residual branches times 0.22, logits over
    16), and the new c_i, the mean gradient, is a difference of two
    larger terms, which float32 rounding alone moves by about 1e-5."""
    import run

    benchmark = harness.load_json(harness.REPO / "BENCHMARK.json")
    files = ({"name": f"tiny-{CELL}", "chips": 1}, tiny_granite(),
             tiny.traffic("silo-2k", ci_std={"A": 1e-4, "B": 5e-4}),
             harness.resolve(CELL, benchmark)[3])
    res = run.run_cell(f"tiny-{CELL}", 2**31 + 11, 0.05, False, benchmark, files,
                       t_start=time.perf_counter())
    assert res["attempted"] >= 1 and res["failed"] == 0
    # float32 program against the float32 reference: rounding only
    assert all(v["value"] < 1e-5 for v in res["checks"].values()), res["checks"]
    assert res["correct"]
