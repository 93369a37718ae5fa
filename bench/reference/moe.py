"""The MoE tail of a granite-4.0-h layer (model_type granitemoehybrid),
plain float32, and its FLOP count; ``bench/kinds/M_moe.py`` and
``F_moe.py`` end in it.

The router scores all ``n_experts`` experts; a token's gates are the
softmax of its ``top_k`` largest scores, zero elsewhere. This chip holds
``held_experts`` of them, from ``first_held_expert`` on, and the tail is
their part of the routed sum plus the shared expert:

    tail(h) = sum_{j held} gate_j(h) SwiGLU_j(h) + SwiGLU_shared(h).

The sum runs densely over the held experts for every token (a gate of
zero adds nothing); the program routes instead. What the absent experts
would add is left out of both. Keys of ``shapes``: ``d_ff`` (one
expert's width), ``n_experts``, ``held_experts``, ``first_held_expert``,
``top_k``, ``shared_d_ff``.
"""
import jax
import jax.numpy as jnp

from reference import lm


def program_shapes(cfg):
    """The MoE keys of ``shapes`` from the program's ``cfg.moe``, and the
    model's multipliers, which the kinds that end in this tail apply."""
    mo = cfg.moe
    if cfg.mlp_kind != "silu_gated":
        raise ValueError(f"a {cfg.mlp_kind} expert is another kind")
    return {"d_ff": mo.expert_d_ff, "n_experts": mo.num_experts,
            "held_experts": mo.held(), "first_held_expert": mo.first_held_expert,
            "top_k": mo.top_k, "shared_d_ff": mo.shared_d_ff * mo.num_shared_experts,
            "residual_multiplier": cfg.residual_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "logits_scaling": cfg.logits_scaling}


def gates(s, scores):
    """(..., n_experts) router scores -> the held experts' gates
    (..., held_experts): softmax over each token's top_k scores."""
    top, ids = jax.lax.top_k(scores, s["top_k"])
    w = jnp.einsum("...k,...ke->...e", jax.nn.softmax(top, axis=-1),
                   jax.nn.one_hot(ids, s["n_experts"], dtype=scores.dtype))
    first = s["first_held_expert"]
    return w[..., first:first + s["held_experts"]]


def tail(s, p, lin, h, quant):
    """The tail on the normed stream h (B, T, d); ``p`` the layer's
    ``moe`` parameters, ``lin`` the layer's projection."""
    g = gates(s, lin("moe.router", h))  # (B, T, J)
    x = h[:, None]  # (B, 1, T, d) against the experts' (J, d, f)
    act = jax.nn.silu(lm.mm(x, p["w_gate"], quant)) * lm.mm(x, p["w_up"], quant)
    routed = jnp.einsum("bjtd,btj->btd", lm.mm(act, p["w_down"], quant), g)
    shared = lin("moe.shared.w_down", jax.nn.silu(lin("moe.shared.w_gate", h))
                 * lin("moe.shared.w_up", h))
    return routed + shared


def matmuls(s):
    """(leaf path, in, out) of the tail's dense matmuls: the router and
    the shared expert."""
    d, f = s["d_model"], s["shared_d_ff"]
    return [("moe.router", d, s["n_experts"]), ("moe.shared.w_gate", d, f),
            ("moe.shared.w_up", d, f), ("moe.shared.w_down", f, d)]


def routed_flops(s, seq):
    """The held experts' forward FLOPs on one sequence at the rows routed
    to them on average: seq * top_k * held / n_experts tokens, each
    through an expert's three d x d_ff matmuls."""
    rows = seq * s["top_k"] * s["held_experts"] / s["n_experts"]
    return rows * 3 * 2 * s["d_model"] * s["d_ff"]
