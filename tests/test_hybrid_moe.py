"""Layer forms of granite-4.0-h on the program alone: Mamba-2 layers with
an MoE tail, an expert share that routes over all experts and computes
its own experts' part without dropping, NoPE attention at a given scale,
and the model's multipliers."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.models import decode_step, forward, init_cache, init_params, loss_fn
from repro.models import layers as L
from repro.models import model as M

ARCH = "granite-4.0-h-small"


def _cfg(num_experts=8, top_k=3, held=0, first=0, **kw):
    """The reduced granite config on the ragged dispatch, with the MoE
    sizes given."""
    cfg = get_reduced(ARCH)
    moe = dataclasses.replace(cfg.moe, num_experts=num_experts, top_k=top_k,
                              held_experts=held, first_held_expert=first)
    return dataclasses.replace(cfg, moe_impl="ragged", moe=moe, **kw)


def _share(p, first, n):
    return dict(p, **{k: p[k][first:first + n] for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("share", [2, 4])
def test_expert_shares_sum_to_the_whole_layer(share):
    """Shares of experts 0..k, k..2k, ... each computed as its device
    would, the shared expert counted once, add up to the uncut layer."""
    whole = _cfg()
    key = jax.random.key(0)
    p = L.init_moe(whole, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, whole.d_model))
    want, _ = L.moe_block_ragged(whole, p, x)
    shared = L._shared_expert(whole, p, x)
    total = shared
    for first in range(0, whole.moe.num_experts, share):
        cfg = _cfg(held=share, first=first)
        out, _ = L.moe_block_ragged(cfg, _share(p, first, share), x)
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def _dense_share(cfg, p, x):
    """Every held expert on every token, times its gate (the softmax of
    the token's top_k router scores, zero off them), plus the shared
    expert: the share with nothing dropped."""
    mo = cfg.moe
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    scores = xf @ np.asarray(p["router"], np.float64)
    top = np.argsort(-scores, axis=-1)[:, :mo.top_k]
    picked = np.take_along_axis(scores, top, -1)
    gates = np.zeros_like(scores)
    e = np.exp(picked - picked.max(-1, keepdims=True))
    np.put_along_axis(gates, top, e / e.sum(-1, keepdims=True), -1)
    out = np.zeros_like(xf)
    for j in range(mo.held()):
        w = {k: np.asarray(p[k][j], np.float64) for k in ("w_gate", "w_up", "w_down")}
        g = xf @ w["w_gate"]
        h = g / (1 + np.exp(-g)) * (xf @ w["w_up"])
        out += gates[:, mo.first_held_expert + j, None] * (h @ w["w_down"])
    return out.reshape(x.shape) + np.asarray(L._shared_expert(cfg, p, x))


def _skewed(top_k, held, first):
    """A share whose router sends every token's choices into the held
    experts, as many as fit: every token carries a feature that the router
    reads as a large score for the held experts and a low one for the
    rest."""
    cfg = _cfg(top_k=top_k, held=held, first=first)
    key = jax.random.key(3)
    p = L.init_moe(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    x = x.at[..., 0].set(4.0)
    p["router"] = p["router"].at[0].set(-2.0).at[0, first:first + held].set(2.0)
    return cfg, p, x


@pytest.mark.parametrize("top_k,held", [(3, 4), (4, 3)])
def test_share_is_dropless_under_forced_skew(top_k, held):
    """A router that sends every token's choices into the held experts
    (as many as fit) fills the whole buffer, and the share still equals
    the dense sum over the held experts."""
    first = 2
    cfg, p, x = _skewed(top_k, held, first)
    _, ids, _ = L._router(cfg, p, x.reshape(-1, cfg.d_model))
    local = np.asarray(ids) - first
    assert (((local >= 0) & (local < held)).sum(-1) == min(top_k, held)).all()
    out, _ = L.moe_block_ragged(cfg, p, x)
    np.testing.assert_allclose(out, _dense_share(cfg, p, x), rtol=1e-4, atol=1e-5)


def test_rows_past_the_groups_change_nothing(monkeypatch):
    """On the TPU the grouped matmuls leave the rows past the routed ones
    unwritten. Written as NaN here, in the forward and the backward, they
    reach neither the share nor its gradient."""
    cfg = _cfg(top_k=3, held=2, first=1)
    key = jax.random.key(5)
    p = L.init_moe(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))

    def fn(x):
        out, _ = L.moe_block_ragged(cfg, p, x)
        return out, jax.grad(lambda x: jnp.sum(L.moe_block_ragged(cfg, p, x)[0] ** 2))(x)

    want = fn(x)
    real = jax.lax.ragged_dot

    def unwritten(a, rows):
        return jnp.where((jnp.arange(a.shape[0]) < rows)[:, None], a, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, group_sizes):
        return unwritten(real(lhs, rhs, group_sizes), group_sizes.sum())

    def fwd(lhs, rhs, group_sizes):
        return ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        d_lhs = real(g, jnp.swapaxes(rhs, 1, 2), group_sizes)
        d_rhs = jax.vjp(lambda r: real(lhs, r, group_sizes), rhs)[1](g)[0]
        return unwritten(d_lhs, group_sizes.sum()), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    got = fn(x)
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _scatter_add_share(cfg, p, x):
    """The share as it was computed before the inverse permutation: the
    sorted rows gathered by token, and the combine a scatter-add of the
    weighted expert rows into (T, d_model) (whose transpose, like the
    dispatch gather's, is a scatter-add)."""
    mo = cfg.moe
    n_held = mo.held()
    b, s, e = x.shape
    xf = x.reshape(b * s, e)
    t = xf.shape[0]
    w, ids, aux = L._router(cfg, p, xf)
    local = ids.reshape(-1) - mo.first_held_expert
    held = (local >= 0) & (local < n_held)
    slot = jnp.where(held, local, n_held)
    rows = t * min(mo.top_k, n_held)
    order = jnp.argsort(slot)[:rows]
    tok_idx = order // mo.top_k
    group_sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(jnp.int32)
    routed = (jnp.arange(rows) < group_sizes.sum())[:, None]
    xs = jnp.where(routed, xf[tok_idx], 0)
    g = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    out_s = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["w_down"], group_sizes)
    out_s = jnp.where(routed, out_s, 0)
    wsort = w.reshape(-1)[order][:, None].astype(out_s.dtype)
    out = jnp.zeros((t, e), out_s.dtype).at[tok_idx].add(out_s * wsort)
    return out.reshape(b, s, e).astype(x.dtype) + L._shared_expert(cfg, p, x), aux


def _value_and_grads(block, cfg, p, x):
    """The block's output, and the gradient of a random projection of it
    with respect to x (through the router too) and every weight."""
    r = jax.random.normal(jax.random.key(9), x.shape)
    out, _ = block(cfg, p, x)
    grads = jax.grad(lambda x, p: jnp.vdot(block(cfg, p, x)[0], r), argnums=(0, 1))(x, p)
    return out, grads


@pytest.mark.parametrize("top_k,held,first,skew", [
    (3, 2, 1, False),  # top_k > held, a share past expert 0
    (2, 4, 0, False),  # top_k <= held, the first experts
    (3, 4, 4, False),  # top_k <= held, the last experts
    (3, 0, 0, False),  # every expert held
    (3, 4, 2, True),   # forced skew fills the buffer
    (4, 3, 2, True),
])
def test_permutation_matches_scatter_add(top_k, held, first, skew):
    """Dispatch and combine by the inverse permutation give the value and
    the gradients of the scatter-add formulation, within float32."""
    if skew:
        cfg, p, x = _skewed(top_k, held, first)
    else:
        cfg = _cfg(top_k=top_k, held=held, first=first)
        key = jax.random.key(6)
        p = L.init_moe(cfg, key, jnp.float32)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    got = _value_and_grads(L.moe_block_ragged, cfg, p, x)
    want = _value_and_grads(_scatter_add_share, cfg, p, x)
    assert set(got[1][1]) >= {"router", "shared"}
    assert float(jnp.abs(got[1][1]["router"]).max()) > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _scatter_shapes(block, cfg, p, x, remat):
    loss = lambda x, p: jnp.sum(block(cfg, p, x)[0] ** 2)  # noqa: E731
    if remat:
        loss = jax.checkpoint(loss, policy=jax.checkpoint_policies.nothing_saveable)
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, p).compile().as_text()
    return {tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(", hlo)}


@pytest.mark.parametrize("remat", [False, True])
def test_no_row_scatter_in_forward_or_backward(remat):
    """The compiled gradient of the share, rematerialised or not, holds no
    scatter into a (tokens, d_model) array; the scatter-add formulation
    holds them (so the check can see one)."""
    cfg = _cfg(top_k=3, held=2, first=1)
    p = L.init_moe(cfg, jax.random.key(7), jnp.float32)
    x = jax.random.normal(jax.random.key(8), (2, 16, cfg.d_model))
    rows = (2 * 16, cfg.d_model)
    assert rows not in _scatter_shapes(L.moe_block_ragged, cfg, p, x, remat)
    assert rows in _scatter_shapes(_scatter_add_share, cfg, p, x, remat)


def _leave_rows_unwritten(monkeypatch):
    """``lax.ragged_dot`` as the TPU runs it: rows past the groups of its
    result, and of its input's cotangent, hold NaN."""
    real = jax.lax.ragged_dot

    def unwritten(a, rows):
        return jnp.where((jnp.arange(a.shape[0]) < rows)[:, None], a, jnp.nan)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, group_sizes):
        return unwritten(real(lhs, rhs, group_sizes), group_sizes.sum())

    def fwd(lhs, rhs, group_sizes):
        return ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        d_lhs = real(g, jnp.swapaxes(rhs, 1, 2), group_sizes)
        d_rhs = jax.vjp(lambda r: real(lhs, r, group_sizes), rhs)[1](g)[0]
        return unwritten(d_lhs, group_sizes.sum()), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)


def test_share_that_no_token_picks_is_the_shared_expert(monkeypatch):
    """When no choice lands on the held experts, every row of the buffer
    is unwritten (NaN here); the combine and its transpose select rows,
    so the share is the shared expert alone, value and gradient."""
    first, held = 2, 3
    cfg = _cfg(top_k=3, held=held, first=first)
    key = jax.random.key(3)
    p = L.init_moe(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    x = x.at[..., 0].set(4.0)
    p["router"] = p["router"].at[0].set(2.0).at[0, first:first + held].set(-2.0)
    _, ids, _ = L._router(cfg, p, x.reshape(-1, cfg.d_model))
    assert not ((np.asarray(ids) >= first) & (np.asarray(ids) < first + held)).any()
    _leave_rows_unwritten(monkeypatch)
    out, grads = _value_and_grads(L.moe_block_ragged, cfg, p, x)
    want = L._shared_expert(cfg, p, x)
    r = jax.random.normal(jax.random.key(9), x.shape)
    want_dx = jax.grad(lambda x: jnp.vdot(L._shared_expert(cfg, p, x), r))(x)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(grads[0], want_dx, rtol=1e-5, atol=1e-6)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


def test_share_with_gshard_is_refused():
    with pytest.raises(ValueError, match="dropless"):
        dataclasses.replace(_cfg(held=2), moe_impl="gshard")
    with pytest.raises(ValueError, match="outside"):
        _cfg(held=4, first=6)


@pytest.mark.parametrize("seq,threshold", [(32, 2048), (2048, 1024)])
def test_nope_attention_takes_the_given_scale(seq, threshold):
    """With no RoPE and attention_multiplier set, attention_block is
    dense causal attention at that scale on the raw projections, on the
    dense path and on the kv-blocked one."""
    cfg = get_reduced(ARCH)
    assert cfg.rope_theta is None and cfg.attention_multiplier == 1 / 64
    key = jax.random.key(4)
    p = L.init_attention(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, seq, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(seq)[None], (1, seq))
    got = L.attention_block(cfg, p, x, pos, kind="F", use_flash_threshold=threshold)
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = ((x @ p[n]).reshape(1, seq, nh, d)
               for n, nh in (("wq", h), ("wk", hkv), ("wv", hkv)))
    want = L.dense_attention(q, k, v, scale=1 / 64).reshape(1, seq, h * d) @ p["wo"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    default = L.dense_attention(q, k, v).reshape(1, seq, h * d) @ p["wo"]
    assert not np.allclose(got, default, rtol=1e-3, atol=1e-3)


def test_mamba_layer_with_moe_keeps_its_tail():
    """An M layer under a config with MoE has the MoE tail, and the loss
    reads it; mamba2's M layers stay mixer only."""
    cfg = _cfg(held=2, first=1)
    params = init_params(cfg, jax.random.key(0))
    m_layer, f_layer = params["layers"]
    assert set(m_layer) == {"ln_attn", "mamba", "ln_mlp", "moe"}
    assert set(f_layer) == {"ln_attn", "attn", "ln_mlp", "moe"}
    assert m_layer["moe"]["w_gate"].shape[:2] == (1, 2)  # (layers, held)
    mamba2 = init_params(get_reduced("mamba2-2.7b"), jax.random.key(0))
    assert set(mamba2["layers"][0]) == {"ln_attn", "mamba"}
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    g = jax.jit(jax.grad(
        lambda p: loss_fn(cfg, p, {"tokens": tokens, "labels": tokens})[0]))(params)
    for leaf in (g["layers"][0]["moe"]["shared"]["w_down"], g["layers"][0]["moe"]["w_down"]):
        assert float(jnp.abs(leaf).max()) > 0


def test_decode_with_moe_tail_and_share_matches_forward():
    """Decoding through the cache, one token at a time, gives the full
    forward's logits: the M layer's tail is on the serve path too."""
    cfg = _cfg(held=2, first=1)
    params = init_params(cfg, jax.random.key(0))
    b, seqlen = 2, 24
    tokens = jax.random.randint(jax.random.key(1), (b, seqlen), 0, cfg.vocab_size)
    full, _ = forward(cfg, params, {"tokens": tokens})
    cache = init_cache(cfg, b, seqlen)
    step = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))
    for i in range(seqlen):
        lg, cache = step(params, cache, tokens[:, i:i + 1], jnp.full((b,), i, jnp.int32))
        np.testing.assert_allclose(lg[:, 0], full[:, i], rtol=1e-4, atol=5e-4)


def test_streamed_loss_matches_full_loss_with_multipliers():
    """The chunked cross-entropy divides the logits as the full head does."""
    cfg = _cfg()
    assert (cfg.embedding_multiplier, cfg.logits_scaling) == (12.0, 16.0)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    full, _ = loss_fn(cfg, params, batch)
    streamed, _ = loss_fn(dataclasses.replace(cfg, loss_chunk_vocab=128), params, batch)
    np.testing.assert_allclose(streamed, full, rtol=1e-5)
    unscaled, _ = loss_fn(dataclasses.replace(cfg, logits_scaling=1.0), params, batch)
    assert abs(float(unscaled) - float(full)) > 1e-3


def test_count_params_of_the_published_model():
    """40 layers: 36 Mamba-2 and 4 attention, each with a router over 72
    experts, 72 SwiGLU experts of 768 and a shared one of 1536; the tied
    embedding. 32.21 B in all, 8.80 B active a token (top-10)."""
    cfg = get_config(ARCH)
    d, v = 4096, 100352
    mamba = (d * (2 * 8192 + 2 * 128 + 128) + 4 * (8192 + 256) + (8192 + 256)
             + 3 * 128 + 8192 + 8192 * d)
    attn = d * 4096 + 2 * d * 1024 + 4096 * d
    tail = d + d * 72 + 72 * 3 * d * 768 + 3 * d * 1536
    want = 36 * (d + mamba) + 4 * (d + attn) + 40 * tail + v * d + d
    assert M.count_params_analytic(cfg) == want == 32_207_337_984
    assert M.count_active_params(cfg) == want - 40 * 62 * 3 * d * 768
    # a share of 9 experts a layer holds 9 of them
    share = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held_experts=9))
    assert M.count_params_analytic(share) == want - 40 * 63 * 3 * d * 768
