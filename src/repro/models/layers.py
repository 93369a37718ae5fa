"""Model substrate layers: norms, RoPE, attention (GQA / SWA / MLA), MLP,
MoE (ragged + GShard dispatch), Mamba2 SSD — all pure JAX, scan/jit friendly.

Conventions:
  activations  (B, S, E)           E = d_model
  q/k/v        (B, S, H, D)        D = head_dim
  params       nested dicts of jnp arrays (pytree)

Long-sequence attention uses a kv-block-chunked online-softmax path
(``flash_attention_jnp``) so that lowering at 32k/500k never materialises an
(S, S) score matrix; sliding-window attention uses a banded two-block path
(``local_attention_jnp``) that is O(S*W). On a TPU, W layers run the
splash-attention kernel behind ``repro.kernels.swa_attention`` instead,
where their shapes allow (``swa.takes``); these jnp cores are its
references and the path everywhere else.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import spans
from repro.kernels.swa_attention import ops as swa
from repro.util import umap, uscan

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if len(shape) == 3:  # (E, d_in, d_out) expert weights
        fan_in = shape[1]
    scale = (1.0 / math.sqrt(fan_in)) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out.astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * lax.rsqrt(var + eps) * weight.astype(jnp.float32) + bias.astype(
        jnp.float32
    )
    return out.astype(dt)


def apply_norm(cfg, x, p):
    if cfg.norm_kind == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def init_norm(cfg, key, dim, dtype):
    if cfg.norm_kind == "layernorm":
        return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}
    return {"scale": jnp.zeros((dim,), dtype)}  # rmsnorm stores (w - 1)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D), positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def dense_attention(q, k, v, *, mask_kind: str = "causal", prefix_len: int = 0,
                    window: int = 0, scale: Optional[float] = None):
    """Reference (non-chunked) attention. Used for short sequences & tests.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, Dk/Dv). mask_kind in
    {"causal", "sliding", "prefix", "full"}. Assumes q positions are
    [Skv-Sq, Skv) (prefill/self-attention alignment).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    q_pos = jnp.arange(sq) + (skv - sq)
    k_pos = jnp.arange(skv)
    rel = q_pos[:, None] - k_pos[None, :]  # >=0 means k not in future
    if mask_kind == "causal":
        mask = rel >= 0
    elif mask_kind == "sliding":
        mask = (rel >= 0) & (rel < window)
    elif mask_kind == "prefix":
        # bidirectional over [0, prefix_len), causal afterwards
        mask = (rel >= 0) | (k_pos[None, :] < prefix_len)
    elif mask_kind == "full":
        mask = jnp.ones((sq, skv), dtype=bool)
    else:
        raise ValueError(mask_kind)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention_jnp(q, k, v, *, mask_kind: str = "causal", prefix_len: int = 0,
                        block_kv: int = 1024, scale: Optional[float] = None):
    """Online-softmax attention, scanned over kv blocks — never builds (S, S).

    Semantics identical to ``dense_attention`` for mask_kind in
    {"causal", "prefix", "full"}.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if skv % block_kv != 0:
        return dense_attention(q, k, v, mask_kind=mask_kind, prefix_len=prefix_len,
                               scale=scale)
    n_rep = hq // hkv
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    nb = skv // block_kv
    kb = k.reshape(b, nb, block_kv, hkv, d)
    vb = v.reshape(b, nb, block_kv, hkv, v.shape[-1])
    q32 = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(sq) + (skv - sq)

    def body(carry, blk):
        o, m, l = carry
        kblk, vblk, idx = blk
        kblk = _repeat_kv(kblk, n_rep).astype(jnp.float32)
        vblk = _repeat_kv(vblk, n_rep).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kblk)  # (B,H,Sq,block)
        k_pos = idx * block_kv + jnp.arange(block_kv)
        rel = q_pos[:, None] - k_pos[None, :]
        if mask_kind == "causal":
            mask = rel >= 0
        elif mask_kind == "prefix":
            mask = (rel >= 0) | (k_pos[None, :] < prefix_len)
        elif mask_kind == "full":
            mask = jnp.ones((sq, block_kv), bool)
        else:
            raise ValueError(mask_kind)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vblk)
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, hq, sq, v.shape[-1]), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (o, m, l), _ = uscan(
        body, (o0, m0, l0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nb)),
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B,Sq,H,D)


def local_attention_jnp(q, k, v, *, window: int, scale: Optional[float] = None):
    """Exact sliding-window causal attention in O(S*2W).

    Requires Sq == Skv == S with S % window == 0 (caller pads). Each
    window-sized q block attends to its own and the previous kv block,
    masked to the exact band ``0 <= q_pos - k_pos < window``.
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if s % window != 0 or s < 2 * window:
        return dense_attention(q, k, v, mask_kind="sliding", window=window, scale=scale)
    n_rep = hq // hkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    nb = s // window
    qb = q.reshape(b, nb, window, hq, d).astype(jnp.float32) * scale
    kb = k.reshape(b, nb, window, hq, d)
    vb = v.reshape(b, nb, window, hq, v.shape[-1])
    # kv context for block i = concat(block i-1, block i); block -1 is zeros
    prev = jnp.pad(kb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    kctx = jnp.concatenate([prev, kb], axis=2)  # (B, nb, 2W, H, D)
    prevv = jnp.pad(vb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    vctx = jnp.concatenate([prevv, vb], axis=2)
    s_ = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kctx.astype(jnp.float32))
    q_pos = jnp.arange(window)[:, None]  # within block
    k_pos = jnp.arange(2 * window)[None, :] - window  # relative to block start
    rel = q_pos - k_pos
    mask = (rel >= 0) & (rel < window)  # (W, 2W)
    blk = jnp.arange(nb)
    # first block has no previous block: kill the prev half there
    first = (blk == 0)[:, None, None] & (k_pos[None] < 0)
    s_ = jnp.where(mask[None, None, None], s_, NEG_INF)
    s_ = jnp.where(first[:, None, :, :], NEG_INF, s_)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p, vctx.astype(jnp.float32))
    return out.reshape(b, s, hq, v.shape[-1]).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale: Optional[float] = None):
    """Single-token decode: q (B,1,H,D) vs cache (B,C,Hkv,D).

    ``pos`` (B,) is the index of the new token. For ring-buffer SWA caches
    (C == window) every slot is valid once pos >= window; validity handled
    by masking slots > pos when the cache is larger than the history.

    Written SPMD-friendly: the cache is contracted in its native dtype
    (f32 accumulation via preferred_element_type) and GQA is expressed as a
    grouped einsum — never ``_repeat_kv`` — so a seq- or headdim-sharded
    cache reduces to partial scores + a small all-reduce instead of a full
    cache all-gather (§Perf HC2).
    """
    b, _, hq, d = q.shape
    c, hkv = k_cache.shape[1], k_cache.shape[2]
    n_rep = hq // hkv
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, 1, hkv, n_rep, d)
    qg = qg.astype(k_cache.dtype)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_cache,
                   preferred_element_type=jnp.float32)  # (B,Hkv,R,1,C)
    slot = jnp.arange(c)[None, :]  # (1, C)
    if window and c == window:
        # ring buffer: slot valid iff it holds one of the last `window` tokens
        valid = (slot <= pos[:, None]) | (pos[:, None] >= window)
    else:
        valid = slot <= pos[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (F / W layers)
# ---------------------------------------------------------------------------


def init_attention(cfg, key, dtype):
    e, h, hkv, d = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (e, h * d), dtype),
        "wk": dense_init(ks[1], (e, hkv * d), dtype),
        "wv": dense_init(ks[2], (e, hkv * d), dtype),
        "wo": dense_init(ks[3], (h * d, e), dtype),
    }


def attention_block(cfg, p, x, positions, *, kind: str, prefix_len: int = 0,
                    use_flash_threshold: int = 2048):
    """Self-attention over full sequence (train / prefill)."""
    b, s, e = x.shape
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, d)
    k = (x @ p["wk"]).reshape(b, s, hkv, d)
    v = (x @ p["wv"]).reshape(b, s, hkv, d)
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    scale = cfg.attention_multiplier
    with jax.named_scope(spans.ATTENTION):
        if kind == "W":
            w = cfg.sliding_window
            if swa.takes(s, w) and scale is None:
                out = swa.swa_attention(q, k, v, w)
            elif s % w == 0 and s >= 2 * w:
                out = local_attention_jnp(q, k, v, window=w, scale=scale)
            else:
                out = dense_attention(q, k, v, mask_kind="sliding", window=w,
                                      scale=scale)
        else:
            mask_kind = "prefix" if prefix_len else "causal"
            if s > use_flash_threshold:
                out = flash_attention_jnp(q, k, v, mask_kind=mask_kind,
                                          prefix_len=prefix_len, scale=scale)
            else:
                out = dense_attention(q, k, v, mask_kind=mask_kind,
                                      prefix_len=prefix_len, scale=scale)
    return out.reshape(b, s, h * d) @ p["wo"]


def attention_decode(cfg, p, x, cache, pos, *, kind: str):
    """One-token decode. cache: {"k": (B,C,Hkv,D), "v": ...}; pos: (B,)."""
    b, _, e = x.shape
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, 1, h, d)
    k = (x @ p["wk"]).reshape(b, 1, hkv, d)
    v = (x @ p["wv"]).reshape(b, 1, hkv, d)
    if cfg.rope_theta is not None:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    c = cache["k"].shape[1]
    window = cfg.sliding_window if kind == "W" else 0
    slot = (pos % c) if (window and c == window) else pos
    k_cache = jax.vmap(lambda buf, kk, i: lax.dynamic_update_slice(buf, kk, (i, 0, 0)))(
        cache["k"], k.astype(cache["k"].dtype), slot
    )
    v_cache = jax.vmap(lambda buf, vv, i: lax.dynamic_update_slice(buf, vv, (i, 0, 0)))(
        cache["v"], v.astype(cache["v"].dtype), slot
    )
    out = decode_attention(q, k_cache, v_cache, pos, window=window,
                           scale=cfg.attention_multiplier)
    out = out.reshape(b, 1, h * d) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


def init_attention_cache(cfg, batch, seq_len, dtype, kind: str):
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    c = min(cfg.sliding_window, seq_len) if kind == "W" else seq_len
    return {
        "k": jnp.zeros((batch, c, hkv, d), dtype),
        "v": jnp.zeros((batch, c, hkv, d), dtype),
    }


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V3 / MiniCPM3)
# ---------------------------------------------------------------------------


def init_mla(cfg, key, dtype):
    m = cfg.mla
    e, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    return {
        "wq_a": dense_init(ks[0], (e, m.q_lora_rank), dtype),
        "q_norm": {"scale": jnp.zeros((m.q_lora_rank,), dtype)},
        "wq_b": dense_init(ks[1], (m.q_lora_rank, h * qk), dtype),
        "wkv_a": dense_init(ks[2], (e, m.kv_lora_rank), dtype),
        "kv_norm": {"scale": jnp.zeros((m.kv_lora_rank,), dtype)},
        "wk_rope": dense_init(ks[3], (e, m.qk_rope_head_dim), dtype),
        "wk_b": dense_init(ks[4], (m.kv_lora_rank, h * m.qk_nope_head_dim), dtype),
        "wv_b": dense_init(ks[5], (m.kv_lora_rank, h * m.v_head_dim), dtype),
        "wo": dense_init(ks[6], (h * m.v_head_dim, e), dtype),
    }


def mla_block(cfg, p, x, positions, *, prefix_len: int = 0):
    """MLA self-attention (train / prefill): expand latent to full k/v."""
    m = cfg.mla
    b, s, e = x.shape
    h = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    cq = rms_norm(x @ p["wq_a"], p["q_norm"]["scale"])
    q = (cq @ p["wq_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = rms_norm(x @ p["wkv_a"], p["kv_norm"]["scale"])  # (B,S,R)
    k_nope = (ckv @ p["wk_b"]).reshape(b, s, h, dn)
    v = (ckv @ p["wv_b"]).reshape(b, s, h, dv)
    k_rope = apply_rope((x @ p["wk_rope"]).reshape(b, s, 1, dr), positions,
                        cfg.rope_theta)
    k_rope = jnp.broadcast_to(k_rope, (b, s, h, dr))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope], axis=-1)
    mask_kind = "prefix" if prefix_len else "causal"
    scale = 1.0 / math.sqrt(dn + dr)
    if s > 2048:
        out = flash_attention_jnp(q_full, k_full, v, mask_kind=mask_kind,
                                  prefix_len=prefix_len, scale=scale)
    else:
        out = dense_attention(q_full, k_full, v, mask_kind=mask_kind,
                              prefix_len=prefix_len, scale=scale)
    return out.reshape(b, s, h * dv) @ p["wo"]


def mla_decode(cfg, p, x, cache, pos):
    """Absorbed-latent MLA decode: cache holds (c_kv, k_rope) only.

    scores = (q_nope @ W_uk) @ c_kv^T + q_rope @ k_rope^T ;
    out    = (attn @ c_kv) @ W_uv  — the production MLA trick: the big
    per-head K/V are never materialised at decode time.
    """
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    dn, dr, dv, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    cq = rms_norm(x @ p["wq_a"], p["q_norm"]["scale"])
    q = (cq @ p["wq_b"]).reshape(b, 1, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos[:, None], cfg.rope_theta)
    ckv_new = rms_norm(x @ p["wkv_a"], p["kv_norm"]["scale"]).reshape(b, 1, r)
    kr_new = apply_rope((x @ p["wk_rope"]).reshape(b, 1, 1, dr), pos[:, None],
                        cfg.rope_theta).reshape(b, 1, dr)
    ckv = jax.vmap(lambda buf, nw, i: lax.dynamic_update_slice(buf, nw, (i, 0)))(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos)
    kr = jax.vmap(lambda buf, nw, i: lax.dynamic_update_slice(buf, nw, (i, 0)))(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos)
    # absorb W_uk into q: (B,1,H,dn) @ (R,H,dn) -> (B,1,H,R)
    # latent cache contracted in its native dtype (f32 accumulation via
    # preferred_element_type) — same SPMD-friendliness fix as
    # decode_attention (§Perf HC2): no f32 copy of the cache
    wk_b = p["wk_b"].reshape(r, h, dn)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk_b)
    s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(kr.dtype), kr,
                        preferred_element_type=jnp.float32)
    scale = 1.0 / math.sqrt(dn + dr)
    s = (s_lat + s_rope) * scale
    c = ckv.shape[1]
    valid = jnp.arange(c)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", pattn.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)  # (B,1,H,R)
    wv_b = p["wv_b"].reshape(r, h, dv)
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat.astype(x.dtype), wv_b)
    out = out.reshape(b, 1, h * dv).astype(x.dtype) @ p["wo"]
    return out, {"ckv": ckv, "k_rope": kr}


def init_mla_cache(cfg, batch, seq_len, dtype):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, seq_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq_len, m.qk_rope_head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(cfg, key, dtype, d_ff: Optional[int] = None):
    e = cfg.d_model
    f = cfg.d_ff if d_ff is None else d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "gelu":
        return {
            "w_up": dense_init(ks[0], (e, f), dtype),
            "w_down": dense_init(ks[1], (f, e), dtype),
        }
    return {
        "w_gate": dense_init(ks[0], (e, f), dtype),
        "w_up": dense_init(ks[1], (e, f), dtype),
        "w_down": dense_init(ks[2], (f, e), dtype),
    }


def mlp_block(cfg, p, x):
    if cfg.mlp_kind == "gelu":
        return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]
    act = jax.nn.silu if cfg.mlp_kind == "silu_gated" else jax.nn.gelu
    return (act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (routed experts): ragged_dot path + GShard dispatch path
# ---------------------------------------------------------------------------


def init_moe(cfg, key, dtype):
    """The router scores all ``num_experts``; the expert weights are those
    of the experts this device holds (``MoEConfig.held``)."""
    mo = cfg.moe
    e, f, n = cfg.d_model, mo.expert_d_ff, mo.held()
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (e, mo.num_experts), dtype, scale=0.02),
        "w_gate": dense_init(ks[1], (n, e, f), dtype),
        "w_up": dense_init(ks[2], (n, e, f), dtype),
        "w_down": dense_init(ks[3], (n, f, e), dtype),
    }
    if mo.num_shared_experts:
        fs = mo.shared_d_ff * mo.num_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kk[0], (e, fs), dtype),
            "w_up": dense_init(kk[1], (e, fs), dtype),
            "w_down": dense_init(kk[2], (fs, e), dtype),
        }
    return p


def _router(cfg, p, xf):
    """xf: (T, E) tokens. Returns top-k weights (T,k), ids (T,k), aux loss."""
    mo = cfg.moe
    logits = (xf.astype(jnp.float32)) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, Ex)
    w, ids = lax.top_k(probs, mo.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(ids, mo.num_experts, dtype=jnp.float32).sum(1), axis=0
    ) / mo.top_k
    frac_probs = probs.mean(0)
    aux = mo.num_experts * jnp.sum(frac_tokens * frac_probs)
    return w, ids, aux


class _Route(NamedTuple):
    """Where the (token, choice) pairs of ``moe_block_ragged`` go: the
    sorted buffer's rows come from pairs ``order`` of tokens ``tok_idx``,
    the first ``routed`` of them held; pair (t, j), where ``held``, sits
    at row ``pos[t, j]``."""
    order: jax.Array    # (rows,)
    tok_idx: jax.Array  # (rows,)
    routed: jax.Array   # (rows, 1)
    pos: jax.Array      # (T, top_k), 0 where not held
    held: jax.Array     # (T, top_k)


def _sum_choices(y, route, w=None):
    """``out[t] = sum_j held[t, j] * w[t, j] * y[pos[t, j]]`` in float32:
    each token's rows gathered back from the buffer and summed over its
    choices. The rows are selected with ``where``, so rows never picked
    (past the groups, unwritten on the TPU) leak no NaN into the sum. One
    (T, E) gather per choice: the TPU's gathers do not fuse into the sum,
    and one (T, top_k, E) gather would be written out whole."""
    out = 0
    for j in range(route.pos.shape[1]):
        row = jnp.where(route.held[:, j, None], y[route.pos[:, j]], 0)
        row = row.astype(jnp.float32)
        out = out + (row if w is None else row * w[:, j, None])
    return out


@jax.custom_vjp
def _dispatch(xf, route):
    """Token rows into the sorted buffer: ``xf[tok_idx]`` on the routed
    rows, zero past them. Its transpose gathers by ``pos`` and sums over
    the choices, instead of scatter-adding the rows back."""
    return jnp.where(route.routed, xf[route.tok_idx], 0)


def _dispatch_fwd(xf, route):
    return _dispatch(xf, route), route


def _dispatch_bwd(route, d_xs):
    return _sum_choices(d_xs, route).astype(d_xs.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out_s, w, route):
    """The buffer's rows back into token order, weighted by the router,
    summed in float32 and cast once. The cotangent of ``out_s`` gathers
    ``d_out`` by ``tok_idx``; that of ``w`` is the dot of each routed
    row with its token's ``d_out``, gathered back by ``pos``."""
    return _sum_choices(out_s, route, w).astype(out_s.dtype)


def _combine_fwd(out_s, w, route):
    return _combine(out_s, w, route), (out_s, w, route)


def _combine_bwd(res, d_out):
    out_s, w, route = res
    d_rows = d_out[route.tok_idx].astype(jnp.float32)  # (rows, E)
    wsort = w.reshape(-1)[route.order][:, None]
    d_out_s = jnp.where(route.routed, d_rows * wsort, 0)
    d_wsort = jnp.where(route.routed, d_rows * out_s.astype(jnp.float32), 0).sum(-1)
    d_w = jnp.where(route.held, d_wsort[route.pos], 0)
    return d_out_s.astype(out_s.dtype), d_w.astype(w.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_block_ragged(cfg, p, x):
    """Dropless routed experts by sort + ``lax.ragged_dot`` grouped matmuls.

    The router scores all ``num_experts``; this device computes the part
    of the result that its held experts give (all of them unless
    ``MoEConfig.held_experts`` sets a share). Every (token, choice) pair
    routed to a held expert is computed, whatever the skew: at most
    min(top_k, held) of a token's choices can land here, so the sorted
    buffer has T * min(top_k, held) rows, held choices first by expert.

    Rows move between token order and the sorted buffer by a permutation
    and its inverse, never by a scatter-add: ``order`` sorts the pairs,
    ``tok_idx`` gives each buffer row its token (the dispatch gathers
    ``x`` by it), and ``pos``, the inverse permutation, gives each
    (token, choice) pair its row (the combine gathers the experts' rows
    by it and sums the top_k choices in float32). Each direction's
    transpose is the other gather (``_dispatch``, ``_combine``), so the
    backward pass scatters no rows either.

    Rows past the routed ones belong to no group. On the TPU the grouped
    matmuls leave those rows of their results unwritten, so the dispatch
    zeroes them on the input, and the combine selects the held choices'
    rows with ``where`` (a zero weight would keep NaN * 0) and gives them
    zero cotangents in the backward pass.
    """
    mo = cfg.moe
    n_held = mo.held()
    b, s, e = x.shape
    xf = x.reshape(b * s, e)
    t = xf.shape[0]
    w, ids, aux = _router(cfg, p, xf)
    local = ids - mo.first_held_expert  # (T, k)
    held = (local >= 0) & (local < n_held)
    slot = jnp.where(held, local, n_held).reshape(-1)  # absent experts sort last
    rows = t * min(mo.top_k, n_held)
    order = jnp.argsort(slot)
    pos = jnp.argsort(order).reshape(t, mo.top_k)  # the inverse permutation
    group_sizes = jnp.bincount(slot, length=n_held + 1)[:n_held].astype(jnp.int32)
    routed = (jnp.arange(rows) < group_sizes.sum())[:, None]
    order = order[:rows]
    route = _Route(order, order // mo.top_k, routed, jnp.where(held, pos, 0), held)
    act = jax.nn.silu if cfg.mlp_kind != "gelu_gated" else jax.nn.gelu
    xs = _dispatch(xf, route)  # (rows, E)
    with jax.named_scope(spans.MOE_EXPERTS):
        g = lax.ragged_dot(xs, p["w_gate"], group_sizes)
        u = lax.ragged_dot(xs, p["w_up"], group_sizes)
        out_s = lax.ragged_dot(act(g) * u, p["w_down"], group_sizes)  # (rows, E)
    out = _combine(out_s, w, route).reshape(b, s, e).astype(x.dtype)
    return out + _shared_expert(cfg, p, x), aux


def moe_block_gshard(cfg, p, x, *, capacity_factor: Optional[float] = None,
                     group_size: Optional[int] = None):
    """GShard-style capacity dispatch via one-hot einsums, chunked over token
    groups so the (g, Ex, C) dispatch tensor stays bounded. Deterministic
    shapes; the dispatch/combine einsums are what GSPMD turns into
    all-to-all when experts are expert-parallel sharded."""
    mo = cfg.moe
    capacity_factor = (mo.capacity_factor if capacity_factor is None
                       else capacity_factor)
    group_size = mo.gshard_group_size if group_size is None else group_size
    b, s, e = x.shape
    xf = x.reshape(b * s, e)
    t = xf.shape[0]
    g = min(group_size, t)
    while t % g != 0:
        g //= 2
    ng = t // g
    cap = max(int(g * mo.top_k / mo.num_experts * capacity_factor), mo.top_k)
    w, ids, aux = _router(cfg, p, xf)
    act = jax.nn.silu if cfg.mlp_kind != "gelu_gated" else jax.nn.gelu

    def per_group(xg, wg, idg):
        # xg (g,E), wg (g,k), idg (g,k)
        onehot = jax.nn.one_hot(idg, mo.num_experts, dtype=jnp.float32)  # (g,k,Ex)
        # capacity position must count across ALL (token, k) assignments of
        # an expert — flatten (g, k) before the cumsum or slots collide
        gsz, kk, ex = onehot.shape
        oh_flat = onehot.reshape(gsz * kk, ex)
        pos_flat = jnp.cumsum(oh_flat, axis=0) - oh_flat
        pos = jnp.einsum("ge,ge->g", pos_flat, oh_flat).reshape(gsz, kk)
        keep = (pos < cap).astype(jnp.float32)
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # (g,k,C)
        disp = jnp.einsum("gke,gkc->gec", onehot * keep[..., None], pos_oh)
        comb = jnp.einsum("gec,gk,gke->gec", disp, wg.astype(jnp.float32), onehot)
        # dispatch/combine einsums run in the compute dtype (bf16 on the
        # production mesh): one-hot values are exact, each capacity slot
        # receives <= 1 token, so only the combine weights round
        disp_c = disp.astype(x.dtype)
        xin = jnp.einsum("gec,gd->ecd", disp_c, xg)
        with jax.named_scope(spans.MOE_EXPERTS):
            hg = act(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"]))
            hu = jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
            ho = jnp.einsum("ecf,efd->ecd", hg * hu, p["w_down"])
        return jnp.einsum("gec,ecd->gd", comb.astype(x.dtype), ho)

    xg = xf.reshape(ng, g, e)
    wg = w.reshape(ng, g, mo.top_k)
    idg = ids.reshape(ng, g, mo.top_k)
    out = umap(lambda args: per_group(*args), (xg, wg, idg))
    out = out.reshape(b, s, e)
    return out + _shared_expert(cfg, p, x), aux


def _shared_expert(cfg, p, x):
    if "shared" not in p:
        return jnp.zeros_like(x)
    sp = p["shared"]
    act = jax.nn.silu if cfg.mlp_kind != "gelu_gated" else jax.nn.gelu
    return (act(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]


def moe_block(cfg, p, x, impl: str = "ragged"):
    with jax.named_scope(spans.MOE):
        if impl == "gshard":
            return moe_block_gshard(cfg, p, x)
        return moe_block_ragged(cfg, p, x)


# ---------------------------------------------------------------------------
# Mamba2 (SSD) block
# ---------------------------------------------------------------------------


def init_mamba(cfg, key, dtype):
    sm = cfg.ssm
    e = cfg.d_model
    di = sm.d_inner(e)
    h = sm.n_heads(e)
    n = sm.d_state
    g = sm.n_groups
    conv_dim = di + 2 * g * n
    ks = jax.random.split(key, 5)
    return {
        "w_in": dense_init(ks[0], (e, 2 * di + 2 * g * n + h), dtype),
        "conv_w": dense_init(ks[1], (sm.conv_kernel, conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(
            jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)
        ).astype(jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "out_norm": {"scale": jnp.zeros((di,), dtype)},
        "w_out": dense_init(ks[2], (di, e), dtype),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: (B,S,C); w: (K,C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def _ssd_chunked(xh, dt, a_log, bmat, cmat, d_skip, chunk: int):
    """SSD (state-space duality) chunked scan.

    xh (B,S,H,P), dt (B,S,H) post-softplus, bmat/cmat (B,S,N) [n_groups=1],
    a_log (H,). Returns y (B,S,H,P) and final state (B,H,N,P).
    """
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    l = min(chunk, s)
    while s % l != 0:
        l //= 2
    nc = s // l
    a = -jnp.exp(a_log)  # (H,) negative
    dta = dt * a  # (B,S,H)
    xc = xh.reshape(b, nc, l, h, p)
    dtc = dt.reshape(b, nc, l, h)
    dtac = dta.reshape(b, nc, l, h)
    bc = bmat.reshape(b, nc, l, n)
    cc = cmat.reshape(b, nc, l, n)
    seg = jnp.cumsum(dtac, axis=2)  # (B,nc,L,H) cumulative log-decay
    total = seg[:, :, -1:, :]  # (B,nc,1,H)

    # ---- intra-chunk (quadratic within chunk, masked) ----
    cb = jnp.einsum("bcln,bcmn->bclm", cc, bc)  # (B,nc,L,L) t=l, s=m
    mask = jnp.tril(jnp.ones((l, l), bool))
    diff = seg[:, :, :, None, :] - seg[:, :, None, :, :]  # (B,nc,L,L,H)
    # mask BEFORE exp: the upper triangle is exp(+large) = inf, and inf*0
    # from the post-hoc where still poisons the backward pass with NaNs
    diff = jnp.where(mask[None, None, :, :, None], diff, -jnp.inf)
    decay = jnp.exp(diff)
    m = cb[..., None] * decay * dtc[:, :, None, :, :]  # (B,nc,L,L,H)
    m = jnp.where(mask[None, None, :, :, None], m, 0.0)
    y_intra = jnp.einsum("bclmh,bcmhp->bclhp", m, xc)

    # ---- chunk states ----
    state_decay = jnp.exp(total - seg)  # decay from step to chunk end (B,nc,L,H)
    sc = jnp.einsum("bcln,bclh,bclhp->bchnp", bc, dtc * state_decay, xc)

    # ---- inter-chunk recurrence over nc ----
    chunk_decay = jnp.exp(total[:, :, 0, :])  # (B,nc,H)

    def scan_fn(carry, inp):
        s_prev = carry  # (B,H,N,P)
        s_c, dec = inp  # (B,H,N,P), (B,H)
        s_new = s_prev * dec[:, :, None, None] + s_c
        return s_new, s_prev

    s0 = jnp.zeros((b, h, n, p), xh.dtype)
    final_state, s_prevs = uscan(
        scan_fn, s0,
        (jnp.moveaxis(sc, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)  # (B,nc,H,N,P) state entering chunk

    # ---- inter-chunk contribution ----
    in_decay = jnp.exp(seg)  # (B,nc,L,H)
    y_inter = jnp.einsum("bcln,bclh,bchnp->bclhp", cc, in_decay, s_prevs)

    y = (y_intra + y_inter).reshape(b, s, h, p)
    y = y + xh * d_skip[None, None, :, None]
    return y, final_state


def mamba_block(cfg, p, x):
    """Full-sequence Mamba2 forward. x: (B,S,E) -> (B,S,E)."""
    sm = cfg.ssm
    b, s, e = x.shape
    di = sm.d_inner(e)
    h = sm.n_heads(e)
    n = sm.d_state
    g = sm.n_groups
    proj = x @ p["w_in"]  # (B,S, 2di+2gn+h)
    z, xin, bc, dt = jnp.split(proj, [di, 2 * di, 2 * di + 2 * g * n], axis=-1)
    conv_in = jnp.concatenate([xin, bc], axis=-1)
    conv_out = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
    xin, bmat, cmat = jnp.split(conv_out, [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    xh = xin.reshape(b, s, h, sm.head_dim)
    y, _ = _ssd_chunked(xh.astype(jnp.float32), dt, p["a_log"],
                        bmat.astype(jnp.float32), cmat.astype(jnp.float32),
                        p["d_skip"], sm.chunk_size)
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"]["scale"])
    return y @ p["w_out"]


def mamba_decode(cfg, p, x, cache, pos):
    """One-token Mamba2 step. cache: {"conv": (B,K-1,C), "state": (B,H,N,P)}."""
    sm = cfg.ssm
    b, _, e = x.shape
    di = sm.d_inner(e)
    h = sm.n_heads(e)
    n = sm.d_state
    g = sm.n_groups
    proj = (x[:, 0] @ p["w_in"])  # (B, ·)
    z, xin, bc, dt = jnp.split(proj, [di, 2 * di, 2 * di + 2 * g * n], axis=-1)
    conv_in = jnp.concatenate([xin, bc], axis=-1)  # (B,C)
    hist = jnp.concatenate([cache["conv"], conv_in[:, None]], axis=1)  # (B,K,C)
    w = p["conv_w"]
    conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", hist, w) + p["conv_b"])
    xin, bmat, cmat = jnp.split(conv_out, [di, di + g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["a_log"])  # (H,)
    decay = jnp.exp(dt * a)  # (B,H)
    xh = xin.reshape(b, h, sm.head_dim).astype(jnp.float32)
    state = cache["state"] * decay[:, :, None, None] + jnp.einsum(
        "bn,bh,bhp->bhnp", bmat.astype(jnp.float32), dt, xh
    )
    y = jnp.einsum("bn,bhnp->bhp", cmat.astype(jnp.float32), state)
    y = y + xh * p["d_skip"][None, :, None]
    y = y.reshape(b, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"]["scale"])
    out = (y @ p["w_out"])[:, None]
    new_cache = {"conv": hist[:, 1:], "state": state}
    return out, new_cache


def init_mamba_cache(cfg, batch, dtype):
    sm = cfg.ssm
    e = cfg.d_model
    di = sm.d_inner(e)
    h = sm.n_heads(e)
    conv_dim = di + 2 * sm.n_groups * sm.d_state
    return {
        "conv": jnp.zeros((batch, sm.conv_kernel - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, h, sm.d_state, sm.head_dim), jnp.float32),
    }
