"""Transformer composition: layer-pattern grouping, lax.scan over stacked
homogeneous layer runs, encoder tower (whisper), cross-attention, caches.

Layers are grouped into maximal runs of identical (kind, uses_moe) signature;
each run's params are stacked on a leading axis and applied with ``lax.scan``
(remat-wrapped), keeping the HLO compact for 60+-layer models.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.util import uscan


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    kind: str  # F | W | M | Y
    uses_moe: bool
    count: int
    has_cross: bool = False  # whisper decoder layers


def layer_groups(cfg) -> List[LayerGroup]:
    pattern = cfg.pattern_for_layers()
    has_cross = cfg.encoder is not None
    sigs = [
        (pattern[i], cfg.layer_uses_moe(i), has_cross) for i in range(cfg.num_layers)
    ]
    groups: List[LayerGroup] = []
    for sig in sigs:
        if groups and (groups[-1].kind, groups[-1].uses_moe, groups[-1].has_cross) == sig:
            groups[-1] = dataclasses.replace(groups[-1], count=groups[-1].count + 1)
        else:
            groups.append(LayerGroup(sig[0], sig[1], 1, sig[2]))
    return groups


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------


def _has_ffn_tail(kind: str, uses_moe: bool) -> bool:
    """F, W and Y layers end in an MLP or MoE; an M layer only where it
    uses MoE (granite-4.0-h), else its mixer is the whole layer."""
    return kind != "M" or uses_moe


def _init_layer(cfg, key, kind: str, uses_moe: bool, has_cross: bool, dtype):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {}
    if kind in ("F", "W", "Y"):
        p["ln_attn"] = L.init_norm(cfg, ks[0], cfg.d_model, dtype)
        p["attn"] = (
            L.init_mla(cfg, ks[1], dtype) if cfg.mla else L.init_attention(cfg, ks[1], dtype)
        )
    if _has_ffn_tail(kind, uses_moe):
        p["ln_mlp"] = L.init_norm(cfg, ks[2], cfg.d_model, dtype)
        if uses_moe:
            p["moe"] = L.init_moe(cfg, ks[3], dtype)
        else:
            d_ff = None
            if cfg.moe is not None and cfg.moe.first_dense_layers:
                d_ff = cfg.moe.dense_d_ff
            p["mlp"] = L.init_mlp(cfg, ks[3], dtype, d_ff=d_ff)
    if kind in ("M", "Y"):
        nkey = "ln_mamba" if kind == "Y" else "ln_attn"
        if nkey not in p:
            p[nkey] = L.init_norm(cfg, ks[4], cfg.d_model, dtype)
        p["mamba"] = L.init_mamba(cfg, ks[5], dtype)
    if has_cross:
        kc = jax.random.split(ks[0], 2)
        p["ln_cross"] = L.init_norm(cfg, kc[0], cfg.d_model, dtype)
        p["cross"] = L.init_attention(cfg, kc[1], dtype)
    return p


def _cross_attention(cfg, p, x, enc_out):
    """Cross-attention: queries from decoder x, k/v from encoder output."""
    b, s, e = x.shape
    h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    se = enc_out.shape[1]
    q = (x @ p["wq"]).reshape(b, s, h, d)
    k = (enc_out @ p["wk"]).reshape(b, se, hkv, d)
    v = (enc_out @ p["wv"]).reshape(b, se, hkv, d)
    out = L.dense_attention(q, k, v, mask_kind="full")
    return out.reshape(b, s, h * d) @ p["wo"]


def _residual(cfg, x, branch):
    """x + branch, the branch times ``residual_multiplier`` where set."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    return x + branch


def _ffn_tail(cfg, p, x, uses_moe: bool, moe_impl: str):
    """The layer's MLP or MoE on its normed residual stream; returns
    (x, aux_loss)."""
    h2 = L.apply_norm(cfg, x, p["ln_mlp"])
    if uses_moe:
        out, aux = L.moe_block(cfg, p["moe"], h2, impl=moe_impl)
    else:
        out, aux = L.mlp_block(cfg, p["mlp"], h2), jnp.zeros((), jnp.float32)
    return _residual(cfg, x, out), aux


def _apply_layer(cfg, p, x, positions, kind: str, uses_moe: bool, *,
                 prefix_len: int = 0, enc_out=None, moe_impl: str = "ragged"):
    """Full-sequence layer forward. Returns (x, aux_loss)."""
    h_in = L.apply_norm(cfg, x, p["ln_attn"])
    if kind == "Y":
        # Hymba-style: attention and mamba heads in parallel on the same input
        attn_out = L.attention_block(cfg, p["attn"], h_in, positions, kind="W"
                                     if cfg.sliding_window else "F",
                                     prefix_len=prefix_len)
        mamba_out = L.mamba_block(cfg, p["mamba"], h_in)
        x = _residual(cfg, x, 0.5 * (attn_out + mamba_out))
    elif kind == "M":
        x = _residual(cfg, x, L.mamba_block(cfg, p["mamba"], h_in))
    else:  # F / W
        if cfg.mla:
            attn_out = L.mla_block(cfg, p["attn"], h_in, positions,
                                   prefix_len=prefix_len)
        else:
            attn_out = L.attention_block(cfg, p["attn"], h_in, positions,
                                         kind=kind, prefix_len=prefix_len)
        x = _residual(cfg, x, attn_out)
        if enc_out is not None:
            hc = L.apply_norm(cfg, x, p["ln_cross"])
            x = x + _cross_attention(cfg, p["cross"], hc, enc_out)
    if not _has_ffn_tail(kind, uses_moe):
        return x, jnp.zeros((), jnp.float32)
    return _ffn_tail(cfg, p, x, uses_moe, moe_impl)


def _decode_layer(cfg, p, x, cache, pos, kind: str, uses_moe: bool, *,
                  moe_impl: str = "ragged"):
    """One-token layer decode. Returns (x, new_cache)."""
    new_cache = dict(cache)
    h_in = L.apply_norm(cfg, x, p["ln_attn"])
    if kind == "Y":
        attn_out, new_cache["attn"] = L.attention_decode(
            cfg, p["attn"], h_in, cache["attn"], pos,
            kind="W" if cfg.sliding_window else "F")
        mamba_out, new_cache["mamba"] = L.mamba_decode(cfg, p["mamba"], h_in,
                                                       cache["mamba"], pos)
        x = _residual(cfg, x, 0.5 * (attn_out + mamba_out))
    elif kind == "M":
        out, new_cache["mamba"] = L.mamba_decode(cfg, p["mamba"], h_in,
                                                 cache["mamba"], pos)
        x = _residual(cfg, x, out)
    else:
        if cfg.mla:
            attn_out, new_cache["attn"] = L.mla_decode(cfg, p["attn"], h_in,
                                                       cache["attn"], pos)
        else:
            attn_out, new_cache["attn"] = L.attention_decode(
                cfg, p["attn"], h_in, cache["attn"], pos, kind=kind)
        x = _residual(cfg, x, attn_out)
        if "cross_kv" in cache:
            hc = L.apply_norm(cfg, x, p["ln_cross"])
            b = x.shape[0]
            h, d = cfg.num_heads, cfg.head_dim
            q = (hc @ p["cross"]["wq"]).reshape(b, 1, h, d)
            ck, cv = cache["cross_kv"]
            out = L.dense_attention(q, ck, cv, mask_kind="full")
            x = x + out.reshape(b, 1, h * d) @ p["cross"]["wo"]
    if _has_ffn_tail(kind, uses_moe):
        x, _ = _ffn_tail(cfg, p, x, uses_moe, moe_impl)
    return x, new_cache


def _init_layer_cache(cfg, g: LayerGroup, batch, seq_len, dtype):
    cache: Dict[str, Any] = {}
    if g.kind in ("F", "W"):
        if cfg.mla:
            cache["attn"] = L.init_mla_cache(cfg, batch, seq_len, dtype)
        else:
            cache["attn"] = L.init_attention_cache(cfg, batch, seq_len, dtype, g.kind)
    if g.kind == "Y":
        cache["attn"] = L.init_attention_cache(cfg, batch, seq_len, dtype, "W"
                                               if cfg.sliding_window else "F")
        cache["mamba"] = L.init_mamba_cache(cfg, batch, dtype)
    if g.kind == "M":
        cache["mamba"] = L.init_mamba_cache(cfg, batch, dtype)
    if g.has_cross:
        hkv, d = cfg.num_kv_heads, cfg.head_dim
        nf = cfg.encoder.num_frames
        cache["cross_kv"] = (
            jnp.zeros((batch, nf, hkv, d), dtype),
            jnp.zeros((batch, nf, hkv, d), dtype),
        )
    return cache


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def init_stack(cfg, key, dtype) -> List[Any]:
    """Init per-group stacked layer params (leading axis = layer-in-group)."""
    groups = layer_groups(cfg)
    keys = jax.random.split(key, len(groups))
    stacked = []
    for g, gk in zip(groups, keys):
        lkeys = jax.random.split(gk, g.count)
        per_layer = [
            _init_layer(cfg, lkeys[i], g.kind, g.uses_moe, g.has_cross, dtype)
            for i in range(g.count)
        ]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer))
    return stacked


def apply_stack(cfg, stack, x, positions, *, prefix_len: int = 0, enc_out=None):
    """Full-sequence forward through all layer groups; returns (x, moe_aux)."""
    groups = layer_groups(cfg)
    total_aux = jnp.zeros((), jnp.float32)
    for g, params in zip(groups, stack):
        body = partial(_apply_layer, cfg, kind=g.kind, uses_moe=g.uses_moe,
                       prefix_len=prefix_len, enc_out=enc_out,
                       moe_impl=cfg.moe_impl)

        def scan_fn(carry, p_layer, _body=body):
            xc, aux = carry
            fn = _body
            if cfg.remat:
                fn = jax.checkpoint(
                    lambda pp, xx: _body(pp, xx, positions),
                    policy=jax.checkpoint_policies.nothing_saveable,
                )
                x_new, aux_l = fn(p_layer, xc)
            else:
                x_new, aux_l = _body(p_layer, xc, positions)
            return (x_new, aux + aux_l), None

        if g.count == 1:
            p0 = jax.tree.map(lambda a: a[0], params)
            (x, total_aux), _ = scan_fn((x, total_aux), p0)
        else:
            (x, total_aux), _ = uscan(scan_fn, (x, total_aux), params)
    return x, total_aux


def decode_stack(cfg, stack, x, caches, pos):
    """One-token decode through all groups; returns (x, new_caches)."""
    groups = layer_groups(cfg)
    new_caches = []
    for g, params, cache in zip(groups, stack, caches):
        def scan_fn(xc, pc, _g=g):
            p_layer, c_layer = pc
            x_new, c_new = _decode_layer(cfg, p_layer, xc, c_layer, pos,
                                         _g.kind, _g.uses_moe,
                                         moe_impl=cfg.moe_impl)
            return x_new, c_new

        if g.count == 1:
            p0 = jax.tree.map(lambda a: a[0], params)
            c0 = jax.tree.map(lambda a: a[0], cache)
            x, c_new = scan_fn(x, (p0, c0))
            new_caches.append(jax.tree.map(lambda a: a[None], c_new))
        else:
            x, c_new = uscan(scan_fn, x, (params, cache))
            new_caches.append(c_new)
    return x, new_caches


def init_cache(cfg, batch, seq_len, dtype):
    """Stacked per-group decode caches."""
    groups = layer_groups(cfg)
    caches = []
    for g in groups:
        per_layer = [_init_layer_cache(cfg, g, batch, seq_len, dtype)
                     for _ in range(g.count)]
        caches.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer))
    return caches


# ---------------------------------------------------------------------------
# encoder tower (whisper)
# ---------------------------------------------------------------------------


def init_encoder(cfg, key, dtype):
    enc = cfg.encoder
    keys = jax.random.split(key, enc.num_layers + 1)
    lyrs = [
        {
            "ln_attn": L.init_norm(cfg, jax.random.fold_in(keys[i], 0), cfg.d_model, dtype),
            "attn": L.init_attention(cfg, jax.random.fold_in(keys[i], 1), dtype),
            "ln_mlp": L.init_norm(cfg, jax.random.fold_in(keys[i], 2), cfg.d_model, dtype),
            "mlp": L.init_mlp(cfg, jax.random.fold_in(keys[i], 3), dtype),
        }
        for i in range(enc.num_layers)
    ]
    return {
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *lyrs),
        "ln_post": L.init_norm(cfg, keys[-1], cfg.d_model, dtype),
    }


def apply_encoder(cfg, p, frames):
    """frames: (B, T, E) stub conv-frontend embeddings -> (B, T, E)."""
    b, t, e = frames.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    x = frames

    def body(xc, p_layer):
        h_in = L.apply_norm(cfg, xc, p_layer["ln_attn"])
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (h_in @ p_layer["attn"]["wq"]).reshape(b, t, h, d)
        k = (h_in @ p_layer["attn"]["wk"]).reshape(b, t, hkv, d)
        v = (h_in @ p_layer["attn"]["wv"]).reshape(b, t, hkv, d)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        out = L.dense_attention(q, k, v, mask_kind="full")
        xc = xc + out.reshape(b, t, h * d) @ p_layer["attn"]["wo"]
        h2 = L.apply_norm(cfg, xc, p_layer["ln_mlp"])
        xc = xc + L.mlp_block(cfg, p_layer["mlp"], h2)
        return xc, None

    x, _ = uscan(body, x, p["layers"])
    return L.apply_norm(cfg, x, p["ln_post"])
