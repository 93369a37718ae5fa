"""Public model API: init / loss_fn / forward / prefill / decode_step /
input_specs — everything the federated core and the launchers consume.

The federated algorithms (repro.core) only need ``init`` and a
``loss_fn(params, batch) -> (scalar, metrics)``; everything else here is
serving/dry-run substrate.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.models import layers as L
from repro.models import transformer as T


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg, key) -> Dict[str, Any]:
    dtype = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    params: Dict[str, Any] = {
        "embed": L.embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        "ln_final": L.init_norm(cfg, ks[1], cfg.d_model, dtype),
        "layers": T.init_stack(cfg, ks[2], dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(ks[3], (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.encoder is not None:
        params["encoder"] = T.init_encoder(cfg, ks[4], dtype)
    if cfg.num_prefix_tokens:
        # projector stub for the modality prefix (identity-ish linear)
        params["prefix_proj"] = L.dense_init(ks[4], (cfg.d_model, cfg.d_model), dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens):
    from repro.dist.activations import constrain_batch_dim

    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return constrain_batch_dim(x.astype(_dtype(cfg.compute_dtype)))


def _unembed(cfg, params, x):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T.astype(x.dtype)
    else:
        logits = x @ params["unembed"]
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def forward_hidden(cfg, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward up to the final norm -> (hidden (B,S,E), aux)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    enc_out = None
    prefix_len = 0
    if cfg.encoder is not None:
        enc_out = T.apply_encoder(cfg, params["encoder"],
                                  batch["frames"].astype(x.dtype))
    if cfg.num_prefix_tokens:
        pre = batch["patches"].astype(x.dtype) @ params["prefix_proj"]
        x = jnp.concatenate([pre, x], axis=1)
        prefix_len = cfg.num_prefix_tokens
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.named_scope(spans.MODEL_BLOCKS):
        x, aux = T.apply_stack(cfg, params["layers"], x, positions,
                               prefix_len=prefix_len, enc_out=enc_out)
    x = L.apply_norm(cfg, x, params["ln_final"])
    if cfg.num_prefix_tokens:
        x = x[:, cfg.num_prefix_tokens:]
    return x, aux


def forward(cfg, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward -> (logits (B,S,V), moe_aux).

    batch: {"tokens": (B, S_text)} plus optional "frames" (audio) or
    "patches" (vlm) stub-frontend embeddings (B, P, E).
    """
    x, aux = forward_hidden(cfg, params, batch)
    return _unembed(cfg, params, x), aux


def _chunked_ce(cfg, params, hidden, labels, mask):
    """Streaming softmax cross-entropy over vocab chunks: never builds the
    (tokens, V) fp32 logits. Online logsumexp; gold logit accumulated from
    the chunk that owns each label. Each chunk is remat'd so the backward
    pass recomputes its logits instead of saving them."""
    from repro.util import uscan

    chunk = cfg.loss_chunk_vocab
    v = cfg.vocab_size
    w = params["embed"] if cfg.tie_embeddings else params["unembed"].T
    # pad vocab to a chunk multiple
    pad = (-v) % chunk
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    nc = w.shape[0] // chunk
    wc = w.reshape(nc, chunk, w.shape[-1])
    # each chunk must stay model-sharded on its vocab slice — otherwise the
    # scan walks the sharded vocab axis and every step gathers + replicates
    # the unembed matmul on all devices (observed: 5.7x compute, HC3 iter 1)
    from repro.dist.activations import constrain_spec

    wc = constrain_spec(wc, (None, "model", None))
    b, s, e = hidden.shape
    m0 = jnp.full((b, s), -1e30, jnp.float32)
    s0 = jnp.zeros((b, s), jnp.float32)
    g0 = jnp.zeros((b, s), jnp.float32)

    def body(carry, inp):
        m, acc, gold = carry
        w_chunk, idx = inp
        logits = (hidden @ w_chunk.T).astype(jnp.float32)  # (B,S,C)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        lo = idx * chunk
        valid = (lo + jnp.arange(chunk))[None, None, :] < v
        logits = jnp.where(valid, logits, -1e30)
        m_new = jnp.maximum(m, logits.max(-1))
        acc = acc * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[..., None]).sum(-1)
        rel = labels - lo
        in_chunk = (rel >= 0) & (rel < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(rel, 0, chunk - 1)[..., None], axis=-1)[..., 0]
        gold = gold + jnp.where(in_chunk, picked, 0.0)
        return (m_new, acc, gold), None

    body = jax.checkpoint(body)
    (m, acc, gold), _ = uscan(body, (m0, s0, g0), (wc, jnp.arange(nc)))
    logz = m + jnp.log(jnp.maximum(acc, 1e-30))
    nll = (logz - gold) * mask
    return nll


def loss_fn(cfg, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (+ MoE aux). labels < 0 are masked."""
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels = jnp.maximum(labels, 0)
    denom = jnp.maximum(mask.sum(), 1.0)
    hidden, aux = forward_hidden(cfg, params, batch)
    with jax.named_scope(spans.MODEL_HEAD):
        if cfg.loss_chunk_vocab:
            nll = _chunked_ce(cfg, params, hidden, labels, mask)
        else:
            logits = _unembed(cfg, params, hidden).astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, labels[..., None], axis=-1)[..., 0]
            nll = (logz - gold) * mask
        loss = nll.sum() / denom
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_coef * aux
    return loss, {"loss": loss, "ntokens": denom}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(cfg, params, batch):
    """Prefill forward (logits for the full prompt). Serving substrate: the
    dry-run lowers this for the prefill_32k shape. (Cache writeback during
    prefill is handled by the serve driver chunk-wise; for the assigned
    shapes the compiled artifact of interest is the prompt forward.)"""
    logits, _ = forward(cfg, params, batch)
    return logits


def init_cache(cfg, batch_size: int, seq_len: int):
    dtype = _dtype(cfg.compute_dtype)
    return {
        "layers": T.init_cache(cfg, batch_size, seq_len, dtype),
        "enc_out": (
            jnp.zeros((batch_size, cfg.encoder.num_frames, cfg.d_model), dtype)
            if cfg.encoder is not None
            else None
        ),
    }


def populate_encoder_cache(cfg, params, cache, frames):
    """Enc-dec serving: run the encoder once per request and write the
    per-layer cross-attention K/V into the decode cache."""
    assert cfg.encoder is not None
    enc_out = T.apply_encoder(cfg, params["encoder"],
                              frames.astype(_dtype(cfg.compute_dtype)))
    b, t, _ = enc_out.shape
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    new_layers = []
    groups = T.layer_groups(cfg)
    for g, params_g, cache_g in zip(groups, params["layers"],
                                    cache["layers"]):
        def fill(p_layer):
            ck = (enc_out @ p_layer["cross"]["wk"]).reshape(b, t, hkv, d)
            cv = (enc_out @ p_layer["cross"]["wv"]).reshape(b, t, hkv, d)
            return ck, cv

        kv = jax.vmap(fill)(params_g)  # stacked over the group
        cg = dict(cache_g)
        cg["cross_kv"] = kv
        new_layers.append(cg)
    return {"layers": new_layers, "enc_out": enc_out}


def decode_step(cfg, params, cache, tokens, pos):
    """One decode step. tokens: (B, 1) int32; pos: (B,) int32.

    Returns (logits (B, 1, V), new_cache).
    """
    x = _embed(cfg, params, tokens)
    x, new_layer_caches = T.decode_stack(cfg, params["layers"], x,
                                         cache["layers"], pos)
    x = L.apply_norm(cfg, x, params["ln_final"])
    logits = _unembed(cfg, params, x)
    return logits, {"layers": new_layer_caches, "enc_out": cache.get("enc_out")}


# ---------------------------------------------------------------------------
# dry-run input specs
# ---------------------------------------------------------------------------


def input_specs(cfg, shape, round_spec=None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input for (cfg, shape).

    For kind=="train" the structs describe one federated round's batch laid
    out as (S_clients, K_steps, b_local, seq); for prefill/decode the
    serving request batch.
    """
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    cdt = _dtype(cfg.compute_dtype)
    text_len = shape.seq_len - cfg.num_prefix_tokens
    if shape.kind == "train":
        assert round_spec is not None
        s, k, bl = round_spec.num_sampled, round_spec.local_steps, round_spec.local_batch
        assert s * k * bl == shape.global_batch, (s, k, bl, shape.global_batch)
        specs = {
            "tokens": sds((s, k, bl, text_len), i32),
            "labels": sds((s, k, bl, text_len), i32),
        }
        if cfg.encoder is not None:
            specs["frames"] = sds((s, k, bl, cfg.encoder.num_frames, cfg.d_model), cdt)
        if cfg.num_prefix_tokens:
            specs["patches"] = sds((s, k, bl, cfg.num_prefix_tokens, cfg.d_model), cdt)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": sds((shape.global_batch, text_len), i32)}
        if cfg.encoder is not None:
            specs["frames"] = sds((shape.global_batch, cfg.encoder.num_frames,
                                   cfg.d_model), cdt)
        if cfg.num_prefix_tokens:
            specs["patches"] = sds((shape.global_batch, cfg.num_prefix_tokens,
                                    cfg.d_model), cdt)
        return specs
    # decode: one new token against a seq_len-sized cache
    b = shape.global_batch
    cache = jax.eval_shape(partial(init_cache, cfg, b, shape.seq_len))
    return {
        "tokens": sds((b, 1), i32),
        "pos": sds((b,), i32),
        "cache": cache,
    }


# ---------------------------------------------------------------------------
# parameter counting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------


def count_params_analytic(cfg) -> int:
    """Total parameter count via eval_shape (no allocation)."""
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def count_active_params(cfg) -> int:
    """Active params per token (MoE: routed experts count top_k/E)."""
    total = count_params_analytic(cfg)
    if cfg.moe is None:
        return total
    mo = cfg.moe
    n_moe_layers = sum(cfg.layer_uses_moe(i) for i in range(cfg.num_layers))
    per_expert = 3 * cfg.d_model * mo.expert_d_ff
    routed = n_moe_layers * mo.num_experts * per_expert
    active_routed = n_moe_layers * mo.top_k * per_expert
    return total - routed + active_routed
