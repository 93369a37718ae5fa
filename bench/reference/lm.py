"""Plain float32 reference of the benchmark's two layer kinds and the loss.

Written from the published layer equations, in ``jax.numpy`` with no
kernels, and run under ``jax.default_matmul_precision("highest")`` (the
caller sets it). It imports nothing of the program; it reads the program's
parameter layout (a dict per layer group, leaves stacked on a leading
layer axis) as data, and its sizes from the ``shapes`` block of the
benchmark's configuration file.

  Y  (hymba, arXiv:2411.13676): h = RMSNorm(x);
     x += 0.5 * (SWA-GQA-attention(h) + SSD(h)); x += SwiGLU(RMSNorm(x)).
     Departures kept from the program: sliding-window attention in every
     layer, no meta tokens, no KV sharing, SSD heads (configuration file,
     ``assumed``).
  M  (Mamba-2, arXiv:2405.21060): x += SSD-block(RMSNorm(x)).

The SSD scan is y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r a)
dt_s x_s + D x_t, computed in chunks as the paper's minimal listing
(section 7) does: the quadratic (masked-attention) form inside each chunk,
a recurrence over the chunks' states between them. ``ssd_recurrent`` is
the same map one position at a time; the tests hold the two together.

Each LoRA target is applied as the LoRA paper writes it,
h W + (alpha / r) (h A) B, with the frozen W never merged, so the
backward pass forms no dense gradient of W.

``quant`` (a dtype or None) rounds every matmul operand to that dtype on
the way forward and passes gradients straight through: the control of the
benchmark's ``correct`` check, computed below the configuration's
precision. None is the float32 reference.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

f32 = jnp.float32
EPS = 1e-6


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, dtype):
    return x.astype(dtype).astype(x.dtype)


def _round_fwd(x, dtype):
    return _round(x, dtype), None


def _round_bwd(dtype, _, g):
    return (g,)


_round.defvjp(_round_fwd, _round_bwd)


def _q(x, quant):
    return x if quant is None else _round(x, quant)


def mm(x, w, quant=None):
    return jnp.matmul(_q(x, quant), _q(w, quant))


def rms_norm(x, w):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + w.astype(f32))


def rope(x, positions, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=f32) / d))
    ang = positions[:, None].astype(f32) * freqs  # (T, d/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def linear(p, d, scale, quant):
    """``lin(path, h)``: h W + scale (h A) B for the layer weight W at the
    dotted ``path`` of ``p``, with the factors ``d[path]`` where the path
    is a LoRA target."""
    def lin(path, h):
        w = p
        for part in path.split("."):
            w = w[part]
        out = mm(h, w, quant)
        if path in d:
            out = out + scale * mm(mm(h, d[path]["A"], quant), d[path]["B"], quant)
        return out
    return lin


def attention(s, lin, h, quant):
    """Causal sliding-window GQA with RoPE; q head i reads kv head
    i // (n_heads / n_kv_heads)."""
    b, t, _ = h.shape
    nh, nkv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    pos = jnp.arange(t)
    q = rope(lin("attn.wq", h).reshape(b, t, nh, hd), pos, s["rope_theta"])
    k = rope(lin("attn.wk", h).reshape(b, t, nkv, hd), pos, s["rope_theta"])
    v = lin("attn.wv", h).reshape(b, t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant))
    scores = scores / math.sqrt(hd)
    rel = pos[:, None] - pos[None, :]
    band = (rel >= 0) & (rel < s["window"])
    scores = jnp.where(band[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", _q(probs, quant), _q(v, quant))
    return lin("attn.wo", out.reshape(b, t, nh * hd))


def segsum(x):
    """(..., Q) -> (..., Q, Q): sum_{r=s+1..t} x_r at [t, s], -inf above
    the diagonal."""
    cum = jnp.cumsum(x, axis=-1)
    q = x.shape[-1]
    seg = cum[..., :, None] - cum[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg, -jnp.inf)


def ssd_chunked(xh, dt, a, bm, cm, chunk: int = 256):
    """xh (B,T,H,P), dt (B,T,H), a (H,), bm/cm (B,T,N) -> y (B,T,H,P),
    in chunks of the largest divisor of T up to ``chunk``."""
    b, t, h, p = xh.shape
    q = max(d for d in range(1, min(t, chunk) + 1) if t % d == 0)
    c = t // q
    x = (xh * dt[..., None]).reshape(b, c, q, h, p)
    bq, cq = bm.reshape(b, c, q, -1), cm.reshape(b, c, q, -1)
    da = jnp.moveaxis((dt * a).reshape(b, c, q, h), 3, 1)  # (B,H,C,Q)
    cum = jnp.cumsum(da, axis=-1)
    # within each chunk: the quadratic form
    m = jnp.einsum("bcln,bcsn->bcls", cq, bq)[:, None] * jnp.exp(segsum(da))
    y = jnp.einsum("bhcls,bcshp->bclhp", m, x)
    # each chunk's end state, then the recurrence over chunks
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", bq,
                        jnp.exp(cum[..., -1:] - cum), x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay = jnp.exp(segsum(jnp.pad(cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay, states)[:, :-1]
    y = y + jnp.einsum("bcln,bchpn,bhcl->bclhp", cq, states, jnp.exp(cum))
    return y.reshape(b, t, h, p)


def ssd_recurrent(xh, dt, a, bm, cm):
    """The same map as ``ssd_chunked``, one position at a time:
    S_t = exp(dt_t a) S_{t-1} + dt_t B_t x_t^T;  y_t = C_t S_t."""
    b, _, h, p = xh.shape
    n = bm.shape[-1]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + jnp.einsum("bh,bn,bhp->bhnp", dt_t, b_t, x_t))
        return state, jnp.einsum("bn,bhnp->bhp", c_t, state)

    seq = (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(dt, 1, 0),
           jnp.moveaxis(bm, 1, 0), jnp.moveaxis(cm, 1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, n, p), f32), seq)
    return jnp.moveaxis(ys, 0, 1)


def mamba(s, p, lin, h, quant, ssd=ssd_chunked):
    """Mamba-2 block: in-projection, depthwise causal conv + SiLU, SSD,
    gated RMSNorm, out-projection (one B/C group)."""
    b, t, d = h.shape
    di = s["ssm_expand"] * d
    n, hp = s["ssm_d_state"], s["ssm_head_dim"]
    nh = di // hp
    proj = lin("mamba.w_in", h)
    z, xin, bc, dt = (proj[..., :di], proj[..., di:2 * di],
                      proj[..., 2 * di:2 * di + 2 * n], proj[..., 2 * di + 2 * n:])
    conv_in = jnp.concatenate([xin, bc], axis=-1)
    k = p["conv_w"].shape[0]
    padded = jnp.pad(conv_in, ((0, 0), (k - 1, 0), (0, 0)))
    w = p["conv_w"].astype(f32)
    conv = sum(padded[:, i:i + t] * w[i] for i in range(k)) + p["conv_b"].astype(f32)
    conv = jax.nn.silu(conv)
    xin, bm, cm = conv[..., :di], conv[..., di:di + n], conv[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
    a = -jnp.exp(p["a_log"].astype(f32))
    xh = xin.reshape(b, t, nh, hp)
    y = ssd(_q(xh, quant), dt, a, _q(bm, quant), _q(cm, quant))
    y = y + xh * p["d_skip"].astype(f32)[None, None, :, None]
    y = rms_norm(y.reshape(b, t, di) * jax.nn.silu(z), p["out_norm"]["scale"])
    return lin("mamba.w_out", y)


def mlp(lin, h):
    return lin("mlp.w_down", jax.nn.silu(lin("mlp.w_gate", h)) * lin("mlp.w_up", h))


def layer(s, p, d, scale, x, quant, ssd=ssd_chunked):
    """One layer of kind ``s["layer_kind"]`` on the residual stream x;
    ``d`` maps "attn.wq"-style paths to the layer's LoRA factors."""
    p = jax.tree.map(lambda w: w.astype(f32), p)
    lin = linear(p, d, scale, quant)
    if s["layer_kind"] == "Y":
        h = rms_norm(x, p["ln_attn"]["scale"])
        x = x + 0.5 * (attention(s, lin, h, quant)
                       + mamba(s, p["mamba"], lin, h, quant, ssd))
        return x + mlp(lin, rms_norm(x, p["ln_mlp"]["scale"]))
    if s["layer_kind"] == "M":
        return x + mamba(s, p["mamba"], lin, rms_norm(x, p["ln_attn"]["scale"]),
                         quant, ssd)
    raise ValueError(s["layer_kind"])


def split_deltas(deltas):
    """``{"layers.0.attn.wq": f}`` -> ``({"attn.wq": f}, top-level)``:
    the per-layer factors of the stacked group and any others."""
    per_layer, other = {}, {}
    for path, fac in deltas.items():
        parts = path.split(".")
        if parts[0] == "layers":
            assert parts[1] == "0", f"one layer group expected, got {path}"
            per_layer[".".join(parts[2:])] = fac
        else:
            other[path] = fac
    return per_layer, other


def loss(s, base, deltas, batch, scale, quant=None, ssd=ssd_chunked):
    """Mean next-token cross-entropy of the adapted model on ``batch``
    (tokens/labels (B, T)). One scan over the layers, each recomputed in
    the backward pass, so only the residual stream is kept per layer."""
    per_layer, other = split_deltas(deltas)
    assert not other, f"LoRA on top-level leaves is not modelled: {list(other)}"
    (group,) = base["layers"]
    x = base["embed"].astype(f32)[batch["tokens"]]

    @jax.checkpoint
    def body(x, lp_ld):
        lp, ld = lp_ld
        return layer(s, lp, ld, scale, x, quant, ssd), None

    x, _ = jax.lax.scan(body, x, (group, per_layer))
    x = rms_norm(x, base["ln_final"]["scale"])
    logits = mm(x, base["embed"].astype(f32).T, quant)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)
