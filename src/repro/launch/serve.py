"""Serving driver: batched decode against a KV/SSM cache.

On CPU this runs a reduced config end-to-end (prompt ingestion via the
decode path, then generation); on the production mesh the same
``decode_step`` is what launch/dryrun.py lowers for decode_32k/long_500k.

``--checkpoint`` closes the federated train→serve loop (DESIGN.md §17):
the weights come from a ``launch/train.py`` checkpoint instead of a
fresh init, with the update space's merge (``apply`` folding the trained
LoRA/head deltas into the frozen base) done once at load time — the
decode path itself always sees ordinary full-shaped weights.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_serving_params
from repro.models import model as M
from repro.util import use_repo_compile_cache


def checkpoint_params(cfg, path: str):
    """Merged full parameters from a ``save_trainer`` checkpoint,
    validated leaf-by-leaf against ``cfg``'s init shapes (a silent
    arch/preset mismatch would decode garbage)."""
    params = load_serving_params(path)
    expect = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.key(0))
    got = jax.tree.map(lambda a: (jnp.shape(a), jnp.asarray(a).dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), expect)
    if got != want:
        raise SystemExit(
            f"checkpoint {path!r} does not match --arch/--preset: expected "
            f"{want}, got {got}")
    return jax.tree.map(jnp.asarray, params)


def generate(cfg, params, prompts: jnp.ndarray, max_new: int, *,
             temperature: float = 0.0, seed: int = 0):
    """prompts: (B, P) int32. Greedy (or sampled) continuation."""
    b, plen = prompts.shape
    total = plen + max_new
    cache = M.init_cache(cfg, b, total)
    step = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    key = jax.random.key(seed)
    logits = None
    # prompt ingestion (decode-path prefill keeps this driver exact; the
    # bulk prefill_step is the artifact lowered for prefill_32k)
    for i in range(plen):
        logits, cache = step(params, cache, prompts[:, i:i + 1],
                             jnp.full((b,), i, jnp.int32))
    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for i in range(max_new):
        out.append(tok)
        logits, cache = step(params, cache, tok,
                             jnp.full((b,), plen + i, jnp.int32))
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, -1] / temperature, axis=-1
            )[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--preset", default="reduced",
                    choices=["reduced", "100m", "full"])
    ap.add_argument("--checkpoint", default="",
                    help="serve a launch/train.py checkpoint: deltas of a "
                         "non-full update space (lora/head_only) are "
                         "merged into the frozen base at load time "
                         "('' = fresh random init)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    use_repo_compile_cache()

    from repro.launch.train import preset_config

    cfg = preset_config(args.arch, args.preset)
    if cfg.encoder is not None or cfg.num_prefix_tokens:
        raise SystemExit("serve driver targets text-only archs; audio/vlm "
                         "decode is exercised by the dry-run")
    if args.checkpoint:
        params = checkpoint_params(cfg, args.checkpoint)
        print(f"serving merged checkpoint {args.checkpoint}")
    else:
        params = M.init_params(cfg, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    out = generate(cfg, params, prompts, args.max_new,
                   temperature=args.temperature)
    dt = time.time() - t0
    ntok = args.batch * args.max_new
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({ntok/dt:.1f} tok/s incl. compile)")
    print(np.asarray(out)[:, :16])


if __name__ == "__main__":
    main()
