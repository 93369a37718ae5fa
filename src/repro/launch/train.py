"""Federated LM training driver (runs on CPU at reduced scale; the same
code path jit-lowers onto the production mesh via launch/dryrun.py).

Example (≈100M-param model, a few hundred rounds):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --preset 100m \
      --algorithm scaffold --rounds 200
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial

import jax
import numpy as np

from repro.checkpoint import load_trainer, save_trainer
from repro.configs import get_config, get_reduced
from repro.configs.base import FedRoundSpec
from repro.core import (
    FederatedTrainer,
    algorithm_names,
    availability_names,
    compressor_names,
    local_solver_names,
    privatizer_names,
    server_optimizer_names,
    staleness_weighting_names,
    store_backend_names,
    update_space_names,
)
from repro.optim.schedules import schedule_names
from repro.data import SyntheticLMFederated
from repro.models import model as M
from repro.util import use_repo_compile_cache


def preset_config(arch: str, preset: str):
    cfg = get_config(arch)
    if preset == "full":
        return cfg
    if preset == "reduced":
        return get_reduced(arch)
    if preset == "100m":
        # ~100M-param member of the same family (129M for the llama layout)
        return dataclasses.replace(
            get_reduced(arch),
            num_layers=12,
            d_model=768,
            num_heads=12,
            num_kv_heads=max(1, min(4, cfg.num_kv_heads)),
            head_dim=64,
            d_ff=3072,
            vocab_size=32768,
            param_dtype="float32",
            compute_dtype="float32",
        )
    raise ValueError(preset)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--preset", default="reduced",
                    choices=["reduced", "100m", "full"])
    ap.add_argument("--algorithm", default="scaffold",
                    choices=list(algorithm_names()))
    ap.add_argument("--server-opt", default="",
                    choices=[""] + list(server_optimizer_names()),
                    help="server optimizer ('' = algorithm default)")
    ap.add_argument("--server-momentum", type=float, default=0.0)
    ap.add_argument("--local-solver", default="sgd",
                    choices=list(local_solver_names()),
                    help="client inner optimizer (stateful solvers persist "
                         "per-client slots in the client store; "
                         "DESIGN.md §12)")
    ap.add_argument("--local-momentum", type=float, default=0.9,
                    help="heavy-ball beta of the momentum local solver / "
                         "beta1 of the adam local solver")
    ap.add_argument("--local-beta2", type=float, default=0.99,
                    help="second-moment decay of the adam local solver")
    ap.add_argument("--eta-l-schedule", default="",
                    choices=[""] + list(schedule_names()),
                    help="per-local-step eta_l schedule (sgd_sched solver "
                         "only)")
    ap.add_argument("--use-megakernel", action="store_true",
                    help="fuse the whole K-step local loop into one Pallas "
                         "kernel per dtype group per round where the "
                         "grad/solver combination supports it; unsupported "
                         "combos fall back per-step with a "
                         "megakernel_fallback_reason in round metrics "
                         "(DESIGN.md §15)")
    ap.add_argument("--list-registries", action="store_true",
                    help="print the nine strategy registries (algorithms, "
                         "server optimizers, compressors, local solvers, "
                         "store backends, availability models, staleness "
                         "weightings, privatizers, update spaces) and exit")
    ap.add_argument("--update-space", default="",
                    choices=[""] + list(update_space_names()),
                    help="parameter-efficient update space ('' = full): "
                         "the engine trains a delta pytree (lora adapters / "
                         "head_only subtrees) against frozen base weights — "
                         "c, c_i, residuals, store rows and bytes_up/down "
                         "all shrink to delta shape (DESIGN.md §17)")
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="adapter rank r of --update-space lora "
                         "(required there, rejected elsewhere)")
    ap.add_argument("--lora-alpha", type=float, default=0.0,
                    help="lora scaling alpha (0 = alpha := rank, i.e. "
                         "scale 1)")
    ap.add_argument("--lora-targets", default="",
                    help="comma-separated fnmatch patterns over parameter "
                         "paths selecting the adapted/trained leaves "
                         "('' = the dense-matmul defaults for lora; "
                         "required for head_only)")
    ap.add_argument("--weighted", action="store_true",
                    help="paper §2 weighted aggregation by client sizes")
    ap.add_argument("--compress", default="none",
                    choices=list(compressor_names()),
                    help="uplink delta codec (error-feedback residuals "
                         "ride the client store; DESIGN.md §11)")
    ap.add_argument("--compress-k", type=int, default=32,
                    help="kept coordinates per leaf for topk_ef/randk_ef")
    ap.add_argument("--compress-downlink", default="none",
                    choices=list(compressor_names()),
                    help="codec for the server->client (x, c) broadcast")
    ap.add_argument("--privatizer", default="none",
                    choices=list(privatizer_names()),
                    help="differential-privacy mechanism: L2-clip every "
                         "client delta and add Gaussian noise at the "
                         "server (server_gauss) or on each client "
                         "(distributed_gauss); the dp_epsilon accountant "
                         "rides every round's metrics (DESIGN.md §16)")
    ap.add_argument("--clip-norm", type=float, default=0.0,
                    help="per-update L2 sensitivity bound C of the DP "
                         "mechanism (required when --privatizer != none)")
    ap.add_argument("--noise-multiplier", type=float, default=0.0,
                    help="Gaussian noise multiplier z: the aggregate-mean "
                         "noise std is C*z/S (required when "
                         "--privatizer != none)")
    ap.add_argument("--dp-delta", type=float, default=1e-5,
                    help="delta of the (epsilon, delta) accountant")
    ap.add_argument("--pipeline-depth", type=int, default=0)
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="async buffered-aggregation engine: aggregate once "
                         "this many client updates land (0 = synchronous; "
                         "DESIGN.md §14)")
    ap.add_argument("--max-inflight", type=int, default=0,
                    help="async concurrency cap K: dispatches kept in "
                         "flight (0 = num_sampled)")
    ap.add_argument("--availability", default="always_on",
                    choices=list(availability_names()),
                    help="async client availability model (trace-driven, "
                         "seeded, wall-clock-free)")
    ap.add_argument("--availability-seed", type=int, default=0,
                    help="seed of the availability model's latency/dropout "
                         "draws (independent of --seed)")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-dispatch death probability of the uniform/"
                         "lognormal availability models")
    ap.add_argument("--latency-sigma", type=float, default=1.0,
                    help="lognormal availability: log-space sigma of the "
                         "per-dispatch latency (the straggler-tail knob)")
    ap.add_argument("--availability-trace", default="",
                    help="replay a recorded availability trace from this "
                         "JSON path (--availability trace)")
    ap.add_argument("--staleness-weighting", default="constant",
                    choices=list(staleness_weighting_names()),
                    help="async staleness down-weighting of buffered "
                         "updates (applied before the server optimizer)")
    ap.add_argument("--staleness-alpha", type=float, default=0.5,
                    help="polynomial staleness weighting: 1/(1+tau)^alpha")
    ap.add_argument("--staleness-cutoff", type=float, default=10.0,
                    help="cutoff staleness weighting: drop updates staler "
                         "than this many versions")
    ap.add_argument("--scan-rounds", type=int, default=0,
                    help="scanned-engine chunk size: run rounds on device "
                         "in lax.scan chunks of up to this many (0 = host "
                         "loop; DESIGN.md §10)")
    ap.add_argument("--store", default="dense",
                    choices=["dense", "tiered"],
                    help="client-store tier: 'tiered' keeps the (N, ...) "
                         "population host-side and gathers only cohort "
                         "rows to the device (DESIGN.md §13)")
    ap.add_argument("--store-backend", default="",
                    help="population-store backend ('' = dense RAM; also: "
                         "memmap, sharded — see --list-registries)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="tiered-store gather-ahead depth: chunks of "
                         "population rows prefetched while the device "
                         "computes")
    ap.add_argument("--resume", default="",
                    help="checkpoint to restore before training")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--sampled", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--local-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--eta-l", type=float, default=0.02)
    ap.add_argument("--eta-g", type=float, default=1.0)
    ap.add_argument("--heterogeneity", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args(argv)

    if args.list_registries:
        for title, names in (
            ("algorithms", algorithm_names()),
            ("server_optimizers", server_optimizer_names()),
            ("compressors", compressor_names()),
            ("local_solvers", local_solver_names()),
            ("store_backends", store_backend_names()),
            ("availability_models", availability_names()),
            ("staleness_weightings", staleness_weighting_names()),
            ("privatizers", privatizer_names()),
            ("update_spaces", update_space_names()),
        ):
            print(f"{title}: {' '.join(names)}")
        return None

    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())} "
          f"compile_cache={use_repo_compile_cache()}")
    cfg = preset_config(args.arch, args.preset)
    spec = FedRoundSpec(
        algorithm=args.algorithm,
        num_clients=args.clients,
        num_sampled=args.sampled,
        local_steps=args.local_steps,
        local_batch=args.local_batch,
        eta_l=args.eta_l,
        eta_g=args.eta_g,
        server_optimizer=args.server_opt,
        server_momentum=args.server_momentum,
        local_solver=args.local_solver,
        local_momentum=args.local_momentum,
        local_beta2=args.local_beta2,
        eta_l_schedule=args.eta_l_schedule,
        use_megakernel=args.use_megakernel,
        weighted_aggregation=args.weighted,
        compress=args.compress,
        compress_k=args.compress_k,
        compress_downlink=args.compress_downlink,
        privatizer=args.privatizer,
        clip_norm=args.clip_norm,
        noise_multiplier=args.noise_multiplier,
        dp_delta=args.dp_delta,
        update_space=args.update_space,
        lora_rank=args.lora_rank,
        lora_alpha=args.lora_alpha,
        update_targets=args.lora_targets,
    )
    data = SyntheticLMFederated(args.clients, cfg.vocab_size, args.seq_len,
                                heterogeneity=args.heterogeneity,
                                seed=args.seed)
    n_params = M.count_params_analytic(cfg)
    print(f"arch={cfg.name} preset={args.preset} params={n_params/1e6:.1f}M "
          f"algo={args.algorithm} N={args.clients} S={args.sampled} "
          f"K={args.local_steps} b={args.local_batch}")

    availability_kwargs = {}
    if args.availability == "trace":
        availability_kwargs["trace"] = args.availability_trace
    elif args.availability != "always_on":
        availability_kwargs.update(seed=args.availability_seed,
                                   dropout=args.dropout)
        if args.availability == "lognormal":
            availability_kwargs["sigma"] = args.latency_sigma
    staleness_kwargs = {}
    if args.staleness_weighting == "polynomial":
        staleness_kwargs["alpha"] = args.staleness_alpha
    elif args.staleness_weighting == "cutoff":
        staleness_kwargs["cutoff"] = args.staleness_cutoff
    trainer = FederatedTrainer(
        partial(M.loss_fn, cfg), partial(M.init_params, cfg), spec, data,
        seed=args.seed, pipeline_depth=args.pipeline_depth,
        scan_rounds=args.scan_rounds, store=args.store,
        store_backend=args.store_backend,
        prefetch_depth=args.prefetch_depth,
        async_buffer=args.async_buffer, max_inflight=args.max_inflight,
        availability=args.availability,
        availability_kwargs=availability_kwargs,
        staleness_weighting=args.staleness_weighting,
        staleness_kwargs=staleness_kwargs,
    )
    if trainer.update_space.trains_subset:
        n_train = trainer.update_space.num_params(trainer.server.x)
        print(f"update space: {trainer.update_space.name} — "
              f"{n_train/1e6:.3f}M trainable of {n_params/1e6:.1f}M "
              f"({n_params/max(n_train, 1):.0f}x fewer), per-round "
              f"up={trainer._comm_bytes['bytes_up']/1e6:.2f}MB")
    if trainer.async_active:
        eng = trainer.async_engine
        print(f"async engine: aggregate {eng.buffer_size} of "
              f"{eng.max_inflight} in flight, availability="
              f"{args.availability}, staleness={args.staleness_weighting}")
    if trainer.scan_active:
        print(f"scanned engine: on-device chunks of <= {args.scan_rounds} "
              f"rounds")
    if args.privatizer != "none":
        eps = trainer.privatizer.epsilon(spec, args.rounds)
        print(f"privatizer: {args.privatizer} clip={args.clip_norm} "
              f"z={args.noise_multiplier} -> epsilon="
              f"{eps:.3f} at delta={args.dp_delta} after "
              f"{args.rounds} rounds")
    if args.use_megakernel:
        reason = trainer.megakernel_fallback_reason
        print("megakernel: fused K-step local loop" if reason == ""
              else f"megakernel: per-step fallback ({reason})")
    if args.store == "tiered":
        print(f"tiered store: population host-side "
              f"({args.store_backend or 'dense'} backend), device peak "
              f"{trainer.client_store_device_bytes()/1e6:.2f}MB of client "
              f"state (gather-ahead depth {args.prefetch_depth})")
    if args.resume:
        load_trainer(args.resume, trainer)
        print(f"resumed from {args.resume} at round {trainer.round_idx}")
    t0 = time.time()
    eval_rng = np.random.default_rng(args.seed + 7)
    eval_batch = data.eval_batch(8, eval_rng)
    eval_loss = jax.jit(lambda p, b: M.loss_fn(cfg, p, b)[0])
    # log after round 1, then at every log_every boundary; between logs the
    # scanned engine runs whole chunks, the host loop runs single rounds
    done = 0
    while done < args.rounds:
        target = (1 if done == 0 else
                  min(args.rounds, (done // args.log_every + 1)
                      * args.log_every))
        trainer.run(target - done)
        done = target
        m = trainer.history[-1]
        ev = float(eval_loss(trainer.eval_params(), eval_batch))
        print(f"round {done:4d} loss={m['loss']:.4f} eval={ev:.4f} "
              f"drift={m['drift']:.3e} "
              f"up={m['bytes_up']/1e6:.2f}MB down={m['bytes_down']/1e6:.2f}MB "
              f"({time.time()-t0:.1f}s)")
    if args.checkpoint:
        save_trainer(args.checkpoint, trainer)
        print("checkpoint saved to", args.checkpoint)
    return trainer


if __name__ == "__main__":
    main()
