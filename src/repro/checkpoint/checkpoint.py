"""Checkpointing: the full typed trainer state — ``ServerState`` (x, c,
server-optimizer slots), the per-client host stores (control variates +
uplink error-feedback residuals + stateful local-solver slots), and the
host RNGs (sampler + data) — as flat .npz archives (offline-friendly).

Pytree structure is recorded as the sorted flattened key-paths so restore
round-trips arbitrary nested dicts/lists of arrays. The host RNG states
are JSON-serializable (numpy Generator bit_generator.state) and ride in
the metadata, so a restored trainer re-prepares the exact same client
samples and data batches: the resumed trajectory is bit-for-bit the
unbroken run's (tests/test_checkpoint_roundtrip.py). For a pipelined
trainer the recorded RNG states are rewound past un-executed prefetched
rounds (``FederatedTrainer.host_rng_state``), so resuming is exact there
too.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

import jax
import numpy as np


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def save_checkpoint(path: str, tree, extra: Dict[str, Any] | None = None):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    flat = _flatten(tree)
    meta = {"keys": sorted(flat), "extra": extra or {}}
    np.savez(path, __meta__=json.dumps(meta), **flat)


def _read_checkpoint(path: str) -> Tuple[Dict[str, np.ndarray],
                                         Dict[str, Any]]:
    """The archive's raw flat arrays + extra metadata (no template yet —
    callers whose template depends on the metadata, like the async
    engine's variable-length pending state, read this first)."""
    with np.load(path if path.endswith(".npz") else path + ".npz",
                 allow_pickle=False) as data:
        meta = json.loads(str(data["__meta__"]))
        flat = {k: data[k] for k in meta["keys"]}
    return flat, meta["extra"]


def _unflatten_into(flat: Dict[str, np.ndarray], template):
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path_elems, leaf in paths:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path_elems
        )
        arr = flat[key]
        assert arr.shape == tuple(leaf.shape), (key, arr.shape, leaf.shape)
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def load_checkpoint(path: str, template) -> Tuple[Any, Dict[str, Any]]:
    """Restore into the structure of ``template``."""
    flat, extra = _read_checkpoint(path)
    return _unflatten_into(flat, template), extra


def _trainer_tree(trainer) -> Dict[str, Any]:
    """The trainer's array state as a plain dict (stable checkpoint keys,
    independent of the registered-dataclass pytree paths). A scan-mode
    trainer first mirrors its device-resident client store into the host
    store, so the same keys cover all three execution modes."""
    trainer.sync_host_store()
    all_ids = np.arange(trainer.store.num_clients)
    tree = {
        "x": trainer.server.x,
        "c": trainer.server.c,
        "opt_state": trainer.server.opt_state,
        "store": trainer.store.gather(all_ids),
    }
    if trainer.residual_store is not None:
        tree["residuals"] = trainer.residual_store.gather(all_ids)
    if trainer.solver_store is not None:
        tree["solver_slots"] = trainer.solver_store.gather(all_ids)
    if getattr(trainer, "base_params", None) is not None:
        # non-identity update space (DESIGN.md §17): "x" above is the
        # trainable-delta pytree; the frozen base rides next to it so
        # the checkpoint is self-contained for serving (load_serving_
        # params merges them without the training config)
        tree["base"] = trainer.base_params
    return tree


def save_trainer(path: str, trainer):
    """Checkpoint a FederatedTrainer: ServerState, all N client states
    (+ residuals when compressing), round counter, and host RNG states.
    An async-mode trainer (DESIGN.md §14) additionally records every
    pending (in-flight or buffered) update — stacked payload rows under
    the ``async`` tree key, dispatch/event records in the metadata — so
    resume is deterministic without recomputing them."""
    extra = {
        "round": trainer.round_idx,
        "host_rng": trainer.host_rng_state(),
    }
    space = getattr(trainer, "update_space", None)
    if space is not None and space.trains_subset:
        extra["update_space"] = space.checkpoint_meta(trainer.spec)
    tree = _trainer_tree(trainer)
    engine = getattr(trainer, "async_engine", None)
    if engine is not None:
        tree["async"] = engine.checkpoint_tree()
        extra["async"] = engine.checkpoint_meta()
    save_checkpoint(path, tree, extra=extra)


def load_trainer(path: str, trainer):
    """Restore ``save_trainer`` state into a compatibly-constructed
    trainer (same spec/model/dataset). Clears any prefetched rounds."""
    import dataclasses

    flat, extra = _read_checkpoint(path)
    saved_space = extra.get("update_space", {"name": "full"})["name"] \
        if "update_space" in extra else "full"
    trainer_space = getattr(trainer, "update_space", None)
    trainer_space_name = trainer_space.name if trainer_space else "full"
    if saved_space != trainer_space_name:
        raise ValueError(
            f"checkpoint was trained in update_space={saved_space!r} but "
            f"the trainer is configured for {trainer_space_name!r}; restore "
            f"into a matching FedRoundSpec")
    template = _trainer_tree(trainer)
    engine = getattr(trainer, "async_engine", None)
    if engine is not None:
        assert "async" in extra, (
            "checkpoint has no async-engine state: it was saved by a "
            "synchronous trainer; restore into a matching configuration")
        # the pending-payload template is (P, ...)-shaped with P from the
        # checkpoint itself, not from the (freshly constructed) trainer
        template["async"] = engine.pending_template(extra["async"])
    tree = _unflatten_into(flat, template)
    if "base" in template:
        # the jitted programs take trainer.base_params as an argument and
        # the restore does not replace it — a checkpoint carrying a
        # *different* base would silently continue against other
        # weights than the saved run's, so the match must be bitwise
        for (key, saved), cur in zip(
                sorted(_flatten(tree["base"]).items()),
                (v for _, v in sorted(_flatten(trainer.base_params).items()))):
            if not np.array_equal(saved, np.asarray(cur)):
                raise ValueError(
                    f"checkpoint base parameters differ from the trainer's "
                    f"(leaf {key!r}): the trainer must be constructed with "
                    f"the same model init (same seed/config) as the saved "
                    f"run")
    all_ids = np.arange(trainer.store.num_clients)
    trainer.server = dataclasses.replace(
        trainer.server,
        x=jax.tree.map(np.asarray, tree["x"]),
        c=jax.tree.map(np.asarray, tree["c"]),
        opt_state=jax.tree.map(np.asarray, tree["opt_state"]),
    )
    trainer.store.scatter(all_ids, tree["store"])
    if trainer.residual_store is not None:
        trainer.residual_store.scatter(all_ids, tree["residuals"])
    if trainer.solver_store is not None:
        trainer.solver_store.scatter(all_ids, tree["solver_slots"])
    trainer.push_host_store_to_device()
    trainer.round_idx = int(extra.get("round", 0))
    if "host_rng" in extra:
        trainer.set_host_rng_state(extra["host_rng"])
    if engine is not None:
        engine.restore(tree["async"], extra["async"])
    return trainer


def _nest_flat(flat: Dict[str, np.ndarray], prefix: str):
    """Rebuild the nested tree stored under ``prefix`` from the flat
    "/"-joined archive keys, template-free: dict levels whose keys are
    all digits become lists (the round-trip of ``_flatten`` over the
    dict/list trees this repo checkpoints). Delta-tree keys escape "/"
    to "." (core/update_space.py), so the split is unambiguous."""
    pre = prefix + "/"
    sub = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
    if not sub:
        raise KeyError(f"checkpoint has no tree under {prefix!r}")
    root: Dict[str, Any] = {}
    for key, arr in sub.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr

    def listify(node):
        if not isinstance(node, dict):
            return node
        node = {k: listify(v) for k, v in node.items()}
        if node and all(k.isdigit() for k in node):
            return [node[str(i)] for i in range(len(node))]
        return node

    return listify(root)


def load_serving_params(path: str):
    """The *full* serving parameter pytree of a ``save_trainer``
    checkpoint: the frozen base with the trained deltas merged through
    ``update_space.apply`` (DESIGN.md §17) — or ``x`` itself when the
    run trained in the identity ``full`` space. Needs no trainer, spec,
    or model config: the update-space selection metadata rides in the
    checkpoint (``launch/serve.py --checkpoint``)."""
    from repro.core.update_space import spec_from_meta

    flat, extra = _read_checkpoint(path)
    x = _nest_flat(flat, "x")
    space, shim = spec_from_meta(extra.get("update_space"))
    if not space.trains_subset:
        return x
    return space.apply(shim, _nest_flat(flat, "base"), x)
