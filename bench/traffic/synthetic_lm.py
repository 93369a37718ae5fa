"""Synthetic federated LM token shards with a heterogeneity knob.

The benchmark's own copy of ``repro.data.synthetic_lm``: the one general
generator that every traffic mix under ``bench/traffic/*.json`` feeds, so
the yardstick's data stays fixed when the program's generator changes.

Each client draws tokens from a client-specific unigram mixture: a shared
zipf background blended with a client-private vocabulary slice. At
``heterogeneity=1.0`` clients use disjoint vocabulary slices (maximal
gradient dissimilarity on the embedding/unembedding); at 0.0 all clients
are i.i.d. This is the LM analog of the sort-by-label EMNIST splits.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


class SyntheticLMFederated:
    def __init__(self, num_clients: int, vocab_size: int, seq_len: int, *,
                 heterogeneity: float = 0.8, seed: int = 0):
        self.num_clients = num_clients
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.heterogeneity = heterogeneity
        rng = np.random.default_rng(seed)
        # shared zipf background over the full vocab
        ranks = np.arange(1, vocab_size + 1)
        self.background = (1.0 / ranks) / np.sum(1.0 / ranks)
        # client-private slices (equal contiguous slabs)
        self.slices = np.array_split(np.arange(vocab_size), num_clients)
        # simple client-specific bigram shift for non-trivial structure
        self.shifts = rng.integers(1, 7, size=num_clients)

    def _client_sample(self, cid: int, shape, rng) -> np.ndarray:
        n = int(np.prod(shape))
        het = self.heterogeneity
        use_private = rng.random(n) < het
        sl = self.slices[cid]
        private = sl[rng.integers(0, len(sl), size=n)]
        shared = rng.choice(self.vocab_size, size=n, p=self.background)
        tokens = np.where(use_private, private, shared)
        # inject learnable structure: every other token repeats prev+shift
        tokens = tokens.reshape(-1, shape[-1])
        n_odd = tokens[:, 1::2].shape[1]
        tokens[:, 1::2] = (
            tokens[:, 0::2][:, :n_odd] + self.shifts[cid]
        ) % self.vocab_size
        return tokens.reshape(shape).astype(np.int32)

    def round_batches(self, ids: np.ndarray, K: int, b: int, rng) -> Dict:
        s = len(ids)
        toks = np.empty((s, K, b, self.seq_len + 1), np.int32)
        for si, cid in enumerate(ids):
            toks[si] = self._client_sample(cid, (K, b, self.seq_len + 1), rng)
        return {
            "tokens": jnp.asarray(toks[..., :-1]),
            "labels": jnp.asarray(toks[..., 1:]),
        }

    def client_sizes(self, ids: np.ndarray) -> np.ndarray:
        """Vocabulary-slab sizes stand in for dataset sizes (the stream is
        infinite); ``array_split`` makes them unequal when V % N != 0."""
        return np.asarray([len(self.slices[i]) for i in ids], np.int64)

    # -- device-data protocol (scanned engine, DESIGN.md §10) ------------
    # The unigram mixture resamples on device: the zipf background becomes
    # a categorical over log-probs, the client-private slab a uniform draw
    # inside [slab_start_i, slab_start_i + slab_len_i), and the
    # learnable every-other-token structure is the same vectorised
    # prev+shift rewrite as the host path — no host callback in the scan.

    def device_data(self) -> Dict:
        return {
            "log_bg": jnp.log(jnp.asarray(self.background, jnp.float32)),
            "slab_start": jnp.asarray(
                [s[0] for s in self.slices], jnp.int32),
            "slab_len": jnp.asarray(
                [len(s) for s in self.slices], jnp.int32),
            "shifts": jnp.asarray(self.shifts, jnp.int32),
        }

    def device_batch_fn(self, K: int, b: int):
        L = self.seq_len + 1
        het = self.heterogeneity
        V = self.vocab_size

        def batch_fn(data, ids, key):
            s = ids.shape[0]
            k_mix, k_priv, k_bg = jax.random.split(key, 3)
            shape = (s, K, b, L)
            use_private = jax.random.uniform(k_mix, shape) < het
            slab_len = data["slab_len"][ids][:, None, None, None]
            u = jax.random.uniform(k_priv, shape)
            off = jnp.minimum(
                jnp.floor(u * slab_len.astype(jnp.float32)).astype(jnp.int32),
                slab_len - 1)
            private = data["slab_start"][ids][:, None, None, None] + off
            shared = jax.random.categorical(
                k_bg, data["log_bg"], shape=shape).astype(jnp.int32)
            toks = jnp.where(use_private, private, shared)
            # inject learnable structure: every other token repeats
            # prev+shift (mirrors _client_sample)
            n_odd = toks[..., 1::2].shape[-1]
            shift = data["shifts"][ids][:, None, None, None]
            toks = toks.at[..., 1::2].set(
                (toks[..., 0::2][..., :n_odd] + shift) % V)
            return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

        return batch_fn

    def device_client_sizes(self):
        return jnp.asarray([len(s) for s in self.slices], jnp.float32)

    def eval_batch(self, batch_size: int, rng) -> Dict:
        """I.i.d. mixture batch for global-model eval."""
        toks = np.stack([
            self._client_sample(cid, (self.seq_len + 1,), rng)
            for cid in rng.integers(0, self.num_clients, size=batch_size)
        ])
        return {
            "tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:]),
        }
