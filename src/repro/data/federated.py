"""Stacked per-client datasets for the vectorized-client engine.

The CC-FedAvg engine vmaps local training over a leading client axis, so
client datasets are materialized as dense arrays ``(N, n_i_max, ...)`` with a
validity count per client. Batch sampling inside jit draws uniform indices
modulo each client's true size (unbiased within each client's local data —
Assumption 2).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import Dataset


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x", "y", "sizes"], meta_fields=["n_classes"])
@dataclass(frozen=True)
class FederatedData:
    """A pytree, so the round executors take it as a jit argument and no
    dataset-sized constant is compiled into their programs."""

    x: jax.Array        # (N, M, ...) padded client features
    y: jax.Array        # (N, M) padded client labels
    sizes: jax.Array    # (N,) true per-client sample counts
    n_classes: int

    @property
    def n_clients(self) -> int:
        return self.x.shape[0]

    def client_batch(self, key: jax.Array, batch_size: int):
        """Sample one batch per client: returns (N, B, ...), (N, B)."""
        keys = jax.random.split(key, self.n_clients)

        def one(k, cx, cy, sz):
            idx = jax.random.randint(k, (batch_size,), 0, 2 ** 30) % sz
            return cx[idx], cy[idx]

        return jax.vmap(one)(keys, self.x, self.y, self.sizes)


@dataclass(frozen=True)
class CohortSampler:
    """Per-round cohorts for cross-device federations with N ≫ devices.

    The vectorized executors materialize every client's state, but a round
    only needs the sampled participants on device: the sharded executor
    gathers the cohort's history rows, runs the round ``shard_map``'ed over
    the client mesh, and scatters the updated rows back. Sampling is
    uniform without replacement and *absolute-round keyed* — round ``t``
    always draws the same cohort for a given seed, so resumed sessions see
    identical cohorts regardless of where they restart (the same contract
    the plan masks follow).

    ``cohort_size == n_clients`` degenerates to full participation
    (``indices_for(t) == arange(N)``), which is how the sharded executor
    stays numerically interchangeable with the others.
    """

    n_clients: int
    cohort_size: int
    seed: int = 0

    def __post_init__(self):
        if not 1 <= self.cohort_size <= self.n_clients:
            raise ValueError(
                f"cohort_size must be in [1, {self.n_clients}], "
                f"got {self.cohort_size}")

    def indices_for(self, t: int) -> np.ndarray:
        """Sorted participant ids for round ``t`` (deterministic in seed)."""
        if self.cohort_size == self.n_clients:
            return np.arange(self.n_clients)
        rng = np.random.default_rng((self.seed, t))
        return np.sort(rng.choice(self.n_clients, size=self.cohort_size,
                                  replace=False))

    def indices(self, rounds: int, start: int = 0) -> np.ndarray:
        """(rounds, cohort_size) int32 cohort table for rounds
        ``start .. start+rounds``."""
        return np.stack([self.indices_for(start + t)
                         for t in range(rounds)]).astype(np.int32)


def build_federated(ds: Dataset, parts: list[np.ndarray]) -> FederatedData:
    n_clients = len(parts)
    m = max(len(p) for p in parts)
    feat_shape = ds.x.shape[1:]
    x = np.zeros((n_clients, m) + feat_shape, np.float32)
    y = np.zeros((n_clients, m), np.int32)
    sizes = np.zeros((n_clients,), np.int32)
    for i, idx in enumerate(parts):
        k = len(idx)
        sizes[i] = max(1, k)
        if k:
            x[i, :k] = ds.x[idx]
            y[i, :k] = ds.y[idx]
            # cycle-pad so modulo indexing stays uniform over real samples
            reps = int(np.ceil(m / k))
            x[i, k:] = np.tile(ds.x[idx],
                               (reps,) + (1,) * (ds.x.ndim - 1))[: m - k]
            y[i, k:] = np.tile(ds.y[idx], reps)[: m - k]
    return FederatedData(jnp.asarray(x), jnp.asarray(y),
                         jnp.asarray(sizes), ds.n_classes)
