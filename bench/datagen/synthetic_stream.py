"""Synthetic(alpha, beta) clients of the FedProx generator, one seeded
stream per client, as ``data/shard_source.py``
``SyntheticShardSource`` makes them.

On the streaming plan the program generates its clients itself, in its
own data plan, which is part of what the cell times.  This module is the
benchmark's own copy of that arithmetic: the reference and the work
counts read their clients from it, so a data plan that delivers other
data than the configuration states fails the comparison.  The
population is fixed by the configuration's ``data_seed``; the run's
seed moves the cohorts and the params.
"""
from __future__ import annotations

import numpy as np

#: ``data/shard_source.py``'s seed-sequence tags.
TAG_CLIENT, TAG_EVAL = 0x51AD, 0xE7A1
FEATURES, CLASSES = 60, 10


class Data:
    """Clients of one population: ``client(k)`` arrays, ``sizes(ids)``,
    the eval sample, and the program's dataset object."""

    def __init__(self, config: dict, num_devices: int, seed: int):
        d = config["data"]
        self.num_devices = num_devices
        self.batch_size = int(config["local_batch_size"])
        self.seed = int(d["data_seed"])
        self.alpha, self.beta = float(d["alpha"]), float(d["beta"])
        self.min_samples = int(d["min_samples"])
        self.max_samples = int(d["max_samples"])
        self.eval_clients = min(int(d["eval_clients"]), num_devices)
        self._cov = np.array([(j + 1) ** -1.2 for j in range(FEATURES)])

    def _rng(self, k: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, TAG_CLIENT, int(k)])

    def _size(self, rng: np.random.Generator) -> int:
        return int(np.clip(rng.lognormal(4.0, 2.0) + self.min_samples,
                           self.min_samples, self.max_samples))

    def sizes(self, ids) -> np.ndarray:
        return np.array([self._size(self._rng(k))
                         for k in np.asarray(ids).ravel()], np.int64)

    def client(self, k: int) -> dict:
        rng = self._rng(k)
        n = self._size(rng)
        u = rng.normal(0, self.alpha)
        w = rng.normal(u, 1, (FEATURES, CLASSES))
        b = rng.normal(u, 1, CLASSES)
        mean_x = rng.normal(rng.normal(0, self.beta), 1, FEATURES)
        x = rng.normal(mean_x, np.sqrt(self._cov), (n, FEATURES))
        z = x @ w + b
        e = np.exp(z - z.max(axis=-1, keepdims=True))
        probs = e / e.sum(axis=-1, keepdims=True)
        y = np.array([rng.choice(CLASSES, p=p) for p in probs])
        return {"x": x.astype(np.float32), "y": y.astype(np.int32)}

    def eval_ids(self) -> np.ndarray:
        if self.eval_clients >= self.num_devices:
            return np.arange(self.num_devices)
        rng = np.random.default_rng([self.seed, TAG_EVAL])
        return np.sort(rng.choice(self.num_devices,
                                  size=self.eval_clients, replace=False))

    def program_dataset(self, plan: str):
        if plan == "streaming":
            from repro.data.shard_source import make_synthetic_stream
            return make_synthetic_stream(
                self.alpha, self.beta, num_devices=self.num_devices,
                seed=self.seed, min_samples=self.min_samples,
                batch_size=self.batch_size,
                eval_clients=self.eval_clients)
        if plan == "stacked":
            from repro.data.batching import FederatedData
            return FederatedData(
                [self.client(k) for k in range(self.num_devices)],
                batch_size=self.batch_size, name="synthetic_stacked")
        raise ValueError(f"unknown data plan {plan!r}")
