"""FEMNIST-like writers, made by the benchmark from the seed.

The arithmetic of ``data/leaf_like.py`` ``generate_femnist_like`` (the
procedural stand-in for LEAF FEMNIST: smooth class templates, Dirichlet
class skew per writer, a writer-style gain, bias and pixel offset), with
two changes so that every seed does the same work:

- the writers' sample counts are one fixed set, the quantiles of the
  lognormal matched to the stated mean and stdev (LEAF FEMNIST: 92 and
  159 per writer), clipped as ``_sizes`` clips; the seed deals them out
  over the writers;
- each writer draws from a stream of its own, ``[seed, 1, k]``.

The program receives the arrays in its ``FederatedData`` container (the
stacked data plan); the reference reads the same arrays.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

DIM, CLASSES = 784, 10


def quantile_sizes(num: int, mean: float, stdev: float, lo: int,
                   hi: int) -> np.ndarray:
    """The ``num`` lognormal quantiles at ``(i + 0.5) / num`` of the
    lognormal with this mean and stdev, truncated and clipped as
    ``data/leaf_like.py`` ``_sizes`` does."""
    sigma2 = np.log(1 + (stdev / mean) ** 2)
    mu = np.log(mean) - sigma2 / 2
    z = np.array([NormalDist().inv_cdf((i + 0.5) / num)
                  for i in range(num)])
    return np.clip(np.exp(mu + np.sqrt(sigma2) * z).astype(int), lo, hi)


class Data:
    """The writers of one run: ``client(k)`` arrays, ``sizes(ids)``,
    the eval set, and the program's dataset object."""

    def __init__(self, config: dict, num_devices: int, seed: int):
        d = config["data"]
        self.num_devices = num_devices
        self.batch_size = int(config["local_batch_size"])
        self.seed = seed
        rng = np.random.default_rng([seed, 0])
        self._sizes = rng.permutation(quantile_sizes(
            num_devices, d["mean_samples"], d["stdev_samples"],
            d["min_samples"], d["max_samples"]))
        base = rng.normal(0, 1, (CLASSES, 28, 28))
        freq = np.exp(-0.15 * (np.add.outer(np.arange(28) ** 2,
                                            np.arange(28) ** 2) ** 0.5))
        t = np.stack([np.real(np.fft.ifft2(np.fft.fft2(b) * freq))
                      for b in base])
        self._templates = t / t.std() * 2.0
        self._conc = float(d["class_concentration"])
        self._clients = [self._make(k) for k in range(num_devices)]

    def _make(self, k: int) -> dict:
        rng = np.random.default_rng([self.seed, 1, k])
        n = int(self._sizes[k])
        probs = rng.dirichlet(np.full(CLASSES, self._conc))
        y = rng.choice(CLASSES, size=n, p=probs)
        gain = rng.normal(1.0, 0.25)
        bias = rng.normal(0.0, 0.3)
        style = rng.normal(0, 0.4, (28, 28))
        x = (self._templates[y] * gain + bias + style
             + rng.normal(0, 0.6, (n, 28, 28)))
        return {"x": x.reshape(n, DIM).astype(np.float32),
                "y": y.astype(np.int32)}

    def client(self, k: int) -> dict:
        return self._clients[int(k)]

    def sizes(self, ids) -> np.ndarray:
        return self._sizes[np.asarray(ids, np.int64)]

    def eval_ids(self) -> np.ndarray:
        return np.arange(self.num_devices)

    def program_dataset(self, plan: str):
        if plan != "stacked":
            raise ValueError(f"femnist_like serves the stacked plan, "
                             f"not {plan!r}")
        from repro.data.batching import FederatedData
        return FederatedData(self._clients, batch_size=self.batch_size,
                             name="femnist_like")
