"""Streaming client-shard dataset sources for population-scale runs.

The pre-stacked :class:`~repro.data.batching.FederatedData` container
generates and pads EVERY client's batches eagerly at construction — an
``O(N)`` cost in both time and memory that is fine at the paper's
N=30..772 but memory-impossible at the "massively distributed" scale
the paper actually targets (K=10 of N=1,000,000).

A :class:`ClientShardSource` is the streaming half of the same dataset
protocol: it exposes ``num_devices`` / ``device_batches(k)`` /
``eval_batches()`` exactly like ``FederatedData``, but materializes a
client's arrays only when that client is actually touched (selected
into a round cohort, or part of the bounded eval sample), and keeps
them on the host until a whole cohort or chunk moves to the device.
Per-client data comes from an **O(1) seed-per-client** construction —
``np.random.default_rng([seed, tag, k])`` — so client k's shard is
identical no matter which cohorts it appears in, in which order, or on
which host.  A bounded LRU cache keeps the hot cohort's padded batch
stacks; everything else is regenerated on demand.

Contract notes
--------------
- ``weights`` is ``None``: computing exact ``p_k = n_k / n`` needs all
  N sizes (an O(N) pass), so population-scale sampling is uniform.
  Use :meth:`ClientShardSource.materialize` when you need the dense
  container (small N only — parity tests do this).
- ``eval_batches()`` iterates a fixed, seed-deterministic **sample** of
  at most ``eval_clients`` clients (all of them when
  ``N <= eval_clients``, in id order — so small-N streaming eval
  equals the dense container's eval exactly).  The reported weights
  are the sampled clients' sizes, normalized by the consumer
  (``FederatedTrainer.global_loss`` / ``stack_eval_batches``).
- The streaming generators deliberately do NOT bit-match the dense
  generators in ``synthetic.py`` / ``leaf_like.py`` (those draw one
  sequential stream over clients, which is exactly the O(N) coupling
  streaming removes).  Parity is between *streaming and materialized
  execution over the same streaming data*, not across generators.
- Telemetry: ``materialized_clients`` (generator invocations; cache
  hits do not count), ``cache_bytes`` / ``peak_cache_bytes`` — what
  the population memory tests and ``population_*`` bench rows assert.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Iterable, Optional, Tuple

import jax
import numpy as np

from repro.data.batching import FederatedData, host_batches

#: Seed-sequence domain tags: per-client streams, dataset-shared
#: structures, and the eval-sample draw must never collide.
_TAG_CLIENT = 0x51AD
_TAG_SHARED = 0x5EED
_TAG_EVAL = 0xE7A1


def resolve_streaming(client_source: str, dataset) -> bool:
    """Resolve the ``FederatedConfig.client_source`` knob against a
    dataset: ``"streaming"`` / ``"stacked"`` force the path (streaming
    requires the dataset to declare ``streaming = True``); ``"auto"``
    follows the dataset's own declaration."""
    if client_source == "streaming":
        if not getattr(dataset, "streaming", False):
            raise ValueError(
                "client_source='streaming' needs a streaming dataset "
                "(a ClientShardSource); this dataset does not declare "
                "streaming=True")
        return True
    if client_source == "stacked":
        return False
    return bool(getattr(dataset, "streaming", False))


def _tree_bytes(batches) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(batches))


class ClientShardSource:
    """Base class: on-demand, seed-per-client federated data.

    Subclasses implement :meth:`_client_arrays` — a pure function of
    ``(self, k)`` returning client k's raw ``{name: np.ndarray}``
    arrays from ``self.client_rng(k)``.  Everything else (batching,
    padding caches, the eval sample, telemetry, materialization) is
    shared machinery.
    """

    #: The marker ``resolve_streaming`` / the drivers dispatch on.
    streaming = True

    def __init__(self, num_devices: int, *, batch_size: int = 10,
                 seed: int = 0, name: str = "shard_source",
                 eval_clients: int = 64, cache_clients: int = 256):
        if num_devices < 1:
            raise ValueError(f"num_devices must be >= 1, got "
                             f"{num_devices}")
        self.num_devices = int(num_devices)
        self.batch_size = batch_size
        self.seed = seed
        self.name = name
        #: uniform sampling at population scale (see module docstring)
        self.weights = None
        self.eval_clients = min(int(eval_clients), self.num_devices)
        self.cache_clients = max(1, int(cache_clients))
        self._cache: "OrderedDict[int, dict]" = OrderedDict()
        self._sizes: Dict[int, int] = {}    # touched clients only
        self._eval_ids: Optional[np.ndarray] = None
        # -- telemetry the population tests/benches assert ------------
        self.materialized_clients = 0   # generator invocations
        self.cache_bytes = 0
        self.peak_cache_bytes = 0

    # -- per-client determinism ---------------------------------------

    def client_rng(self, k: int) -> np.random.Generator:
        """Client k's private stream — identical across processes,
        cohort orders, and cache evictions."""
        return np.random.default_rng([self.seed, _TAG_CLIENT, int(k)])

    def shared_rng(self) -> np.random.Generator:
        """The dataset-level stream for structures every client shares
        (global model planes, class templates...)."""
        return np.random.default_rng([self.seed, _TAG_SHARED])

    def _client_arrays(self, k: int) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    # -- the FederatedData protocol -----------------------------------

    def device_batches(self, k: int):
        """Client k's padded ``(num_batches, batch, ...)`` stack as host
        NumPy arrays, generated on first touch and LRU-cached; cohorts
        are padded and stacked on the host and moved to the device
        whole (``data.batching.stack_host_batches``)."""
        with jax.profiler.TraceAnnotation("cohort.fetch"):
            k = int(k)
            hit = self._cache.get(k)
            if hit is not None:
                self._cache.move_to_end(k)
                return hit
            self.materialized_clients += 1
            with jax.profiler.TraceAnnotation("cohort.make"):
                arrays = self._client_arrays(k)
            self._sizes[k] = next(iter(arrays.values())).shape[0]
            batches = host_batches(arrays, self.batch_size)
            self._cache[k] = batches
            self.cache_bytes += _tree_bytes(batches)
            while len(self._cache) > self.cache_clients:
                _, old = self._cache.popitem(last=False)
                self.cache_bytes -= _tree_bytes(old)
            self.peak_cache_bytes = max(self.peak_cache_bytes,
                                        self.cache_bytes)
            return batches

    def eval_ids(self) -> np.ndarray:
        """The fixed eval-sample client ids (all ids, in order, when
        ``N <= eval_clients``; a seed-deterministic uniform sample
        without replacement otherwise)."""
        if self._eval_ids is None:
            if self.eval_clients >= self.num_devices:
                self._eval_ids = np.arange(self.num_devices)
            else:
                rng = np.random.default_rng([self.seed, _TAG_EVAL])
                self._eval_ids = np.sort(rng.choice(
                    self.num_devices, size=self.eval_clients,
                    replace=False))
        return self._eval_ids

    def eval_batches(self) -> Iterable[Tuple[float, dict]]:
        """``(size_k, batches)`` over the bounded eval sample; weights
        are raw sizes — every consumer normalizes, so when the sample
        covers all clients this equals the dense ``p_k`` eval."""
        for k in self.eval_ids():
            b = self.device_batches(int(k))
            yield float(self.size_of(int(k))), b

    def size_of(self, k: int) -> int:
        """Client k's sample count (materializes the client on first
        ask; sizes of touched clients are memoized)."""
        k = int(k)
        if k not in self._sizes:
            self.device_batches(k)
        return self._sizes[k]

    # -- small-N bridges ----------------------------------------------

    def materialize(self) -> FederatedData:
        """The dense container holding this source's exact per-client
        data — O(N), small N only (parity tests and A/B benches)."""
        data = [self._client_arrays(k) for k in range(self.num_devices)]
        return FederatedData(data, batch_size=self.batch_size,
                             name=self.name + "_materialized")

    def stats(self) -> Dict[str, float]:
        """Telemetry snapshot (NOT the O(N) size scan ``FederatedData``
        does): client count plus the streaming counters."""
        return {"devices": self.num_devices,
                "materialized_clients": float(self.materialized_clients),
                "cached_clients": float(len(self._cache)),
                "cache_bytes": float(self.cache_bytes),
                "peak_cache_bytes": float(self.peak_cache_bytes)}


class SyntheticShardSource(ClientShardSource):
    """Streaming synthetic(alpha, beta): the same heterogeneity
    structure as ``data.synthetic.generate_synthetic`` (per-device
    softmax-regression planes ``W_k ~ N(u_k, 1)``, per-device feature
    means ``mean_x_k ~ N(B_k, 1)``, decaying feature covariance) but
    with every client drawn from its own ``[seed, tag, k]`` stream so
    client k is an O(1) generation no matter how large N is."""

    def __init__(self, alpha: float = 0.0, beta: float = 0.0, *,
                 iid: bool = False, num_devices: int = 30,
                 seed: int = 0, min_samples: int = 50,
                 batch_size: int = 10, **kw):
        super().__init__(num_devices, batch_size=batch_size, seed=seed,
                         name=f"synthetic_stream({alpha},{beta})", **kw)
        self.alpha, self.beta, self.iid = alpha, beta, iid
        self.min_samples = min_samples
        from repro.data.synthetic import NUM_CLASSES, NUM_FEATURES
        self._nf, self._nc = NUM_FEATURES, NUM_CLASSES
        self._cov_diag = np.array(
            [(j + 1) ** -1.2 for j in range(self._nf)])
        shared = self.shared_rng()
        self._w_shared = shared.normal(0, 1, (self._nf, self._nc))
        self._b_shared = shared.normal(0, 1, self._nc)

    def _client_arrays(self, k: int) -> Dict[str, np.ndarray]:
        from repro.data.synthetic import _softmax
        rng = self.client_rng(k)
        n = int(np.clip(rng.lognormal(4.0, 2.0) + self.min_samples,
                        self.min_samples, 1000))
        u = rng.normal(0, self.alpha)
        if self.iid:
            W, b = self._w_shared, self._b_shared
        else:
            W = rng.normal(u, 1, (self._nf, self._nc))
            b = rng.normal(u, 1, self._nc)
        Bk = rng.normal(0, self.beta)
        mean_x = rng.normal(Bk, 1, self._nf)
        x = rng.normal(mean_x, np.sqrt(self._cov_diag),
                       (n, self._nf))
        logits = x @ W + b
        probs = _softmax(logits)
        y = np.array([rng.choice(self._nc, p=p) for p in probs])
        return {"x": x.astype(np.float32), "y": y.astype(np.int32)}


class FemnistShardSource(ClientShardSource):
    """Streaming femnist_like: shared smooth class templates, per-device
    Dirichlet class skew + writer-style affine transform — the
    ``data.leaf_like.generate_femnist_like`` structure with O(1)
    per-client generation."""

    def __init__(self, num_devices: int = 200, *, seed: int = 0,
                 class_concentration: float = 0.5,
                 mean_samples: int = 92, stdev_samples: int = 159,
                 batch_size: int = 10, **kw):
        super().__init__(num_devices, batch_size=batch_size, seed=seed,
                         name="femnist_stream", **kw)
        from repro.data.leaf_like import FEMNIST_CLASSES, FEMNIST_DIM
        self._nc, self._dim = FEMNIST_CLASSES, FEMNIST_DIM
        self.class_concentration = class_concentration
        sigma2 = np.log(1 + (stdev_samples / mean_samples) ** 2)
        self._size_mu = np.log(mean_samples) - sigma2 / 2
        self._size_sigma = np.sqrt(sigma2)
        shared = self.shared_rng()
        base = shared.normal(0, 1, (self._nc, 28, 28))
        from numpy.fft import fft2, ifft2
        freq = np.exp(-0.15 * (np.add.outer(np.arange(28) ** 2,
                                            np.arange(28) ** 2) ** 0.5))
        templates = np.stack([np.real(ifft2(fft2(b) * freq))
                              for b in base])
        self._templates = templates / templates.std() * 2.0

    def _client_arrays(self, k: int) -> Dict[str, np.ndarray]:
        rng = self.client_rng(k)
        n = int(np.clip(rng.lognormal(self._size_mu, self._size_sigma),
                        8, 5000))
        class_probs = rng.dirichlet(
            np.full(self._nc, self.class_concentration))
        y = rng.choice(self._nc, size=n, p=class_probs)
        gain = rng.normal(1.0, 0.25)
        bias = rng.normal(0.0, 0.3)
        style = rng.normal(0, 0.4, (28, 28))
        x = (self._templates[y] * gain + bias + style
             + rng.normal(0, 0.6, (n, 28, 28)))
        return {"x": x.reshape(n, self._dim).astype(np.float32),
                "y": y.astype(np.int32)}


def make_synthetic_stream(alpha: float = 0.0, beta: float = 0.0,
                          **kw) -> SyntheticShardSource:
    """Factory mirroring ``data.synthetic.make_synthetic`` for the
    streaming source (same (alpha, beta) heterogeneity axes)."""
    return SyntheticShardSource(alpha, beta, **kw)


def make_femnist_stream(num_devices: int = 200,
                        **kw) -> FemnistShardSource:
    """Factory mirroring ``data.leaf_like.make_femnist_like`` for the
    streaming source."""
    return FemnistShardSource(num_devices, **kw)
