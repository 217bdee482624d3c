"""Federated dataset container: fixed-shape padded batch stacks per device.

Each device's arrays are padded to a whole number of batches by *cycling*
its own examples (so every batch is a valid sample of the device's local
distribution), then reshaped to ``(num_batches, batch_size, ...)``.
``num_batches`` is bucketed to the next power of two so the jitted local
solver compiles O(log max_batches) times, not once per device.

``stack_device_batches`` builds the input of the batched round engine
(core/engine.py): the K selected devices' batch stacks are padded (again
by cycling whole batches) to the max bucketed ``num_batches`` in the
selection and stacked along a new leading device axis, together with a
``(K, num_batches)`` validity mask.  Because per-device ``num_batches``
is already a power of two, the stacked shape is too, so the engine's
jitted round functions compile O(log max_batches) times.

Streaming sources keep each device's stack on the host
(``host_batches``); their cohorts are padded and stacked in NumPy
(``stack_host_batches``) and reach the device in one transfer per leaf.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def host_batches(arrays: Dict[str, np.ndarray], batch_size: int,
                 bucket: bool = True) -> Dict[str, np.ndarray]:
    """One device's ``(num_batches, batch, ...)`` stack as host arrays,
    in the dtype a device transfer would give (float64 -> float32 with
    x64 off).  Shared by ``FederatedData`` (which moves it to the
    device) and the streaming sources (which keep it on the host)."""
    n = next(iter(arrays.values())).shape[0]
    nb = max(1, math.ceil(n / batch_size))
    if bucket:
        nb = _next_pow2(nb)
    target = nb * batch_size
    idx = np.arange(target) % n           # cycle the device's own examples
    out = {}
    for k, a in arrays.items():
        padded = a[idx].astype(jax.dtypes.canonicalize_dtype(a.dtype),
                               copy=False)
        out[k] = padded.reshape((nb, batch_size) + a.shape[1:])
    return out


def pad_to_batches(arrays: Dict[str, np.ndarray], batch_size: int,
                   bucket: bool = True) -> Dict[str, jnp.ndarray]:
    """:func:`host_batches` moved to the device."""
    return {k: jnp.asarray(v)
            for k, v in host_batches(arrays, batch_size, bucket).items()}


def num_batches_of(batches) -> int:
    """Leading (num_batches) dim of one device's padded batch stack."""
    return jax.tree_util.tree_leaves(batches)[0].shape[0]


def pad_batch_stack(batches, nb: int):
    """Pad a ``(num_batches, batch, ...)`` stack to ``nb`` batches by
    cycling whole batches (each padded batch is a real batch of the same
    device, so gradients stay finite; the engine masks them out)."""
    cur = num_batches_of(batches)
    if nb < cur:
        raise ValueError(
            f"pad_batch_stack: target nb={nb} < current {cur} batches "
            "would silently drop device data")
    if cur == nb:
        return batches
    idx = np.arange(nb) % cur
    return jax.tree_util.tree_map(lambda x: x[idx], batches)


def _stack_cycled(xs, nb: int) -> np.ndarray:
    """Stack host ``(nb_k, ...)`` arrays into ``(K, nb, ...)``, each
    cycled out to ``nb`` by the :func:`pad_batch_stack` rule."""
    out = np.empty((len(xs), nb) + xs[0].shape[1:], xs[0].dtype)
    for i, x in enumerate(xs):
        out[i] = x[np.arange(nb) % x.shape[0]]
    return out


def _valid_mask(nbs, nb: int) -> np.ndarray:
    """``(K, nb)`` float32: 1 on each device's own batches."""
    return (np.arange(nb)[None, :]
            < np.asarray(nbs)[:, None]).astype(np.float32)


def _stack_host(devs) -> Tuple[dict, np.ndarray]:
    """Fetched host stacks -> ``(stacked, valid)`` NumPy arrays."""
    with jax.profiler.TraceAnnotation("cohort.pad"):
        nbs = [num_batches_of(d) for d in devs]
        nb_max = max(nbs)
        stacked = jax.tree_util.tree_map(
            lambda *xs: _stack_cycled(xs, nb_max), *devs)
        return stacked, _valid_mask(nbs, nb_max)


def stack_host_batches(dataset, indices) -> Tuple[dict, np.ndarray]:
    """:func:`stack_device_batches` on the host: each selected device's
    host stack (a streaming source's) is fetched once, cycled out to the
    selection's largest bucket and stacked into NumPy arrays."""
    return _stack_host([dataset.device_batches(int(k)) for k in indices])


def stack_device_batches(dataset, indices) -> Tuple[dict, jnp.ndarray]:
    """Stack the selected devices' batch stacks along a leading device axis.

    Returns ``(stacked, valid)`` where ``stacked`` leaves have shape
    ``(K, nb_max, batch, ...)`` and ``valid`` is a float32 ``(K, nb_max)``
    mask: 1 for the device's own (bucketed) batches, 0 for batches that
    only exist to reach the common ``nb_max``.  Masked batches must be
    no-ops in the engine (zero gradient weight, identity SGD step), which
    preserves exact numerical parity with the per-device looped path.

    Host stacks (a streaming source's) are padded and stacked on the
    host and moved to the device once per leaf; device stacks
    (``FederatedData``'s) are padded where they live.
    """
    devs = [dataset.device_batches(int(k)) for k in indices]
    if isinstance(jax.tree_util.tree_leaves(devs[0])[0], np.ndarray):
        return jax.device_put(_stack_host(devs))
    with jax.profiler.TraceAnnotation("cohort.pad"):
        nbs = [num_batches_of(d) for d in devs]
        nb_max = max(nbs)
        getter = getattr(dataset, "device_batches_padded", None)
        if getter is not None:
            padded = [getter(int(k), nb_max) for k in indices]
        else:
            padded = [pad_batch_stack(d, nb_max) for d in devs]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
        valid = jnp.asarray(_valid_mask(nbs, nb_max))
    return stacked, valid


def stack_eval_batches(dataset) -> Tuple[dict, jnp.ndarray, jnp.ndarray]:
    """Stack ALL devices' eval batches for the scanned driver's on-device
    global-loss evaluation.

    Consumes the same ``dataset.eval_batches()`` protocol the host-side
    ``FederatedTrainer.global_loss`` iterates (so per-device eval limits
    are honored identically) and returns ``(stacked, valid, weights)``:
    leaves ``(N, nb_max, batch, ...)``, a float32 ``(N, nb_max)`` validity
    mask, and the float32 ``(N,)`` aggregation weights p_k.  Per device,
    the mean loss over its *valid* batches equals the host eval exactly;
    padded slots cycle real batches and are masked out.
    """
    weights, stacks = [], []
    for wk, batches in dataset.eval_batches():
        weights.append(float(wk))
        stacks.append(batches)
    nbs = [num_batches_of(b) for b in stacks]
    nb_max = max(nbs)
    padded = [pad_batch_stack(b, nb_max) for b in stacks]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
    valid = jnp.asarray(_valid_mask(nbs, nb_max))
    return stacked, valid, jnp.asarray(weights, jnp.float32)


class FederatedData:
    """The dataset protocol consumed by ``FederatedTrainer``."""

    def __init__(self, device_data: List[Dict[str, np.ndarray]],
                 batch_size: int, bucket: bool = True,
                 eval_batch_limit: Optional[int] = None, name: str = "",
                 eval_sample: Optional[int] = None, eval_seed: int = 0):
        self.name = name
        self.batch_size = batch_size
        self.num_devices = len(device_data)
        self.sizes = [next(iter(d.values())).shape[0] for d in device_data]
        total = sum(self.sizes)
        self.weights = [s / total for s in self.sizes]   # p_k = n_k / n
        self._batches = [pad_to_batches(d, batch_size, bucket)
                         for d in device_data]
        self._eval_limit = eval_batch_limit
        self._eval_sample = eval_sample
        self._eval_seed = eval_seed
        self._eval_ids: Optional[np.ndarray] = None
        self._pad_cache: Dict[int, dict] = {}

    def device_batches(self, k: int):
        return self._batches[k]

    def device_batches_padded(self, k: int, nb: int):
        """``device_batches(k)`` cycled out to ``nb >= num_batches``.

        Only the largest padding seen so far is cached per device: cycling
        makes any shorter padding an exact prefix of a longer one
        (``arange(n1) % cur == (arange(n2) % cur)[:n1]``), so smaller
        requests slice the cached stack instead of storing another copy.
        """
        own = num_batches_of(self._batches[k])
        if nb < own:
            raise ValueError(
                f"device_batches_padded: nb={nb} < device {k}'s "
                f"{own} batches would silently drop data")
        cached = self._pad_cache.get(k)
        if cached is None or num_batches_of(cached) < nb:
            cached = pad_batch_stack(self._batches[k], nb)
            self._pad_cache[k] = cached
        if num_batches_of(cached) == nb:
            return cached
        return jax.tree_util.tree_map(lambda x: x[:nb], cached)

    def eval_ids(self) -> np.ndarray:
        """The devices ``eval_batches`` iterates: all of them, or — with
        ``eval_sample`` set below ``num_devices`` — a fixed seeded
        uniform sample without replacement, in id order.  This is the
        dense container's sampled eval path, mirroring the streaming
        sources' bounded ``eval_clients`` contract so neither the host
        eval loop nor ``stack_eval_batches`` is forced through an
        all-N pass when only a loss estimate is needed."""
        if self._eval_ids is None:
            if (self._eval_sample is None
                    or self._eval_sample >= self.num_devices):
                self._eval_ids = np.arange(self.num_devices)
            else:
                rng = np.random.default_rng([self._eval_seed, 0xE7A1])
                self._eval_ids = np.sort(rng.choice(
                    self.num_devices, size=self._eval_sample,
                    replace=False))
        return self._eval_ids

    def eval_batches(self) -> Iterable[Tuple[float, dict]]:
        for k in self.eval_ids():
            b = self._batches[k]
            if self._eval_limit is not None:
                b = {key: v[: self._eval_limit] for key, v in b.items()}
            yield self.weights[k], b

    def stats(self) -> Dict[str, float]:
        s = np.array(self.sizes)
        return {"devices": self.num_devices, "samples": int(s.sum()),
                "mean": float(s.mean()), "stdev": float(s.std())}
