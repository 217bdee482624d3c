"""The one traffic generator: every cohort of a run, drawn from the seed.

A run is ``warmup_chunks`` chunks of warm-up rounds, then the window's
rounds, all of one training run with the params carried forward.  A
round is FedDANE's pair of selections: ``(2, K)`` client ids, row 0 the
gradient-gather phase and row 1 the local-solve phase (the layout
``FederatedTrainer.run(selections=...)`` takes).  The program receives
only these cohorts; the benchmark knows each of them, which it needs for
the work counts and for the reference.

Sampling modes (``traffic["sampling"]``):

- ``"weighted"``: each phase of each round draws K distinct clients with
  ``p_k = n_k / n``, the arithmetic of ``core/server.py``
  ``sample_devices`` (numpy's sequential renormalized draw).
- ``"uniform"``: the same with ``p = None``.
- ``"uniform_distinct"``: population scale.  One draw of distinct
  clients from a stream that does not depend on the seed fills every
  chunk of the run, so each chunk holds the same clients, and so the
  same sample counts and host work, whatever the seed; the seed shuffles
  them over the chunk's rounds and phases.  No client appears twice in
  a run, so the warm-up touches none of the window's clients (the
  shard source's cache never hits across them).  The warm-up's first
  cohorts are arranged so that every pair (client batch bucket, cohort
  batch bucket) that the population can produce occurs once: the
  streaming plan's eager padding ops compile per such pair, and they
  must all compile before the window.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

#: Entropy of the seed-independent stream of ``"uniform_distinct"``.
BASE_ENTROPY = 0xC0407


def derived_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for each use of ``--seed`` (it may be
    larger than 32 bits hold; JAX's ``PRNGKey`` and the program's
    ``cfg.seed`` want small ints)."""
    ss = np.random.SeedSequence(int(seed) % 2**64)
    names = ("params", "cohorts", "data", "program")
    return {n: int(c.generate_state(1)[0] % 2**31)
            for n, c in zip(names, ss.spawn(len(names)))}


def sample_devices(rng: np.random.Generator, num_devices: int, k: int,
                   p=None) -> np.ndarray:
    """``core/server.py`` ``sample_devices`` without replacement: K
    distinct ids, with probability ``p`` renormalized as numpy does."""
    probs = None
    if p is not None:
        probs = np.asarray(p, dtype=np.float64)
        probs = probs / probs.sum()
    return rng.choice(num_devices, size=min(k, num_devices),
                      replace=False, p=probs)


def batch_bucket(n, batch_size: int):
    """The padded batch count of a client of ``n`` samples: whole
    batches, rounded up to a power of two (``data/batching.py``)."""
    nb = np.maximum(1, -(-np.asarray(n) // batch_size))
    return (1 << np.ceil(np.log2(nb)).astype(np.int64)).astype(np.int64)


class Schedule:
    """Cohorts of one run: ``warmup`` is ``(W, 2, K)``, ``window`` the
    longest window the run may take, ``(M, 2, K)``; a run uses its
    first ``n`` rounds, ``n`` a multiple of ``chunk_rounds``.

    ``selections(start)`` is what a ``run`` call starting at round
    ``start`` of the run is handed: always ``M`` rows of int32, of which
    the call uses the first ``num_rounds``.  One shape for every call,
    so the driver's eager slicing of it compiles once, in the warm-up.
    """

    def __init__(self, warmup: np.ndarray, window: np.ndarray,
                 chunk_rounds: int):
        self.rounds = np.concatenate([warmup, window]).astype(np.int32)
        self.num_warmup = len(warmup)
        self.span = len(window)
        self.chunk_rounds = chunk_rounds

    @property
    def warmup(self) -> np.ndarray:
        return self.rounds[:self.num_warmup]

    @property
    def window(self) -> np.ndarray:
        return self.rounds[self.num_warmup:]

    def warmup_chunk(self, i: int) -> np.ndarray:
        c = self.chunk_rounds
        return self.rounds[i * c:(i + 1) * c]

    def window_rounds(self, chunks: int) -> np.ndarray:
        return self.window[:chunks * self.chunk_rounds]

    def selections(self, start: int) -> np.ndarray:
        return self.rounds[start:start + self.span]


def make_schedule(traffic: dict, num_devices: int, seed: int,
                  sizes: Callable[[np.ndarray], np.ndarray],
                  batch_size: int) -> Schedule:
    """Every cohort of a run of ``traffic`` over ``num_devices`` clients,
    a pure function of ``seed``.  ``sizes(ids)`` gives the clients'
    sample counts (used by weighted sampling and by the warm-up's shape
    coverage)."""
    k = int(traffic["devices_per_round"])
    c = int(traffic["chunk_rounds"])
    warm = int(traffic["warmup_chunks"]) * c
    most = int(traffic["max_window_chunks"]) * c
    mode = traffic["sampling"]
    rng = np.random.default_rng(derived_seeds(seed)["cohorts"])
    if mode in ("weighted", "uniform"):
        p = (sizes(np.arange(num_devices)) if mode == "weighted"
             else None)
        rounds = np.stack([[sample_devices(rng, num_devices, k, p)
                            for _ in range(2)]
                           for _ in range(warm + most)])
        return Schedule(rounds[:warm], rounds[warm:], c)
    if mode != "uniform_distinct":
        raise ValueError(f"unknown sampling {mode!r}")
    per_chunk = c * 2 * k
    base = np.random.default_rng(BASE_ENTROPY)
    ids = base.choice(num_devices, size=warm * 2 * k + most * 2 * k,
                      replace=False)
    warm_ids, win_ids = ids[:warm * 2 * k], ids[warm * 2 * k:]
    window = np.concatenate([
        rng.permutation(win_ids[i:i + per_chunk])
        for i in range(0, len(win_ids), per_chunk)])
    groups = _covering_groups(rng, warm_ids, k,
                              batch_bucket(sizes(warm_ids), batch_size))
    return Schedule(groups.reshape(warm, 2, k),
                    window.reshape(most, 2, k), c)


def _covering_groups(rng: np.random.Generator, ids: np.ndarray, k: int,
                     buckets: np.ndarray) -> np.ndarray:
    """``ids`` split into cohorts of ``k``, led by one cohort per bucket
    ``R`` present: one client of bucket ``R``, one of each smaller
    bucket present, filled up with the smallest clients.  The rest
    follow shuffled."""
    order = rng.permutation(len(ids))
    ids, buckets = ids[order], buckets[order]
    free = np.ones(len(ids), bool)
    groups: List[np.ndarray] = []
    present = sorted(set(buckets.tolist()))
    for r in reversed(present):
        pick = []
        for a in [r] + [b for b in present if b < r]:
            hit = np.flatnonzero(free & (buckets == a))
            if len(hit) and len(pick) < k:
                pick.append(hit[0])
                free[hit[0]] = False
        smallest = np.flatnonzero(free & (buckets <= r))
        smallest = smallest[np.argsort(buckets[smallest], kind="stable")]
        for i in smallest[:k - len(pick)]:
            pick.append(i)
            free[i] = False
        if len(pick) < k:           # too few small clients: give back
            free[pick] = True
            continue
        groups.append(ids[pick])
    rest = ids[free]
    groups.extend(rest[i:i + k] for i in range(0, len(rest), k))
    return np.concatenate(groups)


def covered_pairs(rounds: np.ndarray, sizes, batch_size: int) -> set:
    """The (client bucket, cohort bucket) pairs of ``rounds``' cohorts."""
    flat = rounds.reshape(-1, rounds.shape[-1])
    b = batch_bucket(sizes(flat.ravel()), batch_size).reshape(flat.shape)
    top = b.max(axis=1)
    return {(int(a), int(r)) for row, r in zip(b, top) for a in row}
