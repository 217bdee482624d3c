"""The synthetic(alpha, beta) label draw.

``synthetic.categorical_rows`` draws every label of a client in one
vectorised pass; it must give the labels, and leave the generator in
the state, of one ``Generator.choice(C, p=row)`` per sample.  The
benchmark's own copy of the streaming generator
(``bench/datagen/synthetic_stream.py``) keeps that per-sample loop and
its plain reference reads from it, so the program's clients must equal
the copy's bit for bit.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.data.shard_source import SyntheticShardSource
from repro.data.synthetic import _softmax, categorical_rows

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = 10


def _probs(kind: str, rows: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, rows])
    p = _softmax(rng.normal(0, 3, (rows, C)))
    if kind == "zeros":
        # exact zeros, never a whole row: repeated cdf entries
        p[rng.random((rows, C)) < 0.5] = 0.0
        p[np.arange(rows), rng.integers(0, C, rows)] = 1.0
        p /= p.sum(axis=1, keepdims=True)
    elif kind == "one_hot":
        p = np.zeros((rows, C))
        p[np.arange(rows), rng.integers(0, C, rows)] = 1.0
    elif kind == "near_ties":
        p = _softmax(rng.normal(0, 1e-12, (rows, C)))
    elif kind == "on_draw":
        # the first cdf entry is the very double the draw will take
        # (u and 1 - u are multiples of 2**-53, so the cdf is exact):
        # only the count of entries <= u, searchsorted's side="right",
        # gives class 1 here
        u = np.random.default_rng(seed).random(rows)
        p = np.zeros((rows, C))
        p[:, 0], p[:, 1] = u, 1.0 - u
    return p


@pytest.mark.parametrize("kind", ["softmax", "zeros", "one_hot",
                                  "near_ties", "on_draw"])
@pytest.mark.parametrize("rows", [1, 7, 239, 1000])
@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11])
def test_categorical_rows_equals_per_sample_choice(seed, rows, kind):
    probs = _probs(kind, rows, seed)
    loop_rng = np.random.default_rng(seed)
    vec_rng = np.random.default_rng(seed)
    want = np.array([loop_rng.choice(C, p=row) for row in probs])
    got = categorical_rows(vec_rng, probs)
    np.testing.assert_array_equal(got, want)
    assert vec_rng.random() == loop_rng.random()


@pytest.fixture(scope="module")
def bench_data():
    path = os.path.join(REPO, "bench", "datagen", "synthetic_stream.py")
    spec = importlib.util.spec_from_file_location("bench_synthetic_stream",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(REPO, "bench", "configs",
                           "synthetic_1_1_logreg.json")) as f:
        config = json.load(f)
    return mod.Data(config, num_devices=10**6, seed=0)


@pytest.fixture(scope="module")
def source():
    return SyntheticShardSource(1.0, 1.0, num_devices=10**6, seed=0,
                                min_samples=50)


@pytest.mark.parametrize("k", [0, 1, 2, 17, 999, 4096, 31337, 65535,
                               123456, 250000, 500000, 654321, 777777,
                               900001, 999998, 999999])
def test_stream_client_equals_bench_copy(source, bench_data, k):
    got = source._client_arrays(k)
    want = bench_data.client(k)
    for name in ("x", "y"):
        assert got[name].dtype == want[name].dtype
        np.testing.assert_array_equal(got[name], want[name])
