"""Population-scale substrate: streaming shard sources, sparse client
state, and the no-dense-N memory contract.

Three layers of gate, mirroring the PR-5/PR-9 parity style:

1. **Streaming parity** — a ``ClientShardSource`` must be a pure data
   *representation* change: every algorithm run over the source matches
   the same run over ``source.materialize()`` (the dense pre-stacked
   container holding identical per-client arrays) through every round
   driver — host loop, batched engine, scan-fused driver, buffered
   async — at atol 1e-5.  The scanned driver's streaming mode
   additionally replicates the chunk program's key schedule host-side,
   so ``client_source="streaming"`` vs ``"stacked"`` on the SAME source
   is compared with *sampled* (not injected) selections.
2. **Sparse-state equivalence** — property tests (hypothesis via
   ``_hypo_fallback``) that ``SparseClientState`` round-trips arbitrary
   set/evict/scatter/read interleavings identically to the dense
   length-N carry it replaces, while storing only touched rows.
3. **Memory regression** — a fresh-interpreter subprocess
   (tests/_population_child.py) runs the acceptance workload (3 feddane
   rounds, N=1,000,000, K=10) and this suite asserts its peak RSS and
   source telemetry stay at cohort scale, plus an in-process
   directional smoke reproducing the paper's headline at an honest
   participation ratio: FedDANE degrades vs FedAvg/FedProx at
   K/N = 1e-5 under bernoulli availability.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import leaves_allclose

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypo_fallback import given, settings, strategies as st

from repro.configs.base import FederatedConfig
from repro.core import FederatedTrainer
from repro.core.client_state import SparseClientState
from repro.data import FederatedData, make_synthetic_stream
from repro.data.batching import (num_batches_of, pad_batch_stack,
                                 stack_device_batches, stack_eval_batches,
                                 stack_host_batches)
from repro.data.shard_source import ClientShardSource
from repro.models.param import init_params
from repro.models.small import logreg_loss, logreg_specs

ALGOS = ["fedavg", "fedavgm", "feddane", "feddane_decayed",
         "feddane_pipelined", "fedprox", "inexact_dane", "one_shot",
         "scaffold", "sdane"]
#: algorithms with a sampled cohort (the streaming scan path; the two
#: full-participation specs always run the stacked plan by design)
SAMPLED = [a for a in ALGOS if a not in ("inexact_dane", "one_shot")]

N, K, R = 12, 4, 3
BASE = dict(num_devices=N, devices_per_round=K, local_epochs=1,
            local_batch_size=10, learning_rate=0.05, mu=0.01, seed=5,
            correction_decay=0.9)


@pytest.fixture(scope="module")
def setup():
    src = make_synthetic_stream(0.5, 0.5, num_devices=N, seed=3)
    dense = src.materialize()
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    sel = np.stack([
        np.stack([rng.choice(N, size=K, replace=False)
                  for _ in range(2)])
        for _ in range(R)])
    return src, dense, params, sel


def _run(ds, params, sel=None, rounds=R, **kw):
    cfg = FederatedConfig(**{**BASE, **kw})
    tr = FederatedTrainer(logreg_loss, ds, cfg)
    return tr.run(params, rounds, eval_every=1, selections=sel)


def _assert_parity(a, b):
    hist_a, p_a = a
    hist_b, p_b = b
    np.testing.assert_allclose(hist_a["loss"], hist_b["loss"], atol=1e-5)
    leaves_allclose(p_a, p_b, atol=1e-5)


# -- 1. streaming-vs-dense parity, all algorithms x all drivers --------

@pytest.mark.parametrize("algo", ALGOS)
def test_loop_streaming_matches_dense(setup, algo):
    """Host loop over the source == host loop over its materialization
    (uniform sampling on both sides follows the same host rng)."""
    src, dense, params, _ = setup
    kw = dict(algorithm=algo, engine="loop", round_driver="python",
              weighted_sampling=False)
    _assert_parity(_run(src, params, **kw), _run(dense, params, **kw))


@pytest.mark.parametrize("algo", ALGOS)
def test_batched_streaming_matches_dense(setup, algo):
    """Batched round engine fetching K-slices from the source == same
    engine over the dense container."""
    src, dense, params, _ = setup
    kw = dict(algorithm=algo, engine="batched", round_driver="python",
              weighted_sampling=False)
    _assert_parity(_run(src, params, **kw), _run(dense, params, **kw))


@pytest.mark.parametrize("algo", ALGOS)
def test_buffered_streaming_matches_dense(setup, algo):
    """Buffered async driver over the source == over the dense
    container (constant staleness; identical uniform sampling)."""
    src, dense, params, _ = setup
    kw = dict(algorithm=algo, round_driver="buffered",
              staleness_fn="constant", weighted_sampling=False)
    _assert_parity(_run(src, params, **kw), _run(dense, params, **kw))


@pytest.mark.parametrize("algo", SAMPLED)
def test_scan_streaming_matches_stacked(setup, algo):
    """The tentpole gate: the scanned driver's streaming chunk program
    (host-replicated key schedule, cohorts gathered from shard handles,
    sparse state stores) matches the all-N pre-stacked scan on the SAME
    source, with on-chip sampled selections."""
    src, _, params, _ = setup
    kw = dict(algorithm=algo, engine="batched", round_driver="scan",
              chunk_rounds=R)
    _assert_parity(_run(src, params, client_source="streaming", **kw),
                   _run(src, params, client_source="stacked", **kw))


@pytest.mark.parametrize("algo", ["feddane", "scaffold"])
def test_scan_streaming_matches_stacked_bernoulli(setup, algo):
    """Scenario uniforms are part of the replicated key schedule:
    streaming == stacked under bernoulli availability too."""
    src, _, params, _ = setup
    kw = dict(algorithm=algo, engine="batched", round_driver="scan",
              chunk_rounds=R, scenario="bernoulli", avail_prob=0.7)
    _assert_parity(_run(src, params, client_source="streaming", **kw),
                   _run(src, params, client_source="stacked", **kw))


@pytest.mark.parametrize("algo", ["feddane", "scaffold"])
def test_scan_streaming_matches_dense_injected(setup, algo):
    """With injected selections the streaming scan must also match the
    stacked scan over the materialized container (cross-representation,
    sampling taken out of the comparison)."""
    src, dense, params, sel = setup
    kw = dict(algorithm=algo, engine="batched", round_driver="scan",
              chunk_rounds=R, weighted_sampling=False)
    _assert_parity(
        _run(src, params, sel=sel, client_source="streaming", **kw),
        _run(dense, params, sel=sel, client_source="stacked", **kw))


def test_loop_injected_selections_match(setup):
    """Injected selections bypass sampling entirely, so dense-weighted
    and unweighted-source runs coincide exactly."""
    src, dense, params, sel = setup
    kw = dict(algorithm="feddane", engine="loop", round_driver="python")
    _assert_parity(_run(src, params, sel=sel, **kw),
                   _run(dense, params, sel=sel, **kw))


def test_streaming_requires_streaming_dataset(setup):
    """client_source='streaming' on a dense container fails fast."""
    _, dense, params, _ = setup
    with pytest.raises(ValueError, match="streaming"):
        _run(dense, params, algorithm="fedavg", engine="batched",
             round_driver="scan", client_source="streaming")


def test_source_telemetry_counts_cohorts(setup):
    """After a small run the source has materialized every client at
    most once (N=12 < eval sample), and its cache telemetry is live."""
    src = make_synthetic_stream(0.5, 0.5, num_devices=N, seed=9)
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    _run(src, params, algorithm="feddane", engine="loop",
         round_driver="python", weighted_sampling=False)
    s = src.stats()
    assert s["materialized_clients"] == N     # each client generated once
    assert s["peak_cache_bytes"] > 0
    assert s["cached_clients"] <= N


# -- 1b. the host data plan: cohorts stay NumPy until the chunk moves --

class _SizedSource(ClientShardSource):
    """Clients of fixed sizes: at batch size 10, 5 / 35 / 160 samples
    bucket to 1 / 4 / 16 batches."""

    SIZES = (5, 35, 160, 35, 5)

    def __init__(self):
        super().__init__(len(self.SIZES), batch_size=10, seed=4)

    def _client_arrays(self, k):
        rng = self.client_rng(k)
        n = self.SIZES[k]
        return {"x": rng.normal(size=(n, 3)).astype(np.float32),
                "y": rng.integers(0, 10, n).astype(np.int32)}


def _stream_two_chunks(src, k=K, rounds=4, **kw):
    """A feddane streaming scan run of two chunks on ``src``."""
    kw = dict(algorithm="feddane", num_devices=src.num_devices,
              devices_per_round=k, engine="batched", round_driver="scan",
              chunk_rounds=rounds // 2, client_source="streaming", **kw)
    return _run(src, init_params(logreg_specs(60, 10),
                                 jax.random.PRNGKey(0)), rounds=rounds,
                **kw)


def test_streaming_source_caches_host_arrays():
    src = make_synthetic_stream(0.5, 0.5, num_devices=200, seed=9)
    _stream_two_chunks(src)
    assert src._cache
    for batches in src._cache.values():
        for leaf in jax.tree_util.tree_leaves(batches):
            assert isinstance(leaf, np.ndarray), type(leaf)


def test_stack_host_batches_matches_the_device_rule():
    """Mixed buckets (1, 4, 16) cycle out to the largest exactly as
    ``pad_batch_stack`` then a stack would, with the same mask."""
    src = _SizedSource()
    ids = [0, 1, 2, 3, 4]
    stacked, valid = stack_host_batches(src, ids)
    own = [jax.tree_util.tree_map(jnp.asarray, src.device_batches(k))
           for k in ids]
    assert [num_batches_of(b) for b in own] == [1, 4, 16, 4, 1]
    ref = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[pad_batch_stack(b, 16) for b in own])
    for key in ("x", "y"):
        assert isinstance(stacked[key], np.ndarray)
        assert stacked[key].dtype == ref[key].dtype
        np.testing.assert_array_equal(stacked[key], np.asarray(ref[key]))
    ref_valid = np.arange(16)[None, :] < np.array([1, 4, 16, 4, 1])[:, None]
    assert valid.dtype == np.float32
    np.testing.assert_array_equal(valid, ref_valid.astype(np.float32))
    # the device-facing wrapper returns the same stack, on the device
    b, v = stack_device_batches(src, ids)
    assert isinstance(v, jax.Array)
    np.testing.assert_array_equal(np.asarray(b["x"]), stacked["x"])
    np.testing.assert_array_equal(np.asarray(v), valid)


def test_streaming_chunk_moves_each_cohort_leaf_once(monkeypatch):
    """Each chunk's ``b`` / ``ba`` leaves reach the device as one
    ``(R, K, nb, B, ...)`` transfer each: no client's stack is moved on
    its own."""
    put = jax.device_put
    moved = []

    def record(x, *args, **kw):
        moved.append([np.shape(leaf)
                      for leaf in jax.tree_util.tree_leaves(x)])
        return put(x, *args, **kw)

    monkeypatch.setattr(jax, "device_put", record)
    rounds, chunk = 4, 2
    _stream_two_chunks(make_synthetic_stream(0.5, 0.5, num_devices=200,
                                             seed=9), rounds=rounds)
    features = [[s for s in call if s[-1:] == (60,)] for call in moved]
    # two chunks, each one call carrying b's and ba's x leaf whole
    assert [len(f) for f in features if f] == [2, 2]
    for f in features:
        for shape in f:
            assert len(shape) == 5 and shape[:2] == (chunk, K), shape


# -- 2. sparse client-state store == dense carry (property tests) ------

def _tmpl():
    return {"a": jnp.zeros((2,)), "b": jnp.zeros(())}


def _fill(v):
    return jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, np.float32(v)), _tmpl())


@st.composite
def _op_seqs(draw):
    n = draw(st.integers(2, 10))
    ops = []
    for _ in range(draw(st.integers(0, 24))):
        kind = draw(st.sampled_from(["set", "evict", "scatter", "get"]))
        if kind == "set":
            ops.append(("set", draw(st.integers(0, n - 1)),
                        draw(st.floats(-2.0, 2.0))))
        elif kind == "evict":
            ops.append(("evict", draw(st.integers(0, n - 1))))
        elif kind == "scatter":
            ids = draw(st.lists(st.integers(0, n - 1), min_size=1,
                                max_size=4))
            vals = [draw(st.floats(-2.0, 2.0)) for _ in ids]
            ops.append(("scatter", ids, vals))
        else:
            ops.append(("get", draw(st.integers(0, n - 1))))
    return n, ops


@settings(max_examples=25, deadline=None)
@given(_op_seqs())
def test_sparse_store_matches_dense_carry(case):
    """Any interleaving of reads, writes, evictions, and stacked
    scatters (duplicate ids included) produces exactly the dense
    length-N carry — while storing only touched rows."""
    n, ops = case
    sp = SparseClientState(n, _tmpl())
    dense = [_tmpl() for _ in range(n)]
    touched = set()
    for op in ops:
        if op[0] == "set":
            sp[op[1]] = _fill(op[2])
            dense[op[1]] = _fill(op[2])
            touched.add(op[1])
        elif op[0] == "evict":
            sp.evict(op[1])
            dense[op[1]] = _tmpl()
        elif op[0] == "scatter":
            _, ids, vals = op
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[_fill(v) for v in vals])
            sp.scatter(ids, stacked)
            for k, v in zip(ids, vals):
                dense[k] = _fill(v)
            touched.update(ids)
        else:
            leaves_allclose(sp[op[1]], dense[op[1]], atol=0)
    for a, b in zip(sp.to_dense(), dense):
        leaves_allclose(a, b, atol=0)
    got = sp.gather(range(n))
    want = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *dense)
    leaves_allclose(got, want, atol=0)
    # memory contract: O(touched), never O(N)
    assert len(sp) <= len(touched)
    assert sp.peak_clients <= len(touched)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.floats(-2.0, 2.0, width=32, allow_subnormal=False),
                min_size=1, max_size=8))
def test_sparse_store_from_dense_roundtrip(vals):
    """from_dense(to_dense(.)) is the identity, and zero rows are not
    stored (they ARE the shared template).  Values are normal float32:
    XLA flushes a float32 subnormal to zero, so such a row would be a
    zero row the store rightly drops."""
    rows = [_fill(v) for v in vals]
    sp = SparseClientState.from_dense(rows)
    for a, b in zip(sp.to_dense(), rows):
        leaves_allclose(a, b, atol=0)
    assert len(sp) == sum(1 for v in vals if np.float32(v) != 0.0)


def test_sparse_store_bounds_ids():
    sp = SparseClientState(4, _tmpl())
    with pytest.raises(IndexError):
        sp[4]
    with pytest.raises(IndexError):
        sp[-1] = _fill(1.0)


# -- 3. sampled eval path (the dense-N eval hot spot) ------------------

def test_dense_eval_sample_is_bounded_and_deterministic(setup):
    src, _, params, _ = setup
    data = [src._client_arrays(k) for k in range(N)]
    a = FederatedData(data, batch_size=10, eval_sample=4, eval_seed=1)
    b = FederatedData(data, batch_size=10, eval_sample=4, eval_seed=1)
    assert len(a.eval_ids()) == 4
    np.testing.assert_array_equal(a.eval_ids(), b.eval_ids())
    assert len(list(a.eval_batches())) == 4
    # the sampled stack is 4 devices wide, not N
    stacked, valid, w = stack_eval_batches(a)
    assert valid.shape[0] == 4 and w.shape == (4,)


def test_dense_eval_sample_full_coverage_is_dense(setup):
    """eval_sample >= N degenerates to the exact all-N eval."""
    src, dense, params, _ = setup
    data = [src._client_arrays(k) for k in range(N)]
    full = FederatedData(data, batch_size=10, eval_sample=N + 5)
    tr_a = FederatedTrainer(logreg_loss, dense,
                            FederatedConfig(algorithm="fedavg", **BASE))
    tr_b = FederatedTrainer(logreg_loss, full,
                            FederatedConfig(algorithm="fedavg", **BASE))
    assert tr_a.global_loss(params) == pytest.approx(
        tr_b.global_loss(params), abs=1e-6)


# -- 4. the population memory-regression gate --------------------------

def test_population_memory_regression():
    """Fresh-interpreter acceptance run: 3 feddane rounds at
    N=1,000,000, K=10 through BOTH host-driven engines plus a scaffold
    sparse-store run — peak RSS and all telemetry must stay at cohort
    scale (a dense path would need ~10^2 GB of batch stacks alone)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tests",
                                      "_population_child.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=root)
    assert res.returncode == 0, res.stderr[-4000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    # peak_rss_mb is the child's VmHWM (reset at exec) — ru_maxrss would
    # inherit THIS fat parent's resident peak across fork+exec and fail
    # spuriously after a few hundred JAX tests.
    assert out["peak_rss_mb"] < 1500, out
    for run in ("feddane_loop", "feddane_scan"):
        d = out[run]
        assert all(np.isfinite(d["loss"])), (run, d)
        # eval sample (32) + two phases x K x R cohort fetches, never N
        assert d["materialized_clients"] <= 32 + 2 * 10 * 3, (run, d)
        assert d["peak_cache_bytes"] < 64e6, (run, d)
    sc = out["scaffold"]
    assert sc["peak_clients"] <= 2 * 10, sc      # distinct selected ids
    assert sc["stored_controls"] <= 2 * 10, sc


def test_population_directional_feddane_underperforms():
    """The paper's headline finding at an honest participation ratio:
    at K/N = 1e-5 under bernoulli availability, FedDANE's stale
    aggregate gradient degrades while FedAvg/FedProx keep descending
    (§V low-participation discussion)."""
    n, k, rounds = 1_000_000, 10, 4
    src = make_synthetic_stream(1.0, 1.0, num_devices=n, seed=7,
                                eval_clients=32)
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    finals = {}
    for algo in ("fedavg", "fedprox", "feddane"):
        cfg = FederatedConfig(
            algorithm=algo, num_devices=n, devices_per_round=k,
            local_epochs=1, local_batch_size=10, learning_rate=0.05,
            mu=0.01, seed=5, engine="batched", round_driver="scan",
            chunk_rounds=rounds, scenario="bernoulli")
        tr = FederatedTrainer(logreg_loss, src, cfg)
        hist, _ = tr.run(params, rounds, eval_every=rounds)
        finals[algo] = hist["loss"][-1]
    assert finals["feddane"] > 1.5 * finals["fedavg"], finals
    assert finals["feddane"] > 1.5 * finals["fedprox"], finals
