"""Pallas kernel validation: shape/dtype sweeps vs the ref.py jnp oracles.

Kernels execute in interpret mode on CPU (the kernel body runs in Python);
on TPU the same pallas_call compiles to Mosaic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import dane_update, dane_update_array, flash_attention
from repro.kernels.ref import dane_update_ref, flash_attention_ref

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# dane_update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (128,), (1000,), (64, 128),
                                   (3, 5, 7), (2, 128, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("eta,mu", [(0.01, 0.0), (0.1, 1.0), (1e-3, 0.01)])
def test_dane_update_sweep(shape, dtype, eta, mu):
    ks = jax.random.split(KEY, 4)
    w, g, c, a = [jax.random.normal(k, shape, dtype) for k in ks]
    out = dane_update_array(w, g, c, a, eta, mu, interpret=True)
    ref = dane_update_ref(w, g, c, a, eta=eta, mu=mu)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype), rtol=tol(dtype))


def test_dane_update_pytree():
    tree = {"a": jnp.ones((40,)), "b": {"c": jnp.full((3, 9), 2.0)}}
    grads = jax.tree_util.tree_map(jnp.ones_like, tree)
    corr = jax.tree_util.tree_map(lambda x: -jnp.ones_like(x), tree)
    anchor = jax.tree_util.tree_map(jnp.zeros_like, tree)
    out = dane_update(tree, grads, corr, anchor, 0.5, 1.0, interpret=True)
    # grad + corr = 0, so w' = w - 0.5 * mu * (w - 0) = 0.5 w
    ref = jax.tree_util.tree_map(lambda x: 0.5 * x, tree)
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-6)


def test_dane_update_equals_fedprox_when_no_correction():
    """corr=0 reduces the kernel to the FedProx proximal-SGD step."""
    w = jax.random.normal(KEY, (256,))
    g = jax.random.normal(jax.random.PRNGKey(1), (256,))
    zero = jnp.zeros_like(w)
    out = dane_update_array(w, g, zero, w, 0.1, 5.0, interpret=True)
    # anchor == w -> prox term zero: w' = w - eta*g
    np.testing.assert_allclose(np.asarray(out), np.asarray(w - 0.1 * g),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Kv,hd", [
    (1, 128, 4, 4, 64),
    (2, 256, 8, 2, 64),
    (1, 512, 4, 1, 128),
    (2, 128, 6, 6, 32),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Kv, hd, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)

    rep = lambda x: jnp.repeat(x, H // Kv, axis=2).transpose(0, 2, 1, 3)
    ref = flash_attention_ref(q.transpose(0, 2, 1, 3), rep(k), rep(v),
                              causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol(dtype) * 2, rtol=tol(dtype))


def test_flash_attention_matches_model_attention():
    """The Pallas kernel and the in-model XLA chunked path agree."""
    from repro.models.attention import chunked_attention
    B, S, H, hd = 1, 256, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    pallas_out = flash_attention(q, k, v, causal=True, interpret=True)
    xla_out = chunked_attention(q, k, v, causal=True, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(pallas_out), np.asarray(xla_out),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# dane_update_2d blocking edge cases
# ---------------------------------------------------------------------------

def _rand_2d(rows, dtype=jnp.float32):
    ks = jax.random.split(KEY, 4)
    from repro.kernels.dane_update import LANES
    return [jax.random.normal(k, (rows, LANES), dtype) for k in ks]


@pytest.mark.parametrize("rows,block_rows", [
    (7, 4),      # unaligned rows above the target: one whole-dim block
    (6, 4),      # same, even row count
    (40, 16),    # aligned: 8-row blocks (16 does not divide 40)
    (12, None),  # rows < DEFAULT_BLOCK_ROWS: block clamps to rows
])
def test_dane_update_2d_block_degradation(rows, block_rows):
    from repro.kernels.dane_update import DEFAULT_BLOCK_ROWS, dane_update_2d
    w, g, c, a = _rand_2d(rows)
    kw = {} if block_rows is None else {"block_rows": block_rows}
    out = dane_update_2d(w, g, c, a, 0.05, 0.3, interpret=True, **kw)
    ref = dane_update_ref(w, g, c, a, eta=0.05, mu=0.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    assert rows < DEFAULT_BLOCK_ROWS  # the clamp branch is what ran


@pytest.mark.parametrize("rows,target,want", [
    (64, 512, 64),       # fits: the whole dim
    (640, 512, 320),     # FEMNIST logreg, K=10 flat pack
    (616, 256, 88),      # largest aligned divisor, not 154
    (200, 128, 40),      # batch rows of the fused step kernel
    (620, 512, 620),     # no aligned divisor: the whole dim
    (130, 128, 130),
])
def test_row_block_is_mosaic_aligned(rows, target, want):
    """Blocks are a multiple of 8 rows that divides ``rows``, or the
    whole dim — the two shapes Mosaic accepts on the second-minor
    axis (an unaligned divisor such as 154 of 616 is refused)."""
    from repro.kernels.dane_update import SUBLANES, row_block
    bb = row_block(rows, target)
    assert bb == want
    assert bb == rows or (bb % SUBLANES == 0 and rows % bb == 0
                          and bb <= target)


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_dane_update_2d_low_precision(dtype):
    """Kernel computes in f32 and rounds once on output; the eager ref
    runs in the storage dtype — agreement is at storage resolution."""
    from repro.kernels.dane_update import dane_update_2d
    w, g, c, a = _rand_2d(24, dtype)
    out = dane_update_2d(w, g, c, a, 0.1, 0.5, interpret=True)
    assert out.dtype == dtype
    ref = dane_update_ref(w, g, c, a, eta=0.1, mu=0.5)
    t = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=t, rtol=t)


def test_pad_2d_exact_multiple_and_remainder():
    from repro.kernels.ops import _pad_2d
    a = jnp.arange(2048.0)                   # exactly 16 rows of lanes
    v, n = _pad_2d(a)
    assert v.shape == (16, 128) and n == 2048
    np.testing.assert_array_equal(np.asarray(v).ravel(), np.asarray(a))
    b = jnp.arange(130.0)                    # 2 used rows, padded to 8
    v, n = _pad_2d(b)
    assert v.shape == (8, 128) and n == 130
    np.testing.assert_array_equal(np.asarray(v).ravel()[:130],
                                  np.asarray(b))
    np.testing.assert_array_equal(np.asarray(v).ravel()[130:], 0.0)


# ---------------------------------------------------------------------------
# flatpack layout
# ---------------------------------------------------------------------------

MIXED_TREE = {"a": jnp.arange(15.0, dtype=jnp.float32).reshape(5, 3),
              "b": {"c": jnp.arange(7.0, dtype=jnp.bfloat16),
                    "d": jnp.full((2, 2, 2), 3.0, jnp.float32)}}


def test_flatpack_spec_alignment():
    from repro.kernels import flatpack
    spec = flatpack.flat_spec(MIXED_TREE)
    assert spec.total == 15 + 7 + 8
    assert spec.rows % flatpack.ROW_ALIGN == 0
    assert spec.padded >= spec.total


def test_flatpack_roundtrip_preserves_values_and_dtypes():
    from repro.kernels import flatpack
    spec = flatpack.flat_spec(MIXED_TREE)
    buf = flatpack.pack(spec, MIXED_TREE)
    assert buf.shape == (spec.rows, flatpack.LANES)
    assert buf.dtype == jnp.float32
    back = flatpack.unpack(spec, buf)
    for o, r in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(MIXED_TREE)):
        assert o.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(o, np.float32),
                                      np.asarray(r, np.float32))
    # padding tail is zeros (update-invariant rows)
    flat = np.asarray(buf).ravel()
    np.testing.assert_array_equal(flat[spec.total:], 0.0)


def test_flatpack_stacked_roundtrip_and_broadcast():
    from repro.kernels import flatpack
    k = 3
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x + i for i in range(k)]).astype(x.dtype),
        MIXED_TREE)
    spec = flatpack.flat_spec(MIXED_TREE)
    buf = flatpack.pack_stacked(spec, stacked, k)
    assert buf.shape == (k * spec.rows, flatpack.LANES)
    back = flatpack.unpack_stacked(spec, buf, k)
    for o, r in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(stacked)):
        assert o.shape == r.shape and o.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(o, np.float32),
                                      np.asarray(r, np.float32))
    # broadcast pack == packing the same tree into every device slot
    bc = flatpack.pack_broadcast(spec, MIXED_TREE, k)
    one = flatpack.pack(spec, MIXED_TREE)
    np.testing.assert_array_equal(
        np.asarray(bc), np.tile(np.asarray(one), (k, 1)))


# ---------------------------------------------------------------------------
# flat-pack masked update: bitwise vs per-leaf, close vs the jnp oracle
# ---------------------------------------------------------------------------

FLAT_TREES = {
    "logreg": [("w", (60, 10), jnp.float32), ("b", (10,), jnp.float32)],
    "mlp": [("l0", (30, 16), jnp.float32), ("b0", (16,), jnp.float32),
            ("l1", (16, 16), jnp.float32), ("b1", (16,), jnp.float32),
            ("l2", (16, 4), jnp.float32), ("b2", (4,), jnp.float32)],
    "mixed_dtype": [("w", (9, 7), jnp.float32), ("h", (33,), jnp.bfloat16)],
    "single": [("w", (257,), jnp.float32)],
}


def _stacked_trees(leaf_defs, k, seed=0):
    out = []
    for j in range(4):
        key = jax.random.PRNGKey(seed + j)
        tree = {}
        for name, shape, dt in leaf_defs:
            key, sub = jax.random.split(key)
            tree[name] = jax.random.normal(sub, (k,) + shape, dt)
        out.append(tree)
    return out


@pytest.mark.parametrize("tree_name", sorted(FLAT_TREES))
def test_flat_masked_bitwise_equals_per_leaf(tree_name):
    from repro.kernels.ops import dane_update_masked, dane_update_tree_masked
    k = 4
    w, g, c, a = _stacked_trees(FLAT_TREES[tree_name], k)
    valid = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    pl_out = dane_update_masked(w, g, c, a, 0.05, 0.2, valid,
                                interpret=True)
    fl_out = dane_update_tree_masked(w, g, c, a, 0.05, 0.2, valid,
                                     interpret=True)
    for leaf in w:
        np.testing.assert_array_equal(
            np.asarray(fl_out[leaf], np.float32),
            np.asarray(pl_out[leaf], np.float32))
    # masked device is an exact identity step in both paths
    for leaf in w:
        np.testing.assert_array_equal(
            np.asarray(fl_out[leaf][1], np.float32),
            np.asarray(w[leaf][1], np.float32))


@pytest.mark.parametrize("tree_name", ["logreg", "mlp"])
def test_flat_and_per_leaf_match_tree_oracle(tree_name):
    from repro.kernels.ops import dane_update_masked, dane_update_tree_masked
    from repro.kernels.ref import dane_update_tree_ref
    k = 4
    w, g, c, a = _stacked_trees(FLAT_TREES[tree_name], k, seed=5)
    valid = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    want = dane_update_tree_ref(w, g, c, a, eta=0.03, mu=0.7, valid=valid)
    for fn in (dane_update_masked, dane_update_tree_masked):
        got = fn(w, g, c, a, 0.03, 0.7, valid, interpret=True)
        for leaf in w:
            np.testing.assert_allclose(
                np.asarray(got[leaf]), np.asarray(want[leaf]),
                rtol=1e-5, atol=1e-6)


def test_dane_update_flat_multiblock_grid_matches_single_block():
    """Explicit small block_rows (multi-step grid, mask blocks tiled
    alongside data blocks) == the whole-buffer single-block launch."""
    from repro.kernels import flatpack
    from repro.kernels.dane_update import dane_update_flat
    k = 3
    w, g, c, a = _stacked_trees(FLAT_TREES["mlp"], k, seed=9)
    spec = flatpack.flat_spec(
        jax.tree_util.tree_map(lambda x: x[0], w))
    wf, gf, cf, af = (flatpack.pack_stacked(spec, t, k)
                      for t in (w, g, c, a))
    mask = jnp.asarray([1.0, 0.0, 1.0])
    one = dane_update_flat(wf, gf, cf, af, 0.1, 0.4, mask, spec.rows,
                           interpret=True)
    multi = dane_update_flat(wf, gf, cf, af, 0.1, 0.4, mask, spec.rows,
                             block_rows=8, interpret=True)
    assert spec.rows * k > 8  # the explicit grid really had >1 block
    np.testing.assert_array_equal(np.asarray(one), np.asarray(multi))


# ---------------------------------------------------------------------------
# fused local-solve kernels vs autodiff references
# ---------------------------------------------------------------------------

def _logreg_stack(k, d, c, nb, b, seed=3):
    rng = np.random.default_rng(seed)
    w = {"w": jnp.asarray(rng.normal(size=(k, d, c)) * 0.1, jnp.float32),
         "b": jnp.asarray(rng.normal(size=(k, c)) * 0.1, jnp.float32)}
    corr = {"w": jnp.asarray(rng.normal(size=(k, d, c)) * 0.01,
                             jnp.float32),
            "b": jnp.asarray(rng.normal(size=(k, c)) * 0.01, jnp.float32)}
    w0 = {"w": jnp.asarray(rng.normal(size=(d, c)) * 0.1, jnp.float32),
          "b": jnp.asarray(rng.normal(size=(c,)) * 0.1, jnp.float32)}
    batches = {"x": jnp.asarray(rng.normal(size=(k, nb, b, d)),
                                jnp.float32),
               "y": jnp.asarray(rng.integers(0, c, size=(k, nb, b)),
                                jnp.int32)}
    return w, corr, w0, batches


def test_linear_logistic_step_matches_autodiff():
    from repro.kernels.local_solve import linear_logistic_step
    from repro.models.small import logreg_loss
    k, d, c, b = 3, 9, 4, 10
    w, corr, w0, batches = _logreg_stack(k, d, c, 1, b)
    batch = {"x": batches["x"][:, 0], "y": batches["y"][:, 0]}
    mask = jnp.asarray([1.0, 0.0, 1.0])
    eta, mu = 0.05, 0.2
    got = linear_logistic_step(w, batch, corr, w0, eta=eta, mu=mu,
                               mask=mask, interpret=True)
    g = jax.vmap(jax.grad(logreg_loss))(w, batch)
    want = jax.tree_util.tree_map(
        lambda wv, gv, cv, av: wv - eta * (gv + cv + mu * (wv - av)),
        w, g, corr,
        jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (k,) + x.shape), w0))
    for leaf in w:
        keep = mask.reshape((k,) + (1,) * (w[leaf].ndim - 1)) > 0
        want_leaf = jnp.where(keep, want[leaf], w[leaf])
        np.testing.assert_allclose(np.asarray(got[leaf]),
                                   np.asarray(want_leaf), atol=1e-5)


@pytest.mark.parametrize("masked", [False, True])
def test_local_epoch_matches_looped_sgd(masked):
    from repro.kernels.local_solve import local_epoch
    from repro.models.small import logreg_loss
    k, d, c, nb, b, epochs = 2, 6, 3, 3, 8, 2
    _, corr, w0, batches = _logreg_stack(k, d, c, nb, b, seed=8)
    t_total = epochs * nb
    if masked:
        rng = np.random.default_rng(1)
        step_mask = jnp.asarray(
            rng.integers(0, 2, size=(k, t_total)), jnp.float32)
    else:
        step_mask = jnp.ones((k, t_total), jnp.float32)
    eta, mu = 0.1, 0.05
    got = local_epoch(w0, corr, batches, eta=eta, mu=mu,
                      num_epochs=epochs, step_mask=step_mask,
                      interpret=True)
    # per-device python loop over the identical masked SGD recursion
    grad = jax.grad(logreg_loss)
    for i in range(k):
        w = {leaf: w0[leaf] for leaf in w0}
        for t in range(t_total):
            batch = {"x": batches["x"][i, t % nb],
                     "y": batches["y"][i, t % nb]}
            g = grad(w, batch)
            new = {leaf: w[leaf] - eta * (g[leaf] + corr[leaf][i]
                                          + mu * (w[leaf] - w0[leaf]))
                   for leaf in w}
            if float(step_mask[i, t]) > 0:
                w = new
        for leaf in w:
            np.testing.assert_allclose(np.asarray(got[leaf][i]),
                                       np.asarray(w[leaf]), atol=1e-5)
