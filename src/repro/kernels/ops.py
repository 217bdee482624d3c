"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True``;
on TPU they compile to Mosaic.  Wrappers handle pytree flattening
(dane_update) and GQA head layout (flash_attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flatpack
from repro.kernels.dane_update import (LANES, SUBLANES, dane_update_2d,
                                       dane_update_flat)
from repro.kernels.flash_attention import flash_attention_3d


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# dane_update over arbitrary pytrees
# ---------------------------------------------------------------------------

def _pad_2d(a):
    """Flatten to (rows, LANES) with zero pad; returns (view, orig_size).

    ``rows`` is a multiple of ``SUBLANES``, so the kernel can always tile
    it in aligned row blocks.
    """
    flat = a.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // (LANES * SUBLANES)) * SUBLANES
    pad = rows * LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, LANES), n


@functools.partial(jax.jit, static_argnames=("interpret",))
def dane_update_array(w, grad, g_corr, anchor, eta, mu,
                      interpret: bool = True):
    """Fused update for one array of any shape."""
    w2, n = _pad_2d(w)
    g2, _ = _pad_2d(grad)
    c2, _ = _pad_2d(g_corr)
    a2, _ = _pad_2d(anchor)
    out = dane_update_2d(w2, g2, c2, a2, eta, mu, interpret=interpret)
    return out.reshape(-1)[:n].reshape(w.shape)


def dane_update(w_tree, grad_tree, corr_tree, anchor_tree, eta, mu,
                interpret: bool | None = None):
    """Apply the fused FedDANE step leaf-wise over parameter pytrees."""
    if interpret is None:
        interpret = _on_cpu()
    return jax.tree_util.tree_map(
        lambda w, g, c, a: dane_update_array(w, g, c, a, eta, mu,
                                             interpret=interpret),
        w_tree, grad_tree, corr_tree, anchor_tree)


def dane_update_masked(w_tree, grad_tree, corr_tree, anchor_tree, eta, mu,
                       valid, interpret: bool | None = None):
    """Fused FedDANE step over *device-stacked* pytrees with a step mask.

    Leaves carry a leading device axis K; ``valid`` is a ``(K,)`` 0/1
    vector.  Devices with ``valid == 0`` take an identity step (used by
    the batched round engine to make stacking-pad batches no-ops).  The
    kernel itself runs unmasked over the flattened (K * rows, LANES)
    view — one launch per leaf for all devices — and the select is a
    single cheap elementwise op on top.
    """
    if interpret is None:
        interpret = _on_cpu()
    new = dane_update(w_tree, grad_tree, corr_tree, anchor_tree, eta, mu,
                      interpret=interpret)
    def select(n, o):
        keep = valid.reshape(valid.shape + (1,) * (n.ndim - 1)) > 0
        return jnp.where(keep, n, o)
    return jax.tree_util.tree_map(select, new, w_tree)


@functools.partial(jax.jit, static_argnames=("rows_per_dev", "interpret"))
def _flat_masked_jit(wf, gf, cf, af, eta, mu, valid, rows_per_dev,
                     interpret):
    return dane_update_flat(wf, gf, cf, af, eta, mu, valid,
                            rows_per_dev, interpret=interpret)


def dane_update_flat_masked(wf, gf, cf, af, eta, mu, valid,
                            rows_per_dev: int,
                            interpret: bool | None = None):
    """Masked FedDANE step on flat-packed ``(K*rows, LANES)`` buffers.

    The whole-pytree analogue of :func:`dane_update_masked`: operands
    come from ``kernels.flatpack`` packing, the launch count drops from
    one-per-leaf to ONE, and the ``(K,)`` ``valid`` mask is resolved
    inside the kernel via a per-row mask column (no post-hoc select).
    Per-element arithmetic is identical to the per-leaf kernel, so the
    two paths agree bitwise (tests/test_kernels.py pins this).
    """
    if interpret is None:
        interpret = _on_cpu()
    return _flat_masked_jit(wf, gf, cf, af, eta, mu, valid, rows_per_dev,
                            interpret)


def dane_update_tree_masked(w_tree, grad_tree, corr_tree, anchor_tree,
                            eta, mu, valid,
                            interpret: bool | None = None):
    """Flat-packed masked step with pytree in/out: pack -> ONE kernel
    launch -> unpack.  Drop-in replacement for :func:`dane_update_masked`
    used by the batched solver's default ``"flat"`` mode."""
    spec = flatpack.flat_spec(
        jax.tree_util.tree_map(lambda x: x[0], w_tree))
    k = jax.tree_util.tree_leaves(w_tree)[0].shape[0]
    wf = flatpack.pack_stacked(spec, w_tree, k)
    gf = flatpack.pack_stacked(spec, grad_tree, k)
    cf = flatpack.pack_stacked(spec, corr_tree, k)
    af = flatpack.pack_stacked(spec, anchor_tree, k)
    out = dane_update_flat_masked(wf, gf, cf, af, eta, mu, valid,
                                  spec.rows, interpret=interpret)
    return flatpack.unpack_stacked(spec, out, k)


# ---------------------------------------------------------------------------
# flash attention with GQA layout handling
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    interpret: bool = True):
    """q: (B, S, H, hd); k, v: (B, T, Kv, hd) -> (B, S, H, hd).

    GQA (Kv < H) never materializes repeated K/V: the ``group = H/Kv``
    query heads sharing one KV head are folded into that head's query
    *rows* inside the ``to3`` reshape — ``(B*Kv, group*S, hd)`` queries
    against ``(B*Kv, T, hd)`` KV — and the kernel recovers each row's
    true sequence position as ``row % S`` (``causal_period``).  Row-wise
    online softmax makes this exactly the repeated-KV computation
    without the ``group``-fold K/V traffic and memory.
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    group = H // Kv
    to3 = lambda a: a.transpose(0, 2, 1, 3).reshape(
        B * a.shape[2], -1, hd)
    # head h = kv * group + g shares KV head kv (jnp.repeat ordering)
    q3 = q.reshape(B, S, Kv, group, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B * Kv, group * S, hd)
    o = flash_attention_3d(q3, to3(k), to3(v), causal=causal,
                           causal_period=S, interpret=interpret)
    return o.reshape(B, Kv, group, S, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(B, S, H, hd)
