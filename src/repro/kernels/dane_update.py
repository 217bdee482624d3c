"""Fused FedDANE local-update Pallas TPU kernel.

    w' = w - eta * (grad + (g_t - grad F_k(w0)) + mu * (w - w0))

Four model-sized operand streams + one output stream -> arithmetic
intensity ~= 6 flops / 10 bytes (bf16): strictly HBM-bandwidth-bound.
The fusion wins by reading each operand exactly once instead of the 3-4
round trips the unfused pytree expression costs, and the (rows, 128)
blocking keeps each tile VMEM-resident and lane-aligned.

eta/mu arrive as (1,1) SMEM scalars so one compiled kernel serves every
round (mu is swept in the paper's tuning grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
DEFAULT_BLOCK_ROWS = 512


def row_block(rows: int, target: int) -> int:
    """Rows per block when tiling a ``rows``-long second-minor axis.

    Mosaic takes a block whose second-minor dim is a multiple of
    :data:`SUBLANES` or the whole dim.  So: the whole dim when it is at
    most ``target``; else the largest multiple of :data:`SUBLANES` not
    above ``target`` that divides ``rows``; else (``rows`` has no such
    divisor, which a row count padded to :data:`SUBLANES` always has)
    the whole dim.
    """
    if rows <= target:
        return rows
    for bb in range(target - target % SUBLANES, 0, -SUBLANES):
        if rows % bb == 0:
            return bb
    return rows


def _kernel(eta_ref, mu_ref, w_ref, g_ref, c_ref, a_ref, out_ref):
    eta = eta_ref[0, 0]
    mu = mu_ref[0, 0]
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    out = w - eta * (g + c + mu * (w - a))
    out_ref[...] = out.astype(out_ref.dtype)


def dane_update_2d(w, grad, g_corr, anchor, eta, mu,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False):
    """Core pallas_call on a (rows, LANES) view."""
    rows = w.shape[0]
    block_rows = row_block(rows, block_rows)
    block = (block_rows, LANES)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec(block, lambda i: (i, 0))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    eta = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    mu = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[scalar, scalar, spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
    )(eta, mu, w, grad, g_corr, anchor)


def _flat_kernel(eta_ref, mu_ref, m_ref, w_ref, g_ref, c_ref, a_ref,
                 out_ref):
    """Masked update on one row block of the flat-packed buffer.

    ``m_ref`` is the per-row keep-mask column, tiled alongside the data
    blocks — the ``(K,)`` valid/steps_limit select folded into the
    launch instead of the per-leaf path's post-hoc ``jnp.where`` over
    unpacked leaves.  A lane-broadcast row mask (rather than in-kernel
    device-id arithmetic) keeps the body a handful of VPU ops and lets
    row blocks straddle device segments, so block size is a pure tiling
    choice.
    """
    eta = eta_ref[0, 0]
    mu = mu_ref[0, 0]
    keep = m_ref[...] > 0.0                           # (block_rows, 1)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    out = w - eta * (g + c + mu * (w - a))
    out_ref[...] = jnp.where(keep, out, w).astype(out_ref.dtype)


def dane_update_flat(w, grad, g_corr, anchor, eta, mu, mask,
                     rows_per_dev: int,
                     block_rows: int | None = None,
                     interpret: bool = False):
    """ONE masked launch over a ``(K*rows_per_dev, LANES)`` flat view.

    Operands are whole-pytree flat packs (``kernels.flatpack``): all
    leaves × all K devices in a single ``pallas_call``.  ``mask`` is
    the ``(K,)`` per-device step mask, expanded (one cheap XLA repeat)
    to the per-row keep column the kernel tiles with the data.

    ``block_rows=None`` picks the backend's sweet spot: on TPU
    :func:`row_block` of the total row count and ``DEFAULT_BLOCK_ROWS``
    (VMEM-bounded tiles); in interpret mode the whole buffer as ONE
    block — the interpreter's cost scales with grid steps × full-array
    traffic, so a single grid step is the fast shape on CPU.
    """
    total_rows = w.shape[0]
    k = total_rows // rows_per_dev
    if block_rows is None:
        block_rows = total_rows if interpret else DEFAULT_BLOCK_ROWS
    block_rows = row_block(total_rows, block_rows)
    nblocks = total_rows // block_rows
    m_rows = jnp.repeat(jnp.asarray(mask, jnp.float32), rows_per_dev) \
        .reshape(total_rows, 1)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    mspec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    eta = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    mu = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _flat_kernel,
        grid=(nblocks,),
        in_specs=[scalar, scalar, mspec, spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
    )(eta, mu, m_rows, w, grad, g_corr, anchor)
