"""Fused codec decode+aggregate Pallas TPU kernel.

    agg = sum_k mask_k * scale_k * vals_k / max(sum_k mask_k, 1)

One launch dequantizes the whole stacked cohort buffer and reduces it
to the server aggregate: the ``(K, rows, 128)`` transmitted-values
stack (flat-packed layout from ``kernels/flatpack.py``) is read exactly
once, against the 2-3 model-sized round trips the unfused
dequantize -> mask -> mean expression costs.  Like ``dane_update``,
this is HBM-bandwidth-bound at ~2 flops/byte — fusing is what makes
compression a speedup instead of a tax on the aggregation path.

Per-client scales and the active mask ride as ``(K, 1)`` columns tiled
alongside every row block (the ``dane_update_flat`` mask idiom), so the
inactive-client zeroing, the dequantize multiply, and the cohort mean
all happen inside the same VPU loop.  Codecs with a shared linear
post-transform (int8's inverse rotation) apply it to the ``(rows, 128)``
aggregate AFTER this launch — K× less work than per-client, valid by
linearity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dane_update import row_block
from repro.kernels.flatpack import LANES

#: Smaller than dane_update's 512: each grid instance holds the block
#: for ALL K clients (K * block_rows * 128 * 4B of VMEM).
DEFAULT_BLOCK_ROWS = 256


def _agg_kernel(s_ref, m_ref, v_ref, out_ref):
    """Dequantize + masked mean over the cohort axis, one row block."""
    m = m_ref[...]                                  # (K, 1)
    w = s_ref[...] * m                              # (K, 1) dequant weights
    cnt = jnp.maximum(jnp.sum(m), 1.0)
    v = v_ref[...].astype(jnp.float32)              # (K, block_rows, LANES)
    acc = jnp.sum(v * w[:, :, None], axis=0) / cnt
    out_ref[...] = acc.astype(out_ref.dtype)


def _agg_sum_kernel(s_ref, m_ref, v_ref, out_ref):
    """Dequantize + masked SUM over the cohort axis (no normalization):
    the per-shard partial of the sharded aggregate."""
    m = m_ref[...]                                  # (K_local, 1)
    w = s_ref[...] * m                              # (K_local, 1)
    v = v_ref[...].astype(jnp.float32)
    acc = jnp.sum(v * w[:, :, None], axis=0)
    out_ref[...] = acc.astype(out_ref.dtype)


def _launch_agg(kernel, vals, scales, mask, block_rows, interpret):
    k, rows, _ = vals.shape
    if block_rows is None:
        block_rows = rows if interpret else DEFAULT_BLOCK_ROWS
    block_rows = row_block(rows, block_rows)
    scales = jnp.asarray(scales, jnp.float32).reshape(k, 1)
    mask = jnp.asarray(mask, jnp.float32).reshape(k, 1)
    kspec = pl.BlockSpec((k, 1), lambda i: (0, 0))
    vspec = pl.BlockSpec((k, block_rows, LANES), lambda i: (0, i, 0))
    out_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[kspec, kspec, vspec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(scales, mask, vals)


def codec_aggregate(vals, scales, mask, block_rows: int | None = None,
                    interpret: bool = False):
    """ONE fused launch: ``(K, rows, LANES)`` encoded cohort -> the
    ``(rows, LANES)`` dequantized masked-mean aggregate.

    ``scales`` and ``mask`` are ``(K,)`` float32 (per-client dequant
    scale; 0/1 active mask — inactive clients contribute neither signal
    nor count, so an all-inactive cohort yields the zero aggregate and
    the round stays a no-op).  ``block_rows=None`` picks the backend
    sweet spot exactly like ``dane_update_flat``: ``row_block`` of
    ``rows`` and :data:`DEFAULT_BLOCK_ROWS` on TPU, the whole buffer as
    ONE block in interpret mode.
    """
    return _launch_agg(_agg_kernel, vals, scales, mask, block_rows,
                       interpret)


def codec_aggregate_partial(vals, scales, mask,
                            block_rows: int | None = None,
                            interpret: bool = False):
    """Per-shard HALF of the sharded aggregate: ONE fused launch over
    this shard's ``(K_local, rows, LANES)`` cohort slice returning the
    raw masked dequantized SUM (no count normalization).

    Inside a ``shard_map``-ed round body each shard launches this on its
    K/D clients; the partial sums and the local mask counts are then
    ``psum``-ed over the mesh axis and divided exactly once, so the
    sharded aggregate equals :func:`codec_aggregate` on the full cohort
    to float-association order (tests/test_kernels.py pins the oracle;
    tests/test_sharding.py pins mesh8-vs-mesh1 end to end).
    """
    return _launch_agg(_agg_sum_kernel, vals, scales, mask, block_rows,
                       interpret)
