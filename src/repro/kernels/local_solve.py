"""Fused Pallas local-solve kernels for the paper's linear model family.

The FedDANE local subproblem (Alg. 2 line 7) is E epochs of minibatch
SGD whose per-step gradient is ``grad F_k(w) + corr + mu (w - w0)``.
For multinomial logistic regression — the paper's convex case, batches
``{"x": (B, d), "y": (B,)}`` and params ``{"w": (d, C), "b": (C,)}`` —
the whole step is small enough to fuse into ONE launch:

- :func:`linear_logistic_step`: forward ``X_b @ w + b``, softmax
  residual ``(p - onehot(y)) / B``, backprop ``X_bᵀ r`` / ``Σ r``,
  correction + prox term, masked SGD update — grid ``(K, row-blocks)``
  over the batch rows with VMEM gradient accumulators, masked-K via an
  SMEM per-device mask;
- :func:`local_epoch`: the same step *scanned over the batch axis
  inside the kernel* — grid ``(K, E*nb)`` with the running weights in
  VMEM scratch, so a whole local solve is ONE ``pallas_call`` (the
  per-step valid/cutoff mask arrives precomputed, one client's row of
  it in SMEM at a time).

Both recompute the analytic softmax-NLL gradient rather than calling
``jax.grad``, so they are *not* bit-identical to the XLA autodiff path —
parity versus the looped reference is pinned at atol 1e-5
(tests/test_kernels.py, tests/test_local_solve.py).  Selection happens
through the ``SolverSpec`` registry in ``core/client.py``
(:data:`LINEAR_LOGISTIC`, registered for ``models.small.logreg_loss``);
models the spec cannot express fall back to the generic flat-pack path.

On CPU the kernels run in interpret mode (grid executes sequentially in
Python — correct but slow, which is why ``local_solver="auto"`` keeps
CPU on the flat path); on TPU they compile to Mosaic, where the small
``(d, C)`` operand tiles want lane-aligned dims for peak MXU use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dane_update import row_block

#: VMEM budget gate for the fused kernels: per-device operand + scratch
#: footprint (f32 words) beyond which selection falls back to the flat
#: path.  Conservative vs the ~16 MB/core TPU VMEM.
MAX_FUSED_ELEMS = 1 << 20


def _softmax_residual(x, y, w, b, batch_total: int, num_classes: int):
    """(p - onehot(y)) / batch_total and its backprop pieces, f32.

    ``x``: (bb, d); ``y``: (bb, 1) int32; ``w``: (d, C); ``b``: (1, C).
    Returns (gw_partial (d, C), gb_partial (1, C)).
    """
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b
    zmax = jnp.max(logits, axis=1, keepdims=True)
    ez = jnp.exp(logits - zmax)
    p = ez / jnp.sum(ez, axis=1, keepdims=True)
    classes = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], num_classes), 1)
    r = (p - (classes == y).astype(jnp.float32)) / batch_total
    gw = jax.lax.dot_general(x, r, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    gb = jnp.sum(r, axis=0, keepdims=True)
    return gw, gb


def _step_kernel(eta_ref, mu_ref, mask_ref, x_ref, y_ref, w_ref, b_ref,
                 cw_ref, cb_ref, w0_ref, b0_ref, ow_ref, ob_ref,
                 gw_ref, gb_ref, *, num_row_blocks: int,
                 batch_total: int, num_classes: int):
    k = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)
        gb_ref[...] = jnp.zeros_like(gb_ref)

    w = w_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    gw, gb = _softmax_residual(
        x_ref[0].astype(jnp.float32), y_ref[0], w, b,
        batch_total, num_classes)
    gw_ref[...] += gw
    gb_ref[...] += gb

    @pl.when(t == num_row_blocks - 1)
    def _update():
        eta = eta_ref[0, 0]
        mu = mu_ref[0, 0]
        keep = mask_ref[0, k] > 0.0
        w0 = w0_ref[0].astype(jnp.float32)
        b0 = b0_ref[0].astype(jnp.float32)
        wn = w - eta * (gw_ref[...] + cw_ref[0].astype(jnp.float32)
                        + mu * (w - w0))
        bn = b - eta * (gb_ref[...] + cb_ref[0].astype(jnp.float32)
                        + mu * (b - b0))
        ow_ref[0] = jnp.where(keep, wn, w).astype(ow_ref.dtype)
        ob_ref[0] = jnp.where(keep, bn, b).astype(ob_ref.dtype)


def linear_logistic_step(w, batch, corr, w0, *, eta, mu, mask,
                         block_b: int = 128, interpret: bool = False):
    """One fused masked SGD step for K stacked logistic regressions.

    ``w``/``corr``: ``{"w": (K, d, C), "b": (K, C)}``; ``batch``:
    ``{"x": (K, B, d), "y": (K, B)}``; ``w0``: unstacked anchor
    ``{"w": (d, C), "b": (C,)}``; ``mask``: (K,) step mask.  Grid is
    (K, B/row-block): each program consumes a row block of the batch,
    accumulating ``Xᵀr`` in VMEM scratch; the final block applies the
    correction/prox/update and the masked select.
    """
    K, d, C = w["w"].shape
    B = batch["x"].shape[1]
    bb = row_block(B, block_b)
    nrb = B // bb
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    eta2 = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    mu2 = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    mask2 = jnp.asarray(mask, jnp.float32).reshape(1, K)
    kernel = functools.partial(
        _step_kernel, num_row_blocks=nrb, batch_total=B, num_classes=C)
    ow, ob = pl.pallas_call(
        kernel,
        grid=(K, nrb),
        in_specs=[
            scalar, scalar, scalar,
            pl.BlockSpec((1, bb, d), lambda k, t: (k, t, 0)),   # x
            pl.BlockSpec((1, bb, 1), lambda k, t: (k, t, 0)),   # y
            pl.BlockSpec((1, d, C), lambda k, t: (k, 0, 0)),    # w
            pl.BlockSpec((1, 1, C), lambda k, t: (k, 0, 0)),    # b
            pl.BlockSpec((1, d, C), lambda k, t: (k, 0, 0)),    # corr w
            pl.BlockSpec((1, 1, C), lambda k, t: (k, 0, 0)),    # corr b
            pl.BlockSpec((1, d, C), lambda k, t: (0, 0, 0)),    # w0
            pl.BlockSpec((1, 1, C), lambda k, t: (0, 0, 0)),    # b0
        ],
        out_specs=[
            pl.BlockSpec((1, d, C), lambda k, t: (k, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda k, t: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, d, C), w["w"].dtype),
            jax.ShapeDtypeStruct((K, 1, C), w["b"].dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, C), jnp.float32),   # grad-w accumulator
            pltpu.VMEM((1, C), jnp.float32),   # grad-b accumulator
        ],
        interpret=interpret,
    )(eta2, mu2, mask2,
      batch["x"].astype(jnp.float32),
      batch["y"].astype(jnp.int32).reshape(K, B, 1),
      w["w"], w["b"].reshape(K, 1, C),
      corr["w"], corr["b"].reshape(K, 1, C),
      w0["w"].reshape(1, d, C), w0["b"].reshape(1, 1, C))
    return {"w": ow, "b": ob.reshape(K, C)}


def _epoch_kernel(eta_ref, mu_ref, m_ref, x_ref, y_ref, cw_ref, cb_ref,
                  w0_ref, b0_ref, ow_ref, ob_ref, ws_ref, bs_ref, *,
                  num_steps: int, batch_total: int, num_classes: int):
    k = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        ws_ref[...] = w0_ref[0].astype(jnp.float32)
        bs_ref[...] = b0_ref[0].astype(jnp.float32)

    w = ws_ref[...]
    b = bs_ref[...]
    gw, gb = _softmax_residual(
        x_ref[0, 0].astype(jnp.float32), y_ref[0, 0], w, b,
        batch_total, num_classes)
    eta = eta_ref[0, 0]
    mu = mu_ref[0, 0]
    w0 = w0_ref[0].astype(jnp.float32)
    b0 = b0_ref[0].astype(jnp.float32)
    keep = m_ref[0, 0, t] > 0.0
    wn = w - eta * (gw + cw_ref[0].astype(jnp.float32) + mu * (w - w0))
    bn = b - eta * (gb + cb_ref[0].astype(jnp.float32) + mu * (b - b0))
    ws_ref[...] = jnp.where(keep, wn, w)
    bs_ref[...] = jnp.where(keep, bn, b)

    @pl.when(t == num_steps - 1)
    def _out():
        ow_ref[0] = ws_ref[...].astype(ow_ref.dtype)
        ob_ref[0] = bs_ref[...].astype(ob_ref.dtype)


def local_epoch(w0, corr, batches, *, eta, mu, num_epochs: int,
                step_mask, interpret: bool = False):
    """A WHOLE E-epoch local solve for K stacked logistic regressions
    in ONE launch.

    ``w0``: unstacked anchor; ``corr``: K-stacked correction;
    ``batches``: ``{"x": (K, nb, B, d), "y": (K, nb, B)}``;
    ``step_mask``: (K, E*nb) per-step keep mask in scan order (epochs
    outer, batches inner) — the valid/cutoff semantics of the generic
    solver, precomputed closed-form by the caller.  It is tiled into
    SMEM one ``(1, E*nb)`` client row at a time: the whole table
    outgrows the chip's 1 MiB of SMEM at K·E·nb ≥ 2^18.  The running
    weights live in VMEM scratch across the sequential step axis; the
    batch index is ``t % nb`` via the BlockSpec index map.
    """
    d, C = w0["w"].shape
    K, nb, B = batches["x"].shape[:3]
    T = num_epochs * nb
    assert step_mask.shape == (K, T), (step_mask.shape, K, T)
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    eta2 = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    mu2 = jnp.asarray(mu, jnp.float32).reshape(1, 1)
    kernel = functools.partial(
        _epoch_kernel, num_steps=T, batch_total=B, num_classes=C)
    ow, ob = pl.pallas_call(
        kernel,
        grid=(K, T),
        in_specs=[
            scalar, scalar,
            pl.BlockSpec((1, 1, T), lambda k, t: (k, 0, 0),
                         memory_space=pltpu.SMEM),              # mask row
            pl.BlockSpec((1, 1, B, d), lambda k, t: (k, t % nb, 0, 0)),
            pl.BlockSpec((1, 1, B, 1), lambda k, t: (k, t % nb, 0, 0)),
            pl.BlockSpec((1, d, C), lambda k, t: (k, 0, 0)),    # corr w
            pl.BlockSpec((1, 1, C), lambda k, t: (k, 0, 0)),    # corr b
            pl.BlockSpec((1, d, C), lambda k, t: (0, 0, 0)),    # w0
            pl.BlockSpec((1, 1, C), lambda k, t: (0, 0, 0)),    # b0
        ],
        out_specs=[
            pl.BlockSpec((1, d, C), lambda k, t: (k, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda k, t: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, d, C), w0["w"].dtype),
            jax.ShapeDtypeStruct((K, 1, C), w0["b"].dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, C), jnp.float32),   # running weights
            pltpu.VMEM((1, C), jnp.float32),   # running bias
        ],
        interpret=interpret,
    )(eta2, mu2, jnp.asarray(step_mask, jnp.float32).reshape(K, 1, T),
      batches["x"].astype(jnp.float32),
      batches["y"].astype(jnp.int32).reshape(K, nb, B, 1),
      corr["w"], corr["b"].reshape(K, 1, C),
      w0["w"].reshape(1, d, C), w0["b"].reshape(1, 1, C))
    return {"w": ow, "b": ob.reshape(K, C)}


# ---------------------------------------------------------------------------
# SolverSpec registration (core/client.py hook)
# ---------------------------------------------------------------------------

def _is_linear_logistic(w0, batches) -> bool:
    """Shape gate: the stacked workload is the paper's logreg family."""
    if not (isinstance(w0, dict) and set(w0) == {"w", "b"}
            and isinstance(batches, dict) and set(batches) == {"x", "y"}):
        return False
    w, b, x, y = w0["w"], w0["b"], batches["x"], batches["y"]
    if not (w.ndim == 2 and b.ndim == 1 and x.ndim == 4 and y.ndim == 3):
        return False
    d, C = w.shape
    if b.shape != (C,) or x.shape[3] != d:
        return False
    if not jnp.issubdtype(y.dtype, jnp.integer):
        return False
    return True


def _select(w0, batches, num_epochs: int):
    if not _is_linear_logistic(w0, batches):
        return None
    d, C = w0["w"].shape
    _, nb, B = batches["x"].shape[:3]
    if B * d + 2 * d * C > MAX_FUSED_ELEMS:
        return None                 # operands exceed the VMEM budget
    # the whole-epoch scan additionally wants a modest grid length
    if num_epochs * nb <= 4096:
        return "fused_epoch"
    return "fused_step"


def _make_step(eta, interpret: bool):
    def step(w, batch, corr, w0, mu, mask):
        return linear_logistic_step(w, batch, corr, w0, eta=eta, mu=mu,
                                    mask=mask, interpret=interpret)
    return step


def _make_epoch(eta, num_epochs: int, interpret: bool):
    def solve(w0, corr, mu, batches, step_mask):
        return local_epoch(w0, corr, batches, eta=eta, mu=mu,
                           num_epochs=num_epochs, step_mask=step_mask,
                           interpret=interpret)
    return solve


def register() -> None:
    """Register the linear-logistic fused solver with core/client.py."""
    from repro.core.client import SolverSpec, register_local_solver
    from repro.models.small import logreg_loss
    register_local_solver(logreg_loss, SolverSpec(
        name="linear_logistic",
        summary="softmax-regression step/epoch fused into one launch",
        select=_select,
        make_step=_make_step,
        make_epoch=_make_epoch,
    ))
