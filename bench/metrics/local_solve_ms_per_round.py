"""``local_solve_ms_per_round`` (ms): device time of the local-solve
kernel per round.  The trace names a Pallas kernel only as a
``tpu_custom_call``; the round program's kernel names come from its
compiled IR.  Where every Pallas kernel of the round program is a
local-solve kernel, their time is the local solve's; otherwise this
reads nothing."""

#: kernels/local_solve.py (fused epoch, fused step) and
#: kernels/dane_update.py (flat pack, per leaf)
LOCAL_SOLVE = {"_epoch_kernel", "_step_kernel", "_flat_kernel", "_kernel"}


def kernel_s(ctx):
    """Local-solve kernel seconds in the traced window, or None."""
    if ctx.trace is None or not ctx.kernels:
        return None
    if not set(ctx.kernels) <= LOCAL_SOLVE:
        return None
    t = ctx.trace.mosaic_s()
    return t if t > 0 else None


def read(ctx):
    t = kernel_s(ctx)
    if t is None or ctx.rounds <= 0:
        return None
    return 1000.0 * t / ctx.rounds
