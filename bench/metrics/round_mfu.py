"""``round_mfu`` (%): the operations the window's rounds need, counted
from their cohorts' unpadded sample counts (local solves, phase-A and
correction gradients, eval), per second of the window, over the chips'
peak."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.work["round_flops"] <= 0:
        return None
    rate = ctx.work["round_flops"] / ctx.window_s
    return 100.0 * rate / (ctx.peaks["flops_per_s"] * ctx.chips)
