"""``local_solve_roofline`` (%): the least time the chips could take for
the window's local solves, over the local-solve kernel's time.  The
least time is the larger of the solves' operations over peak FLOP/s and
the solve cohorts' bytes, read once, over peak HBM bandwidth; both come
from unpadded sample counts (``round_work`` of the model module).  The
bound that applies is ``ctx.bound["local_solve"]``."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_local_solve_ms",
    pathlib.Path(__file__).with_name("local_solve_ms_per_round.py"))
_ls = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ls)


def read(ctx):
    t = _ls.kernel_s(ctx)
    if t is None:
        return None
    flops = ctx.work["solve_flops"] / (ctx.peaks["flops_per_s"] * ctx.chips)
    mem = ctx.work["solve_bytes"] / (ctx.peaks["hbm_bytes_per_s"]
                                     * ctx.chips)
    ctx.bound["local_solve"] = "compute" if flops >= mem else "memory"
    return 100.0 * max(flops, mem) / t
