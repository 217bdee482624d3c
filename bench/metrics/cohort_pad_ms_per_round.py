"""``cohort_pad_ms_per_round`` (ms): host time in the program's
``cohort.pad`` spans (``data/batching.py`` ``stack_device_batches``:
padding and stacking one cohort) and ``stream.pad`` spans
(``core/engine.py`` ``_run_streaming``: padding the chunk's cohorts to
one batch count and stacking the scan's inputs) per round of the traced
window, summed as ``client_make_ms_per_round`` sums.  The two never
nest in one another."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_client_make_ms",
    pathlib.Path(__file__).with_name("client_make_ms_per_round.py"))
_make = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_make)


def read(ctx):
    return _make.span_ms_per_round(ctx, ("cohort.pad", "stream.pad"))
