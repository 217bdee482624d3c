"""The trace reduction on a hand-made trace and on small traces recorded
on a TPU v5e (``fixtures/``), and the metric readers that use it."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.harness import load_module  # noqa: E402

FIXTURES = Path(__file__).with_name("fixtures")

HAND = {
    "devices": {"/device:TPU:0": [
        ["while.1 (f32[10])", 0, 100, 500],
        ["closed_call.9 (f32[10,784,10]) tpu_custom_call", 1, 150, 100],
        ["fusion.2 f32[10]", 0, 300, 50],
        ["copy.3 f32[10]", 0, 700, 100],
        ["copy.4 f32[10]", 0, 2000, 100],          # after the window
    ]},
    "host": [
        ["python3", "window", 50, 1000],
        ["python3", "cohort_fetch", 600, 100],
        ["python3", "PjitFunction(gather)", 620, 10],   # inside the fetch
        ["python3", "np.asarray(jax.Array)", 800, 200],
        ["main/279", "cohort_fetch", 0, 5000],          # another thread
    ],
}


def test_busy_is_the_union_of_nested_ops_in_the_window():
    red = trace.Reduced(HAND)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s() == pytest.approx(600e-9)      # [100,600] + [700,800]
    assert red.mosaic_s() == pytest.approx(100e-9)


def test_self_times_subtract_children():
    ops = dict(trace.Reduced(HAND).device_ops())
    assert ops["while.1 (f32[10])"] == pytest.approx(350e-9)
    assert ops["closed_call.9 (f32[10,784,10]) tpu_custom_call"] == \
        pytest.approx(100e-9)
    assert ops["copy.3 f32[10]"] == pytest.approx(100e-9)
    assert "copy.4 f32[10]" not in ops


def test_idle_time_goes_to_the_host_event_below_the_window():
    gaps = dict(trace.Reduced(HAND).idle_gaps())
    assert gaps == pytest.approx({"np.asarray(jax.Array)": 200e-9,
                                  "cohort_fetch": 100e-9,
                                  trace.NO_HOST: 100e-9})
    total = sum(gaps.values())
    red = trace.Reduced(HAND)
    assert total == pytest.approx(red.window_s - red.busy_s())


def test_op_label_marks_pallas_kernels():
    name = ('%closed_call.9 = (f32[10,784,10]{2,1,0:T(8,128)S(1)}, f32[10]) '
            'custom-call(f32[1,1]{1,0} %a), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
    label, mosaic = trace.op_label(name)
    assert mosaic == 1 and label.startswith("closed_call.9 ")
    assert label.endswith("tpu_custom_call")
    label, mosaic = trace.op_label("%fusion.160 = f32[2000]{0:T(1024)} "
                                   "fusion(f32[200] %x)")
    assert (label, mosaic) == ("fusion.160 f32[2000]", 0)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.Reduced({"devices": {}, "host": []})


def _fixture(tag):
    with gzip.open(FIXTURES / f"trace_{tag}.json.gz", "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("tag", ["femnist_logreg", "synthetic_1_1_logreg"])
def test_recorded_tpu_trace_reduces_as_recorded(tag):
    fx = _fixture(tag)
    red = trace.Reduced(fx)
    exp = fx["expect"]
    assert red.window_s == pytest.approx(exp["window_s"])
    assert red.busy_s() == pytest.approx(exp["busy_s"])
    assert 0 < red.busy_s() < red.window_s
    assert 0 < red.mosaic_s() < red.busy_s()
    gaps = red.idle_gaps(top=10 ** 6)
    assert sum(v for _, v in gaps) == pytest.approx(
        red.window_s - red.busy_s())
    assert len(red.device_ops()) == 10


def _ctx(tag):
    fx = _fixture(tag)
    exp = fx["expect"]
    return SimpleNamespace(
        trace=trace.Reduced(fx), rounds=exp["rounds"], chips=1,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        window_s=exp["host_window_s"], kernels=exp["kernels"],
        work=exp["work"], bound={}, fetch_s=exp["fetch_s"],
        fetch_calls=exp["fetch_calls"])


def _reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.mark.parametrize("tag", ["femnist_logreg", "synthetic_1_1_logreg"])
def test_readers_on_recorded_traces(tag):
    ctx = _ctx(tag)
    idle = _reader("device_idle_share").read(ctx)
    assert 0 < idle < 100
    ms = _reader("local_solve_ms_per_round").read(ctx)
    assert ms == pytest.approx(1000 * ctx.trace.mosaic_s() / ctx.rounds)
    roof = _reader("local_solve_roofline").read(ctx)
    assert 0 < roof < 100 and ctx.bound["local_solve"] in ("compute",
                                                           "memory")
    mfu = _reader("round_mfu").read(ctx)
    assert 0 < mfu < 100
    fetch = _reader("cohort_fetch_ms_per_round").read(ctx)
    if tag == "femnist_logreg":
        assert fetch is None            # the stacked plan never fetches
    else:
        assert fetch == pytest.approx(1000 * ctx.fetch_s / ctx.rounds)


def test_readers_read_nothing_without_their_inputs():
    ctx = SimpleNamespace(trace=None, rounds=10, chips=1, kernels=[],
                          peaks={"flops_per_s": 1.0}, window_s=1.0,
                          work={"round_flops": 0.0}, bound={},
                          fetch_s=0.0, fetch_calls=0)
    for name in ("device_idle_share", "round_mfu",
                 "local_solve_ms_per_round", "local_solve_roofline",
                 "cohort_fetch_ms_per_round"):
        assert _reader(name).read(ctx) is None, name
    # a round program holding a kernel that is no local solve
    red = trace.Reduced(HAND)
    ctx.trace, ctx.kernels = red, ["_epoch_kernel", "_agg_kernel"]
    assert _reader("local_solve_ms_per_round").read(ctx) is None
