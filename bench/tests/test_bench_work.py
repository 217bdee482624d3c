"""Work counts against hand counts, and the peak table."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import load_module  # noqa: E402
from bench.peaks import PEAKS, peaks_for  # noqa: E402

MODEL = load_module(ROOT / "bench" / "models" / "feddane_logreg.py")


def test_round_work_matches_a_hand_count():
    config = {"num_features": 784, "num_classes": 10}
    traffic = {"local_epochs": 20}
    w = MODEL.round_work(config, traffic, gather_sizes=[100, 50],
                         solve_sizes=[30, 20], eval_sizes=[10])
    dc = 784 * 10
    # E epochs of a 4dC gradient step over the 50 solve samples
    assert w["solve_flops"] == 20 * 50 * 4 * dc == 31_360_000
    # + one 4dC gradient over gather (150) and solve (50) samples at w0
    # + one 2dC forward over the 10 eval samples
    assert w["round_flops"] == 31_360_000 + 200 * 4 * dc + 10 * 2 * dc
    # the 50 samples read once (784 float32 features + an int32 label),
    # and K=2 anchors, corrections and solutions of (784 + 1) x 10
    assert w["solve_bytes"] == 50 * 785 * 4 + 3 * 2 * 785 * 10 * 4


def test_round_work_counts_no_padding():
    config = {"num_features": 60, "num_classes": 10}
    a = MODEL.round_work(config, {"local_epochs": 1}, [51], [51], [51])
    b = MODEL.round_work(config, {"local_epochs": 1}, [60], [60], [60])
    assert b["round_flops"] / a["round_flops"] == pytest.approx(60 / 51)


def test_peaks_of_the_v5e_and_no_default():
    p = peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert all("source" in row for row in PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
