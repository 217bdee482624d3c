"""``correct`` on the CPU at a size a test run can hold: a sound run
passes, and the control and each fault a training cell can have fail
the cell's own limits.

The whole of a run is driven (``bench/run.py``'s ``main``) with only the
look for a chip skipped and the cell shrunk: fewer clients and
rounds, the published widths and the cell's E=20 local epochs kept
(at E=2 the bfloat16 control stays within 2% of the reference; the
epochs are what carry its rounding into the losses).  On the CPU the program
takes its host-loop path; the faults are planted in the server code
that path runs.
"""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness, run  # noqa: E402

CELLS = {"femnist_logreg.k10_e20": 12,
         "synthetic_1_1_logreg.stream_n1m_k10": 5000}
LOAD_CELL = harness.load_cell


def small_cell(name):
    cell = LOAD_CELL(name)
    cell.traffic.update(devices_per_round=4, chunk_rounds=3,
                        max_window_chunks=2, num_devices=CELLS[name])
    return cell


@pytest.fixture
def bench_run(monkeypatch, capsys):
    """``main`` of a shrunk cell without the look for a chip (and
    without the persistent compile cache, which is process-wide);
    returns its result line as a dict."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "load_cell",
                        lambda name, *a, **k: small_cell(name))
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})

    def go(name, seed=2**31 + 3):
        assert run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", "0.01", "--trace", "0"]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_sound_run_is_correct(bench_run, name):
    out = bench_run(name)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(harness.load_cell(name).limits)
    assert out["attempted"] == 3 and out["failed"] == 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        bench_run, monkeypatch, name):
    from repro.core import server
    monkeypatch.setattr(server, "server_step",
                        lambda w0, w_agg, opt=None, opt_state=None:
                        (w0, opt_state))
    out = bench_run(name)
    assert out["correct"] is False
    if "change_gap" in out["checks"]:
        assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_half_the_cohort_left_out_is_not_correct(bench_run, monkeypatch,
                                                 name):
    from repro.core import pytree as pt
    from repro.core import server
    monkeypatch.setattr(server, "aggregate_mean",
                        lambda updates: pt.mean(updates[:len(updates) // 2]))
    assert bench_run(name)["correct"] is False


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_bfloat16_control_is_not_correct(name):
    """The plain reference in bfloat16, put in the program's place."""
    cell = small_cell(name)
    s = run.Setup(cell, 5, program=False)
    ref = run.reference(s)
    ctl = run.reference(s, dtype=jnp.bfloat16)
    rounds = np.arange(1, len(ref[0]) + 1)
    correct, checks = compare.judge(
        run.readings(s, ctl[0], rounds, ctl[1], ref), cell.limits)
    assert correct is False, checks
