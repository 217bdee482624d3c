"""The harness finds cells by name from data files, the generator's
cohorts are a pure function of the seed, and the chip guards hold."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cohorts, harness  # noqa: E402

CELLS = ["femnist_logreg.k10_e20", "synthetic_1_1_logreg.stream_n1m_k10"]


def test_benchmark_json_is_valid():
    assert harness.validate(harness.load_benchmark()) == []


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_loads_its_files(name):
    cell = harness.load_cell(name)
    assert cell.model is not None and cell.datagen is not None
    assert cell.limits and set(cell.limits) <= {"loss_gap", "change_gap"}
    assert {m["name"] for m in cell.end_to_end} == {"rounds_per_s",
                                                    "setup_s"}
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert ("cohort_fetch_ms_per_round" in cell.readers) == (
        cell.traffic["client_source"] == "streaming")


def _snapshot(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_added_as_new_files_is_found_and_valid(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark()
    before = _snapshot(tmp_path)
    # the new cell: one traffic file, one limits file, one entry
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          "k10_e20.json").read_text())
    traffic.update(devices_per_round=20, local_epochs=1)
    (tmp_path / "bench" / "traffic" / "k20_e1.json").write_text(
        json.dumps(traffic))
    shutil.copy(ROOT / "bench" / "limits" / f"{CELLS[0]}.json",
                tmp_path / "bench" / "limits" / "femnist_logreg.k20_e1.json")
    bench["workloads"].append({
        "name": "femnist_logreg.k20_e1", "config": "femnist_logreg",
        "traffic": "k20_e1", "chips": 1, "why": "a test cell"})
    for m in bench["per_layer"]:
        if CELLS[0] in m["workloads"]:
            m["workloads"].append("femnist_logreg.k20_e1")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert harness.validate(bench, tmp_path) == []
    cell = harness.load_cell("femnist_logreg.k20_e1", tmp_path)
    assert cell.traffic["devices_per_round"] == 20
    assert cell.readers.keys() == harness.load_cell(
        CELLS[0], tmp_path).readers.keys()
    after = _snapshot(tmp_path)
    assert all(after[p] == b for p, b in before.items())


def test_validate_reports_a_missing_file(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark()
    bench["workloads"].append({
        "name": "femnist_logreg.nope", "config": "femnist_logreg",
        "traffic": "nope", "chips": 1, "why": "no traffic file"})
    bad = harness.validate(bench, tmp_path)
    assert len(bad) == 1 and "femnist_logreg.nope" in bad[0]


def _schedule(name, seed, num_devices=None):
    cell = harness.load_cell(name)
    n = num_devices or cell.num_devices
    data = cell.datagen.Data(cell.config, n,
                             cohorts.derived_seeds(seed)["data"])
    return data, cohorts.make_schedule(cell.traffic, n, seed, data.sizes,
                                       data.batch_size)


@pytest.mark.parametrize("name", CELLS)
def test_cohorts_are_a_pure_function_of_the_seed(name):
    big = 2**31 + 7
    _, a = _schedule(name, big)
    _, b = _schedule(name, big)
    _, c = _schedule(name, big + 1)
    k = a.warmup.shape[-1]
    assert a.warmup.shape[1:] == a.window.shape[1:] == (2, k)
    assert np.array_equal(a.warmup, b.warmup)
    assert np.array_equal(a.window, b.window)
    assert not np.array_equal(a.window, c.window)
    for rounds in (a.warmup, a.window):
        for s1, s2 in rounds:
            assert len(set(s1)) == len(s1) and len(set(s2)) == len(s2)


def test_population_cohorts_hold_the_same_clients_for_every_seed():
    data, a = _schedule(CELLS[1], 11)
    _, b = _schedule(CELLS[1], 12)
    per = a.chunk_rounds * 2 * a.window.shape[-1]
    for i in range(0, a.window.size, per):
        assert sorted(a.window.ravel()[i:i + per]) == \
            sorted(b.window.ravel()[i:i + per])
    assert sorted(a.warmup.ravel()) == sorted(b.warmup.ravel())
    # no client twice in a run: the warm-up touches none of the window's
    ids = np.concatenate([a.warmup.ravel(), a.window.ravel()])
    assert len(np.unique(ids)) == len(ids)


def test_warmup_covers_every_padded_shape_of_the_window():
    data, s = _schedule(CELLS[1], 3)
    warm = cohorts.covered_pairs(s.warmup, data.sizes, data.batch_size)
    win = cohorts.covered_pairs(s.window_rounds(4), data.sizes,
                                data.batch_size)
    assert win <= warm
    assert {r for _, r in warm} == {8, 16, 32, 64, 128}


def test_batch_bucket_is_the_padded_batch_count():
    assert list(cohorts.batch_bucket(np.array([1, 10, 11, 50, 640, 641,
                                               1000]), 10)) == \
        [1, 1, 2, 8, 64, 128, 128]


def test_seeds_beyond_32_bits_give_small_derived_seeds():
    s = cohorts.derived_seeds(2**33 + 5)
    assert all(0 <= v < 2**31 for v in s.values())
    assert s != cohorts.derived_seeds(5)


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=240)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_device_info_names_the_device_and_refuses_the_cpu(capsys):
    from bench import run
    with pytest.raises(SystemExit) as exc:
        run.device_info(1)
    assert exc.value.code == 2
    assert "cpu" in capsys.readouterr().err


def test_alone_the_benchmark_files_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, env=env, timeout=240)
    assert proc.returncode != 0 and "{" not in proc.stdout
    assert "repro" in proc.stderr
