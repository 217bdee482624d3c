"""``chip_smoke.py``'s phases at tiny sizes on the CPU.

The script itself refuses to run without a TPU (``main`` must fail
here), so these tests drive its phase functions directly: what they
build, compare and check stays in step with the program.  On the CPU
the knobs are pinned to what ``auto`` picks on the chip, and Pallas
runs in interpret mode.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
CHIP_KNOBS = dict(engine="batched", round_driver="scan")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def main_phase(cs):
    return cs.phase_main(num_devices=20, k=4, epochs=2, rounds=2,
                         **CHIP_KNOBS)


def test_main_phase_runs_and_flags_interpret_mode(cs, main_phase):
    a = main_phase
    assert a["resolved"]["engine"] == "batched"
    assert a["resolved"]["round_driver"] == "scan"
    assert len(a["losses"]) == 2 and np.all(np.isfinite(a["losses"]))
    assert a["compile_s"] > 0
    # the scanned round program was compiled and inspected; on the CPU
    # its kernels run interpreted, which the chip check must refuse
    assert "chunk" in a["programs"]
    assert cs.check_main(a) == [
        "the scanned round program holds no tpu_custom_call"]


def test_reference_phase_matches_main_phase(cs, main_phase):
    b = cs.phase_reference(main_phase, epochs=2)
    assert b["batched_python"]["resolved"]["round_driver"] == "python"
    assert b["loop"]["resolved"]["engine"] == "loop"
    for name in ("batched_python", "loop"):
        par = b[name]["parity"]
        assert par["ok"], (name, par)
        # on the CPU every path multiplies at full f32 precision
        assert par["params"] < 1e-5 and par["loss"] < 1e-5, (name, par)


def test_parity_flags_a_diverged_run(cs, main_phase):
    a = main_phase
    off = dict(a, params={k: v + 1.0 for k, v in a["params"].items()})
    assert not cs.parity(off, a, a["params0"], 2.3)["ok"]


def test_population_phase_bounded(cs):
    c = cs.phase_population(num_devices=10**6, k=4, epochs=1, rounds=2,
                            eval_clients=8, **CHIP_KNOBS)
    assert c["ok"], c
    assert c["stats"]["devices"] == 10**6
    assert c["stats"]["materialized_clients"] <= c["max_clients"] == 24


def test_main_refuses_without_tpu(cs, capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_mesh_phase_on_four_host_devices():
    """The ``--chips 4`` phase on four forced CPU devices (its own
    process: the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    code = ("import json, chip_smoke as cs; "
            "m = cs.phase_mesh(num_devices=20, k=8, epochs=1, rounds=2, "
            "engine='batched', round_driver='scan'); "
            "print(json.dumps(m, default=float))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    m = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(m) == {"loss0", "mesh1", "mesh4", "mesh4_edge2"}
    for name in ("mesh4", "mesh4_edge2"):
        par = m[name]["parity"]
        assert par["ok"] and par["params"] < 1e-5, (name, par)
