"""Accuracy-vs-bytes communication frontier: codec x algorithm x scenario.

The codec layer (core/codecs) reports honest per-round ``bytes_up`` /
``bytes_down`` from the declared wire widths and the round's *realized*
participation.  This module sweeps every registered codec over
{feddane, fedavg, fedprox} x {ideal, bernoulli_low} at fixed K on the
synthetic logistic task and writes the frontier as one versioned bench
JSON (``benchmarks/BENCH_comm.json`` is the committed trajectory):

- ``speedup`` per entry = total uplink bytes of the SAME (algo,
  scenario) cell under ``codec="none"`` divided by this entry's — a
  deterministic compression ratio (simulated wire, no clocks), so
  ``regress.py --modes comm`` gates it tightly across machines.  The
  acceptance floors ride the single-phase fedavg rows (int8 >= 3x,
  topk >= 8x at topk_frac=0.1); FedDANE's ratios are intentionally
  worse — its dense phase-A gradient gather dominates uplink, which is
  exactly the pathology the frontier exposes (paper §V discussion).
- ``final_loss`` records what the compression cost in accuracy.
- A ``one_shot`` row records the EconML-style extreme point of the
  frontier: ONE full-participation round, maximal local work, total
  bytes = N dense uploads.

Grid sizes are fixed (deliberately NOT scaled by BENCH_SCALE): the
byte totals and ratios must be bit-reproducible against the committed
baseline for the CI gate to be meaningful.

The ``comm_mesh8_*`` rows measure the SHARDED codec path: the same
frontier cells executed on an 8-way client mesh (per-shard partial
dequantize-aggregate + psum, core/engine.py).  Device counts freeze at
first backend init, so those cells run in a child process under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — which also
makes them producible on a 1-device CI host.  Their uplink ratios must
match the unsharded ratios exactly (bytes are counted once globally,
never per shard), so the committed rows double as a regression gate on
the sharded byte accounting.

Usage::

    PYTHONPATH=src python -m benchmarks.comm_grid [--out BENCH_comm.json]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import bench_entry, write_bench_json
from repro.configs.base import FederatedConfig, one_shot_config
from repro.core import FederatedTrainer
from repro.core.codecs import available_codecs
from repro.data import make_synthetic
from repro.models.param import init_params
from repro.models.small import logreg_loss, logreg_specs

ALGOS = ("feddane", "fedavg", "fedprox")
SCENARIOS = {"ideal": {}, "bernoulli_low": {"scenario": "bernoulli",
                                            "avail_prob": 0.4}}
ROUNDS = 8
K = 4
BASE_KW = dict(num_devices=10, devices_per_round=K, local_epochs=2,
               local_batch_size=10, learning_rate=0.01, mu=0.01, seed=3,
               correction_decay=0.9)

# sharded cells: K must divide the 8-mesh, so they get their own grid
MESH8_CODECS = ("none", "int8", "topk")
MESH8_KW = dict(num_devices=16, devices_per_round=8, local_epochs=2,
                local_batch_size=10, learning_rate=0.01, mu=0.01,
                seed=3, engine="batched", mesh_devices=8)
_MESH8_TAG = "MESH8-CELLS:"


def _cell(algo: str, codec: str, scn_kw: dict, ds, params):
    cfg = FederatedConfig(algorithm=algo, codec=codec,
                          **BASE_KW, **scn_kw)
    tr = FederatedTrainer(logreg_loss, ds, cfg)
    t0 = time.time()
    hist, final = tr.run(params, ROUNDS, eval_every=ROUNDS)
    jax.block_until_ready(final)
    wall = time.time() - t0
    assert np.isfinite(hist["loss"]).all(), f"{algo}/{codec}: loss blew up"
    return {"final_loss": float(hist["loss"][-1]),
            "bytes_up": float(sum(hist["bytes_up"])),
            "bytes_down": float(sum(hist["bytes_down"])),
            "wall_s": wall}


def _mesh8_child() -> None:
    """Body of the forced-8-device subprocess: run the sharded codec
    cells and print them as one tagged JSON line for the parent."""
    assert jax.device_count() == 8, (
        f"mesh8 child needs 8 forced host devices, "
        f"got {jax.device_count()}")
    ds = make_synthetic(0.5, 0.5, num_devices=16, seed=2)
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    cells = {}
    for codec in MESH8_CODECS:
        cfg = FederatedConfig(algorithm="feddane", codec=codec,
                              **MESH8_KW)
        tr = FederatedTrainer(logreg_loss, ds, cfg)
        t0 = time.time()
        hist, final = tr.run(params, ROUNDS, eval_every=ROUNDS)
        jax.block_until_ready(final)
        wall = time.time() - t0
        assert np.isfinite(hist["loss"]).all(), (
            f"mesh8/{codec}: loss blew up")
        cells[codec] = {"final_loss": float(hist["loss"][-1]),
                        "bytes_up": float(sum(hist["bytes_up"])),
                        "bytes_down": float(sum(hist["bytes_down"])),
                        "wall_s": wall}
    print(_MESH8_TAG + json.dumps(cells))


def _mesh8_entries() -> list:
    """Sharded-codec frontier rows, measured in a child process with 8
    forced host CPU devices (works on any host, incl. 1-device CI).
    The child is pinned to the CPU: this process may already hold the
    accelerator, and the rows are byte counts, not timings."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + env.get("XLA_FLAGS", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.comm_grid", "--mesh8-child"],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh8 bench child failed\n--- stdout ---\n{proc.stdout}"
            f"\n--- stderr ---\n{proc.stderr}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith(_MESH8_TAG))
    cells = json.loads(line[len(_MESH8_TAG):])
    dense_up = cells["none"]["bytes_up"]
    entries = []
    for codec, cell in sorted(cells.items()):
        ratio = dense_up / max(cell["bytes_up"], 1.0)
        entries.append(bench_entry(
            f"comm_mesh8_{codec}_feddane_ideal", mode="comm",
            driver="batched", k=8, mesh_devices=8,
            ms_per_round=cell["wall_s"] * 1e3 / ROUNDS,
            algo="feddane", codec=codec, scenario="ideal",
            speedup=round(ratio, 4),
            final_loss=round(cell["final_loss"], 6),
            bytes_up=cell["bytes_up"],
            bytes_down=cell["bytes_down"]))
        print(f"comm_mesh8_{codec}_feddane_ideal,"
              f"{cell['bytes_up']:.0f},x{ratio:.2f}_"
              f"loss{cell['final_loss']:.4f}")
    return entries


def main(out_path: str = "BENCH_comm.json"):
    ds = make_synthetic(0.5, 0.5, num_devices=10, seed=2)
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    entries = []
    for scn_name, scn_kw in SCENARIOS.items():
        for algo in ALGOS:
            cells = {codec: _cell(algo, codec, scn_kw, ds, params)
                     for codec in available_codecs()}
            dense_up = cells["none"]["bytes_up"]
            for codec, cell in sorted(cells.items()):
                ratio = dense_up / max(cell["bytes_up"], 1.0)
                entries.append(bench_entry(
                    f"comm_{codec}_{algo}_{scn_name}", mode="comm",
                    driver="loop", k=K,
                    ms_per_round=cell["wall_s"] * 1e3 / ROUNDS,
                    algo=algo, codec=codec, scenario=scn_name,
                    speedup=round(ratio, 4),
                    final_loss=round(cell["final_loss"], 6),
                    bytes_up=cell["bytes_up"],
                    bytes_down=cell["bytes_down"]))
                print(f"comm_{codec}_{algo}_{scn_name},"
                      f"{cell['bytes_up']:.0f},x{ratio:.2f}_"
                      f"loss{cell['final_loss']:.4f}")
    # the one-shot extreme point: all the local work, one commit
    cfg = one_shot_config(10, local_epochs=16, local_batch_size=10,
                          learning_rate=0.05, seed=3)
    tr = FederatedTrainer(logreg_loss, ds, cfg)
    t0 = time.time()
    hist, final = tr.run(params, 1, eval_every=1)
    jax.block_until_ready(final)
    assert np.isfinite(hist["loss"]).all(), "one_shot: loss blew up"
    entries.append(bench_entry(
        "comm_one_shot_extreme", mode="comm", driver="loop", k=10,
        ms_per_round=(time.time() - t0) * 1e3, algo="one_shot",
        codec="none", scenario="ideal",
        final_loss=round(float(hist["loss"][-1]), 6),
        bytes_up=float(sum(hist["bytes_up"])),
        bytes_down=float(sum(hist["bytes_down"]))))
    # the sharded codec path: same frontier, 8-way mesh (subprocess)
    entries.extend(_mesh8_entries())
    # acceptance floors (single-phase uplink): keep the committed
    # baseline honest at generation time, not just in CI comparisons
    by_name = {e["name"]: e for e in entries}
    assert by_name["comm_int8_fedavg_ideal"]["speedup"] >= 3.0
    assert by_name["comm_topk_fedavg_ideal"]["speedup"] >= 8.0
    # the mesh8 rows count bytes once globally, so their ratios equal
    # the unsharded feddane ratios for the same codec knobs
    assert by_name["comm_mesh8_int8_feddane_ideal"]["speedup"] > 1.0
    assert by_name["comm_mesh8_topk_feddane_ideal"]["speedup"] > 1.0
    write_bench_json(out_path, entries)


if __name__ == "__main__":
    if "--mesh8-child" in sys.argv:
        _mesh8_child()
        sys.exit(0)
    out = "BENCH_comm.json"
    if "--out" in sys.argv:
        out = sys.argv[sys.argv.index("--out") + 1]
    main(out)
