"""Chip smoke test: the federated round on a TPU, through the entry point
users call (``FederatedTrainer(...).run(...)``).

    python chip_smoke.py              # one chip: phases a, b, c
    python chip_smoke.py --chips 4    # four chips: the client-mesh phase only

Phases on one chip:

a. FedDANE on the paper's FEMNIST multinomial logistic regression
   (784 -> 10) over ``make_femnist_like(num_devices=200)``: K=10, E=20,
   B=10, lr 0.003, mu 0.001, 3 rounds, every execution knob on ``auto``.
   Checks that ``auto`` resolved to the batched engine, the scan driver
   and a fused local-solve kernel, and that the round program holds
   Mosaic kernels (``tpu_custom_call``): no kernel ran in interpret mode.
b. The same rounds and selections through ``engine="batched"`` +
   ``round_driver="python"`` (the per-round program with its donated
   state) and through ``engine="loop"`` (the per-device reference),
   each held to phase (a) within :data:`TOL`.
c. Streaming ``make_synthetic_stream(1, 1)`` at K=10 of N=10^6 through
   the scan driver, 3 rounds: finite losses, bounded shard cache.

With ``--chips 4`` only this runs: phase (a)'s setting at K=8 with
``mesh_devices=4`` (flat client mesh), ``mesh_devices=4, edge_shards=2``
(aggregation tree) and ``mesh_devices=1``, held to each other within
:data:`TOL`.

Exits non-zero, with no result line, when JAX finds no TPU.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import FederatedConfig  # noqa: E402
from repro.core import FederatedTrainer  # noqa: E402
from repro.data import make_femnist_like, make_synthetic_stream  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.small import logreg_loss, logreg_specs  # noqa: E402

#: Parity tolerance between execution paths, relative to what the run
#: changed: ``|p - p_ref| <= TOL * |p_ref - p_init|`` for the final
#: params (L2 over all leaves) and ``|l - l_ref| <= TOL * |l_ref - l_init|``
#: for each round's loss.  The CPU tests pin 1e-5 because XLA:CPU
#: multiplies f32 at full precision.  On the TPU a default-precision f32
#: matmul rounds both operands to bf16 (8-bit significand, unit roundoff
#: 2^-8 ~ 3.9e-3), so a product carries a relative error up to ~7.8e-3.
#: Every XLA gradient (the loop reference, phase A, the eval) rounds the
#: same data the same way at every step, while the fused kernels round
#: differently; a systematic per-step error of that size shifts an SGD
#: update of a convex objective by at most about the same relative
#: amount.  2e-2 is 2.5x that worst case.  Sums of many products
#: average the rounding out, so measured deltas sit far below it; a
#: wrong mask, anchor or aggregate moves the result by O(1).
TOL = 2e-2

#: Phase (a)'s FEMNIST setting (paper Fig. 1, FedDANE row).
FEMNIST = dict(num_devices=200, k=10, epochs=20, batch=10, lr=0.003,
               mu=0.001, rounds=3)

#: Compile-time events JAX records per program (tracing, lowering,
#: backend compile).
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def _compile_seconds():
    """Sum of JAX's compile-time events inside the block: ``{"s": ...}``."""
    total = {"s": 0.0}

    def listener(event, duration, **_):
        if event in _COMPILE_EVENTS:
            total["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def _programs(ir_dir: str) -> dict:
    """``{program name: {"mosaic": bool, "kernels": [...]}}`` for every
    program compiled while JAX dumped its IR to ``ir_dir``."""
    out = {}
    for path in sorted(glob.glob(os.path.join(ir_dir, "*_compile.mlir"))):
        name = re.sub(r"^jax_ir\d+_jit_|_compile\.mlir$", "",
                      os.path.basename(path))
        with open(path) as f:
            text = f.read()
        prog = out.setdefault(name, {"mosaic": False, "kernels": []})
        prog["mosaic"] |= "tpu_custom_call" in text
        prog["kernels"] = sorted(set(prog["kernels"]) | set(
            re.findall(r'kernel_name = "(\w+)"', text)))
    return out


def run_trainer(dataset, params0, rounds: int, selections=None,
                **cfg_kw) -> dict:
    """One FedDANE ``FederatedTrainer(...).run(...)``; returns its losses,
    final params, compile seconds, resolved knobs and compiled programs."""
    cfg = FederatedConfig(algorithm="feddane", **cfg_kw)
    trainer = FederatedTrainer(logreg_loss, dataset, cfg)
    with tempfile.TemporaryDirectory() as ir_dir, \
            _compile_seconds() as clock:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            hist, params = trainer.run(params0, rounds,
                                       selections=selections)
            params = jax.block_until_ready(params)
        finally:
            jax.config.update("jax_dump_ir_to", "")
        programs = _programs(ir_dir)
    driver = trainer._resolve_driver()
    kernels = sorted({k for p in programs.values() for k in p["kernels"]})
    return {"trainer": trainer, "params": params,
            "losses": [float(x) for x in hist["loss"]],
            "compile_s": clock["s"], "programs": programs,
            "resolved": {"engine": ("batched" if trainer.engine is not None
                                    else "loop"),
                         "round_driver": driver, "kernels": kernels}}


def random_params(num_features: int, num_classes: int, seed: int = 0):
    """Seeded random logistic-regression params, N(0, 0.01^2).  The paper
    starts from zeros; a random start keeps a symmetric one from hiding
    a fault."""
    specs = logreg_specs(num_features, num_classes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    return {name: 0.01 * jax.random.normal(key, spec.shape)
            for key, (name, spec) in zip(keys, sorted(specs.items()))}


def femnist_setup(num_devices: int, k: int, rounds: int, seed: int = 0):
    """FEMNIST-like data, seeded random params and ``(rounds, 2, k)``
    injected FedDANE selections (gradient phase, solve phase)."""
    data = make_femnist_like(num_devices=num_devices, seed=seed,
                             batch_size=FEMNIST["batch"])
    params0 = random_params(784, 10, seed)
    rng = np.random.default_rng(seed)
    sel = np.stack([[rng.choice(num_devices, k, replace=False)
                     for _ in range(2)] for _ in range(rounds)])
    return data, params0, sel


def femnist_knobs(k: int, epochs: int, seed: int = 0) -> dict:
    return dict(devices_per_round=k, local_epochs=epochs,
                local_batch_size=FEMNIST["batch"],
                learning_rate=FEMNIST["lr"], mu=FEMNIST["mu"], seed=seed)


def _flat(params) -> np.ndarray:
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree_util.tree_leaves(params)])


def parity(run: dict, ref: dict, params0, loss0: float) -> dict:
    """Deltas of ``run`` against ``ref``, each relative to what ``ref``
    changed (see :data:`TOL`)."""
    p, q, p0 = _flat(run["params"]), _flat(ref["params"]), _flat(params0)
    d_params = float(np.linalg.norm(p - q) / np.linalg.norm(q - p0))
    l, lr = np.asarray(run["losses"]), np.asarray(ref["losses"])
    d_loss = float(np.max(np.abs(l - lr) / np.abs(lr - loss0)))
    return {"params": d_params, "loss": d_loss,
            "ok": bool(d_params <= TOL and d_loss <= TOL)}


def phase_main(num_devices: int = FEMNIST["num_devices"],
               k: int = FEMNIST["k"], epochs: int = FEMNIST["epochs"],
               rounds: int = FEMNIST["rounds"], **overrides) -> dict:
    """Phase (a): FedDANE on FEMNIST at full width, knobs on ``auto``
    unless ``overrides`` pins them (the CPU tests do)."""
    data, params0, sel = femnist_setup(num_devices, k, rounds)
    out = run_trainer(data, params0, rounds, sel,
                      **femnist_knobs(k, epochs), **overrides)
    out.update(data=data, params0=params0, selections=sel)
    return out


def check_main(a: dict) -> list:
    """What phase (a) must show on the chip; returns the failures."""
    bad = []
    if a["resolved"]["engine"] != "batched":
        bad.append(f"engine resolved to {a['resolved']['engine']}")
    if a["resolved"]["round_driver"] != "scan":
        bad.append(f"round_driver resolved to "
                   f"{a['resolved']['round_driver']}")
    chunk = a["programs"].get("chunk")
    if chunk is None or not chunk["mosaic"]:
        bad.append("the scanned round program holds no tpu_custom_call")
    elif not {"_epoch_kernel", "_step_kernel"} & set(chunk["kernels"]):
        bad.append(f"no fused local-solve kernel: {chunk['kernels']}")
    if not np.all(np.isfinite(a["losses"])):
        bad.append(f"losses not finite: {a['losses']}")
    return bad


def phase_reference(a: dict, epochs: int = FEMNIST["epochs"]) -> dict:
    """Phase (b): phase (a)'s rounds through the per-round batched
    program and the looped reference; parity against (a)."""
    k = a["selections"].shape[-1]
    rounds = len(a["selections"])
    runs = {
        "batched_python": dict(engine="batched", round_driver="python"),
        "loop": dict(engine="loop"),
    }
    loss0 = a["trainer"].global_loss(a["params0"])
    out = {"loss0": loss0}
    for name, knobs in runs.items():
        r = run_trainer(a["data"], a["params0"], rounds, a["selections"],
                        **femnist_knobs(k, epochs), **knobs)
        out[name] = {"losses": r["losses"], "compile_s": r["compile_s"],
                     "resolved": r["resolved"],
                     "parity": parity(r, a, a["params0"], loss0)}
    return out


def phase_population(num_devices: int = 10**6, k: int = 10,
                     epochs: int = 20, rounds: int = 3,
                     eval_clients: int = 32, **overrides) -> dict:
    """Phase (c): streaming synthetic(1, 1) at K of N through the scan
    driver; finite losses and a shard cache bounded by the cohorts."""
    src = make_synthetic_stream(1.0, 1.0, num_devices=num_devices, seed=0,
                                eval_clients=eval_clients)
    params0 = random_params(60, 10)
    r = run_trainer(src, params0, rounds, devices_per_round=k,
                    local_epochs=epochs, local_batch_size=10,
                    learning_rate=0.01, mu=0.001, seed=0, **overrides)
    stats = src.stats()
    # the eval sample plus two phases x K x rounds of cohort fetches
    max_clients = eval_clients + 2 * k * rounds
    ok = (np.all(np.isfinite(r["losses"]))
          and stats["materialized_clients"] <= max_clients
          and stats["peak_cache_bytes"] < 64e6)
    return {"losses": r["losses"], "compile_s": r["compile_s"],
            "resolved": r["resolved"], "stats": stats,
            "max_clients": max_clients, "ok": bool(ok)}


def phase_mesh(num_devices: int = FEMNIST["num_devices"], k: int = 8,
               epochs: int = FEMNIST["epochs"],
               rounds: int = FEMNIST["rounds"], mesh: int = 4,
               edge: int = 2, **overrides) -> dict:
    """Four-chip phase: the same rounds on a flat client mesh, on the
    aggregation tree and on no mesh; parity against no mesh."""
    data, params0, sel = femnist_setup(num_devices, k, rounds)
    knobs = dict(femnist_knobs(k, epochs), **overrides)
    runs = {"mesh1": run_trainer(data, params0, rounds, sel,
                                 mesh_devices=1, **knobs),
            f"mesh{mesh}": run_trainer(data, params0, rounds, sel,
                                       mesh_devices=mesh, **knobs),
            f"mesh{mesh}_edge{edge}": run_trainer(
                data, params0, rounds, sel, mesh_devices=mesh,
                edge_shards=edge, **knobs)}
    ref = runs["mesh1"]
    loss0 = ref["trainer"].global_loss(params0)
    out = {"loss0": loss0}
    for name, r in runs.items():
        out[name] = {"losses": r["losses"], "compile_s": r["compile_s"],
                     "resolved": r["resolved"]}
        if name != "mesh1":
            out[name]["parity"] = parity(r, ref, params0, loss0)
    return out


def _say(tag: str, obj) -> None:
    print(f"{tag}: {json.dumps(obj, default=float)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    dev ={"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    _say("device", dev)
    failures = []
    t0 = time.time()
    if args.chips == 4:
        m = phase_mesh()
        for name, r in m.items():
            if name != "loss0":
                _say(f"mesh {name}", r)
        failures += [f"mesh {n}: parity {r['parity']}"
                     for n, r in m.items()
                     if isinstance(r, dict) and "parity" in r
                     and not r["parity"]["ok"]]
    else:
        a = phase_main()
        _say("a resolved", a["resolved"])
        _say("a losses", a["losses"])
        _say("a compile_s", a["compile_s"])
        failures += [f"a: {f}" for f in check_main(a)]
        b = phase_reference(a)
        for name in ("batched_python", "loop"):
            _say(f"b {name}", b[name])
            if not b[name]["parity"]["ok"]:
                failures.append(f"b {name}: parity {b[name]['parity']}")
        c = phase_population()
        _say("c population", c)
        if not c["ok"]:
            failures.append(f"c: {c}")
    _say("wall_s", time.time() - t0)
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
