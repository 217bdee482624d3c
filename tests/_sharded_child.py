"""Child process for tests/test_sharding.py's 8-way mesh parity suite.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
parent test sets it): JAX device counts are fixed at first backend
init, so an 8-device CPU mesh can only be exercised in a process of its
own — exactly the documented CPU story for the sharded path.

Checks, all at atol 1e-5 over 3 rounds with injected selections:

- every registered algorithm: batched engine, ``mesh_devices=8`` vs
  ``mesh_devices=1`` (final params AND loss history);
- the scanned driver for a two-phase, a control-variate, and a
  full-participation spec;
- the scanned driver's streaming plan (feddane, ``mesh_devices=4`` vs
  ``1``, two chunks): each chunk's ``xs`` placed with its client axis
  sharded over the mesh;
- one non-ideal scenario (``bernoulli`` availability) under both
  drivers — masked aggregation via psum collectives — including the
  realized ``effective_k`` telemetry;
- every wire codec (int8 / topk / dp_gauss) under both drivers,
  ``mesh_devices=8`` vs ``1`` — the per-shard partial dequantize +
  psum path, including top-k error-feedback carry;
- ``bytes_up``/``bytes_down`` telemetry under a thinned bernoulli
  round with a codec: counted once globally, not once per shard;
- the buffered async driver on the 8-way mesh: degenerate parity vs
  the python driver, a non-divisible commit cohort (masked padded
  lanes) with a codec, and duplicate arrivals under a control-variate
  spec (sequential occurrence layers);
- the scanned driver's replicated fallback when the client-state axis
  does not divide the mesh: still correct, ``sharded: 0.0`` telemetry;
- the hierarchical aggregation tree: ``edge_shards`` in {2, 4}
  regroups the same 8 leaf devices into a 2-D ``(edge, device)`` mesh
  whose nested psum levels must match both the flat 8-mesh and the
  single-device program (mean-of-edge-means is exact at equal shard
  counts); a codec case pins ``linear_shard_index``'s row-major slot
  offsets through the tree, a bernoulli case the masked tree psums, a
  buffered case the tree-reduced commit, and ``edge_shards=1`` must be
  byte-identical to the flat mesh; the no-mesh/indivisible edge error
  paths raise;
- ``mesh_devices="auto"`` resolves to the full 8-way mesh;
- the error paths that need >1 device: indivisible selection size and
  the config-time loop-engine conflict.

Prints ``SHARDED-PARITY-OK`` on success; any failure raises (nonzero
exit) with the offending algorithm in the message.
"""
import sys

import jax
import numpy as np

from repro.configs.base import FederatedConfig
from repro.core import FederatedTrainer, available_algorithms
from repro.core.engine import make_scanned_run
from repro.core.sharding import chunk_stacked_sharding, resolve_mesh_devices
from repro.data import make_synthetic, make_synthetic_stream
from repro.models.param import init_params
from repro.models.small import logreg_loss, logreg_specs

ATOL = 1e-5
N, K, ROUNDS = 16, 8, 3


def leaves_maxdiff(a, b) -> float:
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def main() -> None:
    assert jax.device_count() == 8, (
        f"child needs the 8-device host flag, got {jax.device_count()}")
    assert resolve_mesh_devices("auto") == 8

    dataset = make_synthetic(1, 1, num_devices=N, seed=0)
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    sel = np.stack([np.stack([(np.arange(K) + t) % N,
                              (np.arange(K) + t + 4) % N])
                    for t in range(ROUNDS)])

    def run(algo, mesh_devices, driver="python", **kw):
        cfg = FederatedConfig(
            algorithm=algo, num_devices=N, devices_per_round=K,
            local_epochs=2, learning_rate=0.01, mu=0.001, seed=3,
            engine="batched", round_driver=driver, chunk_rounds=ROUNDS,
            mesh_devices=mesh_devices, **kw)
        tr = FederatedTrainer(logreg_loss, dataset, cfg)
        return tr.run(params, ROUNDS, selections=sel)

    for algo in available_algorithms():
        h1, f1 = run(algo, 1)
        h8, f8 = run(algo, 8)
        dmax = leaves_maxdiff(f1, f8)
        ldiff = float(np.abs(np.asarray(h1["loss"])
                             - np.asarray(h8["loss"])).max())
        assert dmax < ATOL and ldiff < ATOL, (
            f"{algo}: sharded batched round diverged "
            f"(params {dmax:.2e}, loss {ldiff:.2e})")
        print(f"ok batched {algo}: params {dmax:.2e} loss {ldiff:.2e}")

    for algo in ("feddane", "scaffold", "inexact_dane"):
        _, f1 = run(algo, 1, driver="scan")
        _, f8 = run(algo, 8, driver="scan")
        dmax = leaves_maxdiff(f1, f8)
        assert dmax < ATOL, f"{algo}: sharded scan diverged ({dmax:.2e})"
        print(f"ok scan {algo}: params {dmax:.2e}")

    # the streaming plan on a mesh: each chunk's xs is assembled on the
    # host and placed in one transfer per leaf with its client axis
    # sharded as the shard-mapped round body takes it (two chunks)
    stream = make_synthetic_stream(1, 1, num_devices=N, seed=0)

    def run_stream(mesh_devices):
        cfg = FederatedConfig(
            algorithm="feddane", num_devices=N, devices_per_round=K,
            local_epochs=2, learning_rate=0.01, mu=0.001, seed=3,
            engine="batched", round_driver="scan", chunk_rounds=2,
            client_source="streaming", mesh_devices=mesh_devices)
        drv = make_scanned_run(logreg_loss, stream, cfg)
        chunk, placed = drv._chunk_stream, []

        def record(carry, xs, data):
            placed.append(xs["b"]["x"].sharding)
            return chunk(carry, xs, data)

        drv._chunk_stream = record
        hist, final = drv.run(params, ROUNDS, selections=sel)
        assert drv.streaming and len(placed) == 2, placed
        return hist, final, placed, drv.mesh

    h1, f1, _, _ = run_stream(1)
    h4, f4, placed, mesh = run_stream(4)
    want = chunk_stacked_sharding(mesh)
    assert all(p.is_equivalent_to(want, 5) for p in placed), placed
    dmax = leaves_maxdiff(f1, f4)
    ldiff = float(np.abs(np.asarray(h1["loss"])
                         - np.asarray(h4["loss"])).max())
    assert dmax < ATOL and ldiff < ATOL, (
        f"streaming mesh diverged (params {dmax:.2e}, loss {ldiff:.2e})")
    assert h4["sharded"] == [1.0] * ROUNDS, h4["sharded"]
    print(f"ok streaming mesh 4: params {dmax:.2e} loss {ldiff:.2e}")

    # mesh_devices="auto" == the explicit full mesh, to the bit
    _, f8 = run("feddane", 8)
    _, fa = run("feddane", "auto")
    assert leaves_maxdiff(f8, fa) == 0.0, "auto mesh != explicit 8"
    print("ok auto == 8")

    # the fused whole-epoch local solver under the mesh: the Pallas
    # epoch kernel runs inside shard_map (K/mesh devices per shard)
    _, f1 = run("feddane", 1, local_solver="fused_epoch")
    _, f8 = run("feddane", 8, local_solver="fused_epoch")
    dmax = leaves_maxdiff(f1, f8)
    assert dmax < ATOL, f"fused_epoch sharded diverged ({dmax:.2e})"
    print(f"ok fused_epoch mesh: params {dmax:.2e}")

    # non-ideal scenario: masked psum aggregation + telemetry.  With
    # injected selections, the host driver's env uniforms are the only
    # rng consumption, so both mesh settings realize identical
    # environments; the scan driver draws from the carried key (same
    # seed both runs).
    for driver in ("python", "scan"):
        h1, f1 = run("feddane", 1, driver=driver,
                     scenario="bernoulli", avail_prob=0.6)
        h8, f8 = run("feddane", 8, driver=driver,
                     scenario="bernoulli", avail_prob=0.6)
        dmax = leaves_maxdiff(f1, f8)
        assert dmax < ATOL, (
            f"bernoulli/{driver}: sharded env round diverged "
            f"({dmax:.2e})")
        assert h1["effective_k"] == h8["effective_k"], (
            f"bernoulli/{driver}: telemetry diverged "
            f"{h1['effective_k']} vs {h8['effective_k']}")
        assert any(e < K for e in h8["effective_k"]), (
            "bernoulli at 0.6 never thinned a round — scenario inert?")
        print(f"ok bernoulli {driver}: params {dmax:.2e} "
              f"eff_k {h8['effective_k']}")

    # wire codecs on the mesh: per-shard partial dequantize-aggregate
    # + psum, both drivers, vs the identical single-device program.
    # topk carries persistent error-feedback state (dev-sharded), so
    # 3 rounds also pin the EF writeback under sharding.
    for codec in ("int8", "topk", "dp_gauss"):
        for driver in ("python", "scan"):
            h1, f1 = run("feddane", 1, driver=driver, codec=codec)
            h8, f8 = run("feddane", 8, driver=driver, codec=codec)
            dmax = leaves_maxdiff(f1, f8)
            ldiff = float(np.abs(np.asarray(h1["loss"])
                                 - np.asarray(h8["loss"])).max())
            assert dmax < ATOL and ldiff < ATOL, (
                f"{codec}/{driver}: sharded codec round diverged "
                f"(params {dmax:.2e}, loss {ldiff:.2e})")
            print(f"ok codec {codec} {driver}: params {dmax:.2e} "
                  f"loss {ldiff:.2e}")

    # bytes telemetry is a GLOBAL count: under a thinned bernoulli
    # round the effective-k-dependent uplink bytes must match the
    # single-device run exactly, not be multiplied (or split) per
    # shard — the mesh analogue of the PR-8 thinned-gather fix.
    for codec in ("topk", "int8"):
        h1, _ = run("feddane", 1, codec=codec,
                    scenario="bernoulli", avail_prob=0.6)
        h8, _ = run("feddane", 8, codec=codec,
                    scenario="bernoulli", avail_prob=0.6)
        assert h1["bytes_up"] == h8["bytes_up"], (
            f"{codec}: bytes_up diverged under mesh "
            f"{h1['bytes_up']} vs {h8['bytes_up']}")
        assert h1["bytes_down"] == h8["bytes_down"], (
            f"{codec}: bytes_down diverged under mesh "
            f"{h1['bytes_down']} vs {h8['bytes_down']}")
        print(f"ok bytes {codec}: up {h8['bytes_up']}")

    # hierarchical aggregation tree: the same 8 leaf devices regrouped
    # under 2 or 4 edge aggregators — nested (edge, device) collectives
    # must reproduce the flat mesh and the single-device program
    for algo in ("feddane", "scaffold"):
        for driver in ("python", "scan"):
            _, f1 = run(algo, 1, driver=driver)
            _, f8 = run(algo, 8, driver=driver)
            for edge in (2, 4):
                _, ft = run(algo, 8, driver=driver, edge_shards=edge)
                d_flat = leaves_maxdiff(f8, ft)
                d_one = leaves_maxdiff(f1, ft)
                assert d_flat < ATOL and d_one < ATOL, (
                    f"tree {algo}/{driver}/edge={edge}: diverged "
                    f"(vs flat {d_flat:.2e}, vs mesh=1 {d_one:.2e})")
                print(f"ok tree {algo} {driver} edge={edge}: "
                      f"flat {d_flat:.2e} mesh1 {d_one:.2e}")

    # edge_shards=1 is structurally the flat 1-D mesh: bit-identical
    _, f8 = run("feddane", 8, driver="scan")
    _, fe1 = run("feddane", 8, driver="scan", edge_shards=1)
    assert leaves_maxdiff(f8, fe1) == 0.0, "edge_shards=1 != flat mesh"
    print("ok edge_shards=1 == flat mesh (bitwise)")

    # codec through the tree: per-shard partial dequantize + nested
    # psum, cohort slot offsets from linear_shard_index's row-major
    # flattening of the (edge, device) coordinates.  Tolerance note:
    # quantize/sparsify are DISCONTINUOUS in their input, and the tree
    # legitimately reassociates the pre-codec float sums (~1e-8), so a
    # coordinate near a rounding boundary can flip one quantization
    # bucket (~1 int8 step ~ 1e-5/round).  The gate is therefore a few
    # quantization steps — a broken slot mapping changes EVERY
    # per-client dither draw and lands orders of magnitude above it.
    for codec in ("int8", "topk"):
        h8, f8 = run("feddane", 8, driver="scan", codec=codec)
        ht, ft = run("feddane", 8, driver="scan", codec=codec,
                     edge_shards=2)
        dmax = leaves_maxdiff(f8, ft)
        assert dmax < 1e-3, (
            f"tree codec {codec}: diverged ({dmax:.2e})")
        assert h8["bytes_up"] == ht["bytes_up"], (
            f"tree codec {codec}: bytes_up diverged")
        print(f"ok tree codec {codec}: params {dmax:.2e}")

    # masked aggregation through the tree (bernoulli availability)
    _, f8 = run("feddane", 8, driver="scan",
                scenario="bernoulli", avail_prob=0.6)
    _, ft = run("feddane", 8, driver="scan", edge_shards=2,
                scenario="bernoulli", avail_prob=0.6)
    dmax = leaves_maxdiff(f8, ft)
    assert dmax < ATOL, f"tree bernoulli diverged ({dmax:.2e})"
    print(f"ok tree bernoulli: params {dmax:.2e}")

    # the scanned driver keeps sharded layout telemetry honest: N=16
    # divides the 8-mesh -> every round reports sharded 1.0
    h8, _ = run("feddane", 8, driver="scan")
    assert h8["sharded"] == [1.0] * ROUNDS, h8["sharded"]
    print("ok scan sharded telemetry 1.0")

    # N % D != 0: replicated client-state fallback — correct results
    # (vs mesh=1) and sharded: 0.0 telemetry, not a crash
    ds12 = make_synthetic(1, 1, num_devices=12, seed=0)
    sel12 = np.stack([np.stack([(np.arange(K) + t) % 12,
                                (np.arange(K) + t + 4) % 12])
                      for t in range(ROUNDS)])

    def run12(mesh_devices):
        cfg = FederatedConfig(
            algorithm="scaffold", num_devices=12, devices_per_round=K,
            local_epochs=2, learning_rate=0.01, mu=0.001, seed=3,
            engine="batched", round_driver="scan",
            chunk_rounds=ROUNDS, mesh_devices=mesh_devices)
        tr = FederatedTrainer(logreg_loss, ds12, cfg)
        return tr.run(params, ROUNDS, selections=sel12)

    h1, f1 = run12(1)
    h8, f8 = run12(8)
    dmax = leaves_maxdiff(f1, f8)
    assert dmax < ATOL, f"replicated fallback diverged ({dmax:.2e})"
    assert h8["sharded"] == [0.0] * ROUNDS, h8["sharded"]
    print(f"ok replicated fallback: params {dmax:.2e} sharded 0.0")

    # buffered async driver on the mesh -------------------------------
    def run_buf(algo, mesh_devices, selections, rounds=ROUNDS, **kw):
        cfg = FederatedConfig(
            algorithm=algo, num_devices=N, devices_per_round=K,
            local_epochs=2, learning_rate=0.01, mu=0.001, seed=3,
            round_driver="buffered", staleness_fn="constant",
            mesh_devices=mesh_devices, **kw)
        tr = FederatedTrainer(logreg_loss, dataset, cfg)
        return tr.run(params, rounds, selections=selections)

    def run_py(algo, selections, rounds=ROUNDS, **kw):
        cfg = FederatedConfig(
            algorithm=algo, num_devices=N, devices_per_round=K,
            local_epochs=2, learning_rate=0.01, mu=0.001, seed=3,
            round_driver="python", engine="loop", **kw)
        tr = FederatedTrainer(logreg_loss, dataset, cfg)
        return tr.run(params, rounds, selections=selections)

    for algo in ("fedavg", "feddane", "scaffold"):
        _, fp = run_py(algo, sel)
        _, fb = run_buf(algo, 8, sel)
        dmax = leaves_maxdiff(fp, fb)
        assert dmax < ATOL, (
            f"buffered mesh {algo}: degenerate parity broke "
            f"({dmax:.2e})")
        print(f"ok buffered mesh {algo}: params {dmax:.2e}")

    # non-divisible commit cohort (buffer_size=6 over an 8-mesh) plus a
    # codec: masked padded lanes must stay inert, loss finite
    hb, _ = run_buf("feddane", 8, sel, buffer_size=6, codec="int8")
    assert np.isfinite(np.asarray(hb["loss"])).all(), hb["loss"]
    print("ok buffered mesh padded cohort + int8")

    # duplicate arrivals under a control-variate spec: sequential
    # occurrence layers on the mesh == the python driver's loop
    sel_dup = sel[:, 0, :].copy()
    sel_dup[:, 1] = sel_dup[:, 0]
    _, fp = run_py("scaffold", sel_dup, sample_with_replacement=True)
    _, fb = run_buf("scaffold", 8, sel_dup,
                    sample_with_replacement=True)
    dmax = leaves_maxdiff(fp, fb)
    assert dmax < ATOL, (
        f"buffered mesh duplicates diverged ({dmax:.2e})")
    print(f"ok buffered mesh duplicates: params {dmax:.2e}")

    # buffered commits reduced through the tree == the python loop
    _, fp = run_py("feddane", sel)
    _, fb = run_buf("feddane", 8, sel, edge_shards=2)
    dmax = leaves_maxdiff(fp, fb)
    assert dmax < ATOL, f"buffered tree diverged ({dmax:.2e})"
    print(f"ok buffered tree edge=2: params {dmax:.2e}")

    # tree error paths (config- or trainer-time, whichever fires
    # first): an edge count that does not divide the mesh, and edge
    # aggregators without a real mesh to group
    for bad in (dict(mesh_devices=8, edge_shards=3),
                dict(mesh_devices=1, edge_shards=2)):
        try:
            cfg = FederatedConfig(algorithm="fedavg", num_devices=N,
                                  devices_per_round=K,
                                  engine="batched", **bad)
            FederatedTrainer(logreg_loss, dataset, cfg)
        except ValueError as e:
            assert "edge_shards" in str(e), e
            print(f"ok bad tree config raises: {bad}")
        else:
            raise AssertionError(f"{bad} did not raise")

    # error paths that need a real multi-device mesh
    cfg = FederatedConfig(algorithm="fedavg", num_devices=N,
                          devices_per_round=6, engine="batched",
                          mesh_devices=8)
    try:
        FederatedTrainer(logreg_loss, dataset, cfg)
    except ValueError as e:
        assert "divisible" in str(e), e
        print("ok indivisible K raises")
    else:
        raise AssertionError("K=6 over an 8-mesh did not raise")
    # the loop-engine conflict now fails at CONFIG construction
    # (configs/base.py), before any trainer/device state exists
    try:
        FederatedConfig(algorithm="fedavg", num_devices=N,
                        devices_per_round=K, engine="loop",
                        mesh_devices=8)
    except ValueError as e:
        assert "loop" in str(e) and "mesh_devices" in str(e), e
        print("ok loop-engine conflict raises at config time")
    else:
        raise AssertionError("engine='loop' + mesh did not raise")

    print("SHARDED-PARITY-OK")


if __name__ == "__main__":
    sys.exit(main())
