"""Benchmark harness entry: one module per paper table/figure + kernels +
roofline.  Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig1,fig2,...]
  BENCH_SCALE=0.3 PYTHONPATH=src python -m benchmarks.run   # faster
  PYTHONPATH=src python -m benchmarks.run --smoke [--out bench_smoke.json]

``--smoke`` is the CI perf-path canary: a tiny multi-round run of EVERY
algorithm in the strategy registry under both round drivers (python +
scan) that must complete with finite losses — plus one buffered-driver
(async event-queue) run per algorithm family with the staleness
telemetry asserted finite, one population-scale streaming-source run
(N=1e5, cohort-on-demand, cache telemetry asserted bounded), and, on
multi-device hosts (CI's 8-way forced-host step), one mesh-sharded
run.  It prints
one timing line and writes a JSON artifact, so a regression on the
benchmark path — or a registered spec that breaks a driver — fails CI
instead of lurking until the next full benchmark run.

Full (non-smoke) runs additionally leave ``BENCH_round.json`` behind:
the round_engine module's named-entry measurements (driver, mesh size,
K, ms/round) under the versioned schema in ``benchmarks/common.py``,
so bench trajectories stay machine-comparable across PRs.
"""
import json
import os
import sys
import time


def smoke(out_path: str) -> None:
    from benchmarks import round_engine
    t0 = time.time()
    rows = round_engine.smoke()
    wall = time.time() - t0
    assert rows, "smoke benchmark produced no rows"
    with open(out_path, "w") as f:
        json.dump({"total_wall_s": wall, "rows": rows}, f, indent=2)
    scenario_rows = [r for r in rows
                     if r["name"].startswith("bench_smoke_scenario_")]
    sharded_rows = [r for r in rows
                    if r["name"].startswith("bench_smoke_sharded_")]
    buffered_rows = [r for r in rows
                     if r["name"].startswith("bench_smoke_buffered_")]
    codec_rows = [r for r in rows
                  if r["name"].startswith("bench_smoke_codec_")]
    streaming_rows = [r for r in rows
                      if r["name"].startswith("bench_smoke_streaming_")]
    special = (scenario_rows + sharded_rows + buffered_rows
               + codec_rows + streaming_rows)
    algos = sorted({r["name"].replace("bench_smoke_", "")
                    .rsplit("_", 1)[0] for r in rows
                    if r not in special})
    print(f"bench_smoke,{wall * 1e6:.0f},"
          f"algos={len(algos)}({'+'.join(algos)}) "
          f"scenario_runs={len(scenario_rows)} "
          f"sharded_runs={len(sharded_rows)} "
          f"buffered_runs={len(buffered_rows)} "
          f"codec_runs={len(codec_rows)} "
          f"streaming_runs={len(streaming_rows)} runs={len(rows)} "
          f"rounds={rows[0]['rounds']} "
          f"backend={rows[0]['backend']} out={out_path} ok")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        out = "bench_smoke.json"
        if "--out" in sys.argv:
            out = sys.argv[sys.argv.index("--out") + 1]
        smoke(out)
        return

    only = None
    if "--only" in sys.argv:
        only = set(sys.argv[sys.argv.index("--only") + 1].split(","))

    from benchmarks import (fig1_convergence, fig2_participation,
                            fig3_unrealistic, fig4_variants, kernelbench,
                            round_engine, table1_datasets)
    modules = [
        ("table1", table1_datasets),
        ("fig1", fig1_convergence),
        ("fig2", fig2_participation),
        ("fig3", fig3_unrealistic),
        ("fig4", fig4_variants),
        ("kernels", kernelbench),
        ("round_engine", round_engine),
    ]
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name, mod in modules:
        if only and name not in only:
            continue
        try:
            mod.main()
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            print(f"{name}_ERROR,0,{e!r}")
            failed.append(name)
    # roofline table (if dry-run artifacts exist)
    if os.path.isdir("experiments/dryrun") and (not only
                                                or "roofline" in only):
        from benchmarks import roofline
        roofline.main()
    print(f"total,{(time.time() - t0) * 1e6:.0f},all_benchmarks")
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
