"""Run one benchmark cell on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the cell's data and params from the seed, builds the
program's trainer (``FederatedTrainer``, every execution knob on
``auto``), and runs two warm-up chunks of rounds through the same
``run`` call the window makes: the first compiles, the second times a
chunk.  The window is then one ``run`` call of as many whole chunks as
fill ``--seconds``, timed to ``block_until_ready`` on its final params.
With ``--trace 1`` the window runs under the profiler and the result
carries the cell's per-layer metrics instead of its end-to-end ones.

After the window, the plain reference re-runs the first warm-up chunk's
rounds from the same params, and ``correct`` says whether the program
came within the cell's limits (``bench/compare.py``).

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cohorts, compare, harness  # noqa: E402

#: JAX's compile-time events: a program is lowered once per compile,
#: whether the backend then compiles it or reads it from the cache.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration", LOWER_EVENT,
                  "/jax/core/compile/backend_compile_duration")


def say(tag: str, obj) -> None:
    """An earlier line, on standard error."""
    print(f"{tag}: {json.dumps(obj, default=float)}", file=sys.stderr,
          flush=True)


@contextlib.contextmanager
def compile_log():
    """``{"s": compile seconds, "lowered": [program names]}`` of the
    programs JAX lowered and compiled inside the block."""
    import jax
    log = {"s": 0.0, "lowered": []}

    def listener(event, duration, **kw):
        if event in COMPILE_EVENTS:
            log["s"] += duration
        if event == LOWER_EVENT:
            log["lowered"].append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield log
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def kernel_names(ir_dir: str, program: str = "chunk") -> list:
    """Pallas kernel names in the dumped IR of programs named
    ``program`` (the scanned driver's chunk)."""
    names = set()
    for path in glob.glob(os.path.join(ir_dir, f"*jit_{program}_*.mlir")):
        with open(path) as f:
            names |= set(re.findall(r'kernel_name = "(\w+)"', f.read()))
    return sorted(names)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices JAX found; raises
    ``SystemExit(2)`` unless they are at least ``chips`` TPUs."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{info['count']} {info['platform']} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    return info


class ChunkClock(io.TextIOBase):
    """Stands in for stdout during the window: ``run(verbose=True)``
    prints each round's line when its chunk comes back, so the time of
    a chunk's last line ends that chunk."""

    def __init__(self, chunk_rounds: int):
        self.chunk_rounds = chunk_rounds
        self.stamps = []

    def write(self, text: str) -> int:
        m = re.search(r"round\s+(\d+)", text)
        if m and int(m.group(1)) % self.chunk_rounds == 0:
            self.stamps.append(time.perf_counter())
        return len(text)


class Setup:
    """One cell's data, params, cohorts and trainer for one seed."""

    def __init__(self, cell, seed: int, program: bool = True):
        self.cell = cell
        self.seeds = cohorts.derived_seeds(seed)
        t = cell.traffic
        self.data = cell.datagen.Data(cell.config, cell.num_devices,
                                      self.seeds["data"])
        self.schedule = cohorts.make_schedule(
            t, cell.num_devices, seed, self.data.sizes,
            self.data.batch_size)
        self.params0 = cell.model.init_params(cell.config,
                                              self.seeds["params"])
        self.fetch = {"s": 0.0, "calls": 0}
        if program:
            self.dataset = self.data.program_dataset(t["client_source"])
            self._wrap_fetch()
            self.trainer = cell.model.build_trainer(
                cell.config, t, self.dataset, self.seeds["program"])

    def _wrap_fetch(self):
        """Time this dataset instance's ``device_batches`` (the
        streaming plan's cohort fetch) and mark it on the trace."""
        import jax
        inner = self.dataset.device_batches
        fetch = self.fetch

        def device_batches(k):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("cohort_fetch"):
                out = inner(k)
            fetch["s"] += time.perf_counter() - t0
            fetch["calls"] += 1
            return out

        self.dataset.device_batches = device_batches

    def run(self, params, start: int, rounds: int, verbose: bool = False):
        """The window's call: ``trainer.run`` over ``rounds`` rounds of
        the run's cohorts from round ``start`` on, blocked on its final
        params."""
        import jax
        hist, out = self.trainer.run(
            params, rounds, selections=self.schedule.selections(start),
            verbose=verbose, eval_every=int(self.cell.traffic["eval_every"]))
        return hist, jax.block_until_ready(out)

    def resolution(self, ir_dir: str) -> dict:
        """What ``auto`` resolved to (``chip_smoke.check_main``'s
        reading)."""
        tr = self.trainer
        driver = getattr(tr, "_resolve_driver", lambda: "?")()
        return {"engine": "batched" if getattr(tr, "engine", None)
                is not None else "loop",
                "round_driver": driver,
                "kernels": kernel_names(ir_dir)}


def warm_up(s: Setup, second: bool = True) -> dict:
    """Both warm-up chunks: the first compiles (its IR dumped, to read
    the round program's kernels) and is what the reference follows;
    the second times a chunk."""
    import jax
    import numpy as np
    out = {}
    c = s.schedule.chunk_rounds
    ir_dir = tempfile.mkdtemp(prefix="bench_ir_")
    try:
        with compile_log() as log:
            jax.config.update("jax_dump_ir_to", ir_dir)
            try:
                t0 = time.perf_counter()
                hist, p1 = s.run(s.params0, 0, c)
                out["first_chunk_s"] = time.perf_counter() - t0
            finally:
                jax.config.update("jax_dump_ir_to", "")
        out["resolved"] = s.resolution(ir_dir)
    finally:
        shutil.rmtree(ir_dir, ignore_errors=True)
    out["compile_s"], out["compiled"] = log["s"], len(log["lowered"])
    out["losses"] = np.asarray(hist["loss"], np.float64)
    out["loss_rounds"] = np.asarray(hist["round"], np.int64)
    out["params"] = jax.tree_util.tree_map(np.asarray, p1)
    if not second:
        return out
    with compile_log() as log2:
        t0 = time.perf_counter()
        _, p2 = s.run(p1, c, c)
        out["chunk_s"] = time.perf_counter() - t0
    out["second_compiled"] = log2["lowered"]
    out["params_end"] = p2
    return out


def window_work(cell, data, rounds) -> dict:
    """Summed ``round_work`` of ``rounds``."""
    eval_sizes = data.sizes(data.eval_ids())
    tot = {}
    for s1, s2 in rounds:
        w = cell.model.round_work(cell.config, cell.traffic,
                                  data.sizes(s1), data.sizes(s2),
                                  eval_sizes)
        for k, v in w.items():
            tot[k] = tot.get(k, 0.0) + v
    return tot


def reference(s: Setup, **kw):
    """The plain reference over the first warm-up chunk's rounds:
    ``(losses, params, first-round change)``."""
    import jax
    import numpy as np
    data, cell = s.data, s.cell
    eval_ids = data.eval_ids()
    p0 = jax.tree_util.tree_map(np.asarray, s.params0)
    return cell.model.reference_rounds(
        cell.config, cell.traffic, data.client, eval_ids,
        data.sizes(eval_ids), p0, s.schedule.warmup_chunk(0), **kw)


def readings(s: Setup, losses, loss_rounds, params, ref) -> dict:
    """The compared numbers of a first chunk (``losses`` after the
    1-based rounds ``loss_rounds``, final ``params``) against ``ref``."""
    import jax
    import numpy as np
    ref_losses, ref_params, ref_first = ref
    p0 = jax.tree_util.tree_map(np.asarray, s.params0)
    gap, leaves = compare.change_gap(p0, params, ref_params, ref_first)
    return {"loss_gap": compare.loss_gap(losses, loss_rounds, ref_losses),
            "change_gap": gap, "leaf_gaps": leaves}


def check(s: Setup, warm: dict) -> dict:
    """The reference over the first warm-up chunk, and the readings."""
    return readings(s, warm["losses"], warm["loss_rounds"],
                    warm["params"], reference(s))


def trace_window(s: Setup, p, rounds: int, trace_dir: str):
    """The window under the profiler; returns ``(hist, params,
    seconds, extracted trace)``."""
    import jax
    from bench import trace as trace_mod
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            t0 = time.perf_counter()
            hist, out = s.run(p, s.schedule.num_warmup, rounds)
            dt = time.perf_counter() - t0
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return hist, out, dt, trace_mod.extract(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import repro  # noqa: F401  the system under test, before anything
    try:
        dev = device_info(cell.chips)
    except SystemExit as exc:
        return int(exc.code)
    from bench.peaks import peaks_for
    peaks = peaks_for(dev["kind"])
    import jax
    import numpy as np
    say("cache", enable_compile_cache())
    say("device", dev)

    s = Setup(cell, args.seed)
    warm = warm_up(s)
    say("resolved", warm["resolved"])
    say("warmup", {"first_chunk_s": warm["first_chunk_s"],
                   "compile_s": warm["compile_s"],
                   "programs_compiled": warm["compiled"],
                   "chunk_s": warm["chunk_s"],
                   "second_chunk_compiled": warm["second_compiled"]})
    c = int(cell.traffic["chunk_rounds"])
    chunks = int(min(cell.traffic["max_window_chunks"],
                     max(1, round(args.seconds / warm["chunk_s"]))))
    rounds = s.schedule.window_rounds(chunks)
    p = warm.pop("params_end")
    for k in s.fetch:
        s.fetch[k] = 0
    setup_s = time.time() - T_START
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with compile_log() as log:
            if args.trace:
                hist, p, window_s, raw = trace_window(s, p, len(rounds),
                                                      trace_dir)
                stamps = []
            else:
                clock = ChunkClock(c)
                t0 = time.perf_counter()
                with contextlib.redirect_stdout(clock):
                    hist, p = s.run(p, s.schedule.num_warmup, len(rounds),
                                    verbose=True)
                t1 = time.perf_counter()
                window_s = t1 - t0
                stamps = [t0] + clock.stamps
                raw = None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    n = len(rounds)
    losses = np.asarray(hist["loss"], np.float64)
    failed = int(np.sum(~np.isfinite(losses)))
    unwarmed = set()
    if cell.traffic["client_source"] == "streaming":
        unwarmed = (cohorts.covered_pairs(rounds, s.data.sizes,
                                          s.data.batch_size)
                    - cohorts.covered_pairs(s.schedule.warmup,
                                            s.data.sizes,
                                            s.data.batch_size))
    say("window", {"rounds": n, "chunks": chunks, "seconds": window_s,
                   "chunk_s": list(np.diff(stamps)),
                   "compiled_in_window": log["lowered"],
                   "unwarmed_shape_pairs": sorted(unwarmed),
                   "fetch": dict(s.fetch)})
    dev["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips]))
    metrics = {}
    breakdown = None
    if args.trace:
        from bench import trace as trace_mod
        red = trace_mod.Reduced(raw)
        dev["busy_s"] = red.busy_s()
        dev["window_s"] = red.window_s
        ctx = SimpleNamespace(
            trace=red, rounds=n, chips=cell.chips, peaks=peaks,
            window_s=window_s, kernels=warm["resolved"]["kernels"],
            work=window_work(cell, s.data, rounds), bound={},
            fetch_s=s.fetch["s"], fetch_calls=s.fetch["calls"])
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": red.device_ops(),
                     "idle_gaps": red.idle_gaps()}
        say("work", dict(ctx.work, bound=ctx.bound))
        del raw, red
    else:
        values = {"rounds_per_s": n / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    # the program's state goes before the reference runs
    del p, hist, s.trainer, s.dataset
    gc.collect()
    readings = check(s, warm)
    correct, checks = compare.judge(readings, cell.limits)
    say("leaf_gaps", readings["leaf_gaps"])
    result = {"correct": bool(correct and failed == 0), "attempted": n,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, chk in checks.items():
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
