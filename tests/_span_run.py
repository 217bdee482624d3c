"""A tiny streaming run profiled on the CPU, shared by tests/test_spans.py
and bench/tests/test_bench_program_spans.py.

FedDANE on a synthetic(1,1) shard source of N=1000 clients, K=4, two
chunks of two rounds, run once to compile and then again, on cohorts the
first run never touched, under ``jax.profiler.trace`` inside a
``window`` span (the profiler options the benchmark's traced window
uses: host spans on, no Python tracer).
"""
import glob
import os

import jax
import numpy as np

from repro.configs.base import FederatedConfig
from repro.core import FederatedTrainer
from repro.data import make_synthetic_stream
from repro.models.param import init_params
from repro.models.small import logreg_loss, logreg_specs

N, K, CHUNK, ROUNDS = 1000, 4, 2, 4
WINDOW = "window"


def config(**kw) -> FederatedConfig:
    base = dict(algorithm="feddane", num_devices=N, devices_per_round=K,
                local_epochs=1, local_batch_size=10, learning_rate=0.01,
                mu=0.001, seed=5, engine="batched", round_driver="scan",
                chunk_rounds=CHUNK)
    return FederatedConfig(**{**base, **kw})


def selections(clients: np.ndarray, seed: int) -> np.ndarray:
    """``(ROUNDS, 2, K)`` cohorts drawn from ``clients``."""
    rng = np.random.default_rng(seed)
    return np.stack([np.stack([rng.choice(clients, K, replace=False)
                               for _ in range(2)])
                     for _ in range(ROUNDS)])


def profiled_stream_run(trace_dir: str) -> dict:
    """``{"xplane": path, "made": clients generated in the traced run,
    "rounds": ROUNDS, "driver": the trainer's ScannedDriver}``."""
    src = make_synthetic_stream(1.0, 1.0, num_devices=N, seed=3)
    tr = FederatedTrainer(logreg_loss, src, config(
        client_source="streaming"))
    params = init_params(logreg_specs(60, 10), jax.random.PRNGKey(0))
    tr.run(params, ROUNDS, selections=selections(np.arange(N // 2), 1))
    made = src.materialized_clients
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW):
            _, out = tr.run(params, ROUNDS, selections=selections(
                np.arange(N // 2, N), 2))
            jax.block_until_ready(out)
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return {"xplane": path, "made": src.materialized_clients - made,
            "rounds": ROUNDS, "driver": tr._scanned}
