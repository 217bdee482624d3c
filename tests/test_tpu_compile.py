"""Compile the round's Pallas kernels for a TPU v5e chip that is described,
not attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
blocks not aligned to the (8, 128) tiling, SMEM overflow, contractions it
cannot lower.  Here each kernel is lowered through Mosaic and compiled by
the chip's own compiler, at the widths the round runs:

- FEMNIST multinomial logistic regression: d=784, C=10, B=10, K=10;
- synthetic(a, b): d=60, C=10, B=10, K=10.

Nothing runs.  The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.codec import codec_aggregate
from repro.kernels.dane_update import LANES, dane_update_2d, dane_update_flat
from repro.kernels.flatpack import flat_spec
from repro.kernels.local_solve import linear_logistic_step, local_epoch
from repro.models.param import init_params
from repro.models.small import logreg_specs

#: (d, C, B, K, nb, E): nb batches per client, E local epochs.  FEMNIST's
#: largest N=200 client holds 64 batches of 10.
WIDTHS = {
    "femnist": dict(d=784, C=10, B=10, K=10, nb=64, E=20),
    "synthetic": dict(d=60, C=10, B=10, K=10, nb=16, E=20),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``spec(shape, dtype)`` -> a ShapeDtypeStruct on one described chip."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return spec


def _compile(fn, *args) -> str:
    """Compile ``fn`` for the described chip; its Mosaic kernels must be
    in the program (no interpret-mode fallback)."""
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile().as_text()


def _flat_rows(d: int, C: int) -> int:
    return flat_spec(init_params(logreg_specs(d, C),
                                 jax.random.PRNGKey(0))).rows


def _stacked(chip, K, d, C):
    return {"w": chip((K, d, C)), "b": chip((K, C))}


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_dane_update_flat_compiles(chip, width):
    w = WIDTHS[width]
    rows = _flat_rows(w["d"], w["C"])
    buf = chip((w["K"] * rows, LANES))
    _compile(lambda a, g, c, x, m: dane_update_flat(
        a, g, c, x, 0.003, 0.001, m, rows), buf, buf, buf, buf,
        chip((w["K"],)))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_dane_update_2d_compiles(chip, width):
    """At the K-stacked row count left unpadded (620 rows at FEMNIST
    width): no aligned divisor, so one whole-dim block."""
    w = WIDTHS[width]
    rows = w["K"] * -(-(w["d"] * w["C"] + w["C"]) // LANES)
    buf = chip((rows, LANES))
    _compile(lambda a, g, c, x: dane_update_2d(a, g, c, x, 0.003, 0.001),
             buf, buf, buf, buf)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_codec_aggregate_compiles(chip, width):
    w = WIDTHS[width]
    rows = _flat_rows(w["d"], w["C"])
    _compile(codec_aggregate, chip((w["K"], rows, LANES)), chip((w["K"],)),
             chip((w["K"],)))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_linear_logistic_step_compiles(chip, width):
    w = WIDTHS[width]
    K, d, C, B = w["K"], w["d"], w["C"], w["B"]
    _compile(lambda p, x, y, c, p0, m: linear_logistic_step(
        p, {"x": x, "y": y}, c, p0, eta=0.003, mu=0.001, mask=m),
        _stacked(chip, K, d, C), chip((K, B, d)), chip((K, B), jnp.int32),
        _stacked(chip, K, d, C), {"w": chip((d, C)), "b": chip((C,))},
        chip((K,)))


@pytest.mark.parametrize("width,K", [("femnist", None), ("synthetic", None),
                                     ("femnist", 64)])
def test_local_epoch_compiles(chip, width, K):
    """``K=64`` at E*nb=4096 steps: a whole (K, E*nb) step-mask table is
    1 MiB, the chip's entire SMEM; one client row at a time fits."""
    w = WIDTHS[width]
    K = K or w["K"]
    d, C, B, nb = w["d"], w["C"], w["B"], w["nb"]
    E = w["E"] if K == w["K"] else 4096 // nb
    _compile(lambda p0, c, x, y, sm: local_epoch(
        p0, c, {"x": x, "y": y}, eta=0.003, mu=0.001, num_epochs=E,
        step_mask=sm),
        {"w": chip((d, C)), "b": chip((C,))}, _stacked(chip, K, d, C),
        chip((K, nb, B, d)), chip((K, nb, B), jnp.int32),
        chip((K, E * nb)))
