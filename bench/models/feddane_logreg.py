"""FedDANE over multinomial logistic regression: the system under test,
its plain reference, and the round's work counts.

System: ``FederatedTrainer(logreg_loss, data, cfg).run(params, rounds,
selections=...)`` with every execution knob on ``auto``.

Reference: the same rounds in plain ``jax.numpy`` at float32 and
``highest`` matmul precision, one client at a time, written from the
paper's Alg. 2 and the data layer's batching contract, importing nothing
of the program:

- a client's ``n`` samples are cycled to ``nb`` whole batches of ``B``,
  ``nb`` the power of two at or above ``ceil(n / B)``
  (``data/batching.py``); its loss is the mean over those batches of the
  batch-mean cross-entropy;
- phase A: ``g = mean_{k in S1} grad F_k(w0)``;
- each solve client ``k in S2`` runs E epochs of SGD over its batches in
  order, from ``w0``, on ``grad f(w; batch) + (g - grad F_k(w0))
  + mu (w - w0)``;
- the server takes the mean of the K solutions (server SGD at lr 1.0
  is that mean);
- the eval after each round is ``sum_k n_k F_k(w) / sum_k n_k`` over
  the eval clients.

``reference_rounds(..., dtype=bfloat16)`` is the control: the same
arithmetic with params, data and every operation in bfloat16.
``fault="half_cohort"`` averages only the first half of each solve
cohort; ``fault="unchanged"`` returns each round's params unchanged.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def build_trainer(config: dict, traffic: dict, dataset, seed: int):
    """The program's trainer for this cell, knobs on ``auto``."""
    from repro.configs.base import FederatedConfig
    from repro.core import FederatedTrainer
    from repro.models.small import logreg_loss
    cfg = FederatedConfig(
        algorithm=config["algorithm"],
        num_devices=dataset.num_devices,
        devices_per_round=int(traffic["devices_per_round"]),
        local_epochs=int(traffic["local_epochs"]),
        local_batch_size=int(config["local_batch_size"]),
        learning_rate=float(config["learning_rate"]),
        mu=float(config["mu"]),
        seed=seed,
        chunk_rounds=int(traffic["chunk_rounds"]),
        client_source=traffic["client_source"],
        mesh_devices=traffic["mesh_devices"],
        edge_shards=int(traffic["edge_shards"]))
    return FederatedTrainer(logreg_loss, dataset, cfg)


def init_params(config: dict, seed: int) -> Dict[str, jax.Array]:
    """``N(0, std^2)`` weights and bias, made on the device in one
    jitted call from the seed."""
    d, c = int(config["num_features"]), int(config["num_classes"])
    std = float(config["param_init_std"])

    @jax.jit
    def make(key):
        kw, kb = jax.random.split(key)
        return {"w": std * jax.random.normal(kw, (d, c), jnp.float32),
                "b": std * jax.random.normal(kb, (c,), jnp.float32)}

    return make(jax.random.PRNGKey(seed))


# -- work counts ------------------------------------------------------------

def round_work(config: dict, traffic: dict, gather_sizes, solve_sizes,
               eval_sizes) -> Dict[str, float]:
    """Operations and bytes one round needs, from the unpadded sample
    counts of its cohorts (no padded or masked step counts).

    A forward pass is ``2 d C`` operations a sample, a gradient (forward
    plus ``x^T r``) ``4 d C``.  Phase A takes one gradient over each
    gather client; each solve client takes one at ``w0`` for its
    correction and ``E`` epochs of SGD steps; the eval is one forward
    pass over the eval clients.  ``solve_bytes``: the solve cohort's
    samples read once (``d`` float32 features and an int32 label) plus
    the K anchors, corrections and solutions."""
    d, c = int(config["num_features"]), int(config["num_classes"])
    e = int(traffic["local_epochs"])
    gather, solve = float(np.sum(gather_sizes)), float(np.sum(solve_sizes))
    k = len(solve_sizes)
    fwd, grad = 2.0 * d * c, 4.0 * d * c
    solve_flops = e * solve * grad
    return {
        "solve_flops": solve_flops,
        "solve_bytes": solve * (d + 1) * 4.0 + 3.0 * k * (d + 1) * c * 4.0,
        "round_flops": (solve_flops + (gather + solve) * grad
                        + float(np.sum(eval_sizes)) * fwd),
    }


# -- plain reference --------------------------------------------------------

def _batches(arrays: dict, batch_size: int, dtype):
    """A client's samples cycled to whole batches, the count rounded up
    to a power of two: ``(nb, B, d)`` features and ``(nb, B)`` labels."""
    x, y = arrays["x"], arrays["y"]
    n = len(y)
    nb = 1 << max(0, (-(-n // batch_size) - 1).bit_length())
    idx = np.arange(nb * batch_size) % n
    return (jnp.asarray(x[idx].reshape(nb, batch_size, -1), dtype),
            jnp.asarray(y[idx].reshape(nb, batch_size)))


def _batch_loss(p, x, y):
    logits = x @ p["w"] + p["b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


_batch_grad = jax.grad(_batch_loss)


@jax.jit
def _full_grad(p, xb, yb):
    """Mean over the client's batches of the batch gradient."""
    gs = jax.vmap(_batch_grad, in_axes=(None, 0, 0))(p, xb, yb)
    return jax.tree_util.tree_map(lambda g: g.mean(axis=0), gs)


@jax.jit
def _eval_loss(p, xb, yb, wb):
    """``sum_b wb[b] * loss(batch b)`` over the eval clients' batches."""
    return (jax.vmap(_batch_loss, in_axes=(None, 0, 0))(p, xb, yb)
            * wb).sum()


def _make_solve(epochs: int, lr: float, mu: float):
    @jax.jit
    def solve(w0, corr, xb, yb):
        def step(w, batch):
            g = _batch_grad(w, *batch)
            w = jax.tree_util.tree_map(
                lambda wi, gi, ci, ai: wi - lr * (gi + ci + mu * (wi - ai)),
                w, g, corr, w0)
            return w, None

        def epoch(w, _):
            return jax.lax.scan(step, w, (xb, yb))[0], None

        return jax.lax.scan(epoch, w0, None, length=epochs)[0]

    return solve


def reference_rounds(config: dict, traffic: dict, client, eval_ids,
                     eval_sizes, params0, rounds: np.ndarray,
                     dtype=jnp.float32, fault: Optional[str] = None):
    """Run ``rounds`` (``(T, 2, K)`` cohorts) from ``params0``.

    ``client(k)`` gives client k's raw arrays.  Returns ``(losses,
    params, first_change)``: the eval loss after each round, the params
    after the last, and the params' change in the first round (float32
    numpy leaves)."""
    bsz = int(config["local_batch_size"])
    lr = jnp.asarray(config["learning_rate"], dtype)
    mu = jnp.asarray(config["mu"], dtype)
    solve = _make_solve(int(traffic["local_epochs"]), lr, mu)
    precision = "highest" if dtype == jnp.float32 else "default"
    cache: Dict[int, tuple] = {}

    def batches(k):
        k = int(k)
        if k not in cache:
            cache[k] = _batches(client(k), bsz, dtype)
        return cache[k]

    w = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params0)
    # every eval batch weighted n_k / (sum n * nb_k): the n_k-weighted
    # mean over clients of each client's mean batch loss
    wts = np.asarray(eval_sizes, np.float64) / np.sum(eval_sizes)
    ev = [batches(k) for k in eval_ids]
    ex = jnp.concatenate([b[0] for b in ev])
    ey = jnp.concatenate([b[1] for b in ev])
    ew = jnp.asarray(np.concatenate(
        [np.full(len(b[1]), wk / len(b[1])) for b, wk in zip(ev, wts)]),
        dtype)
    losses: List[float] = []
    first = None
    tmap = jax.tree_util.tree_map
    with jax.default_matmul_precision(precision):
        for s1, s2 in rounds:
            gs = [_full_grad(w, *batches(k)) for k in s1]
            g = tmap(lambda *a: sum(a) / len(a), *gs)
            sols = []
            for k in s2:
                xb, yb = batches(k)
                corr = tmap(jnp.subtract, g, _full_grad(w, xb, yb))
                sols.append(solve(w, corr, xb, yb))
            if fault == "half_cohort":
                sols = sols[:len(sols) // 2]
            new = tmap(lambda *a: sum(a) / len(a), *sols)
            if fault == "unchanged":
                new = w
            if first is None:
                first = tmap(lambda a, b: np.asarray(a - b, np.float32),
                             new, w)
            w = new
            losses.append(float(_eval_loss(w, ex, ey, ew)))
    return (np.asarray(losses),
            tmap(lambda a: np.asarray(a, np.float32), w), first)
