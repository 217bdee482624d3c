"""Pod-as-client federated rounds: the faithful multi-pod FedDANE mapping.

The plain dry-run step treats the whole mesh as one round participant
(cross-silo view).  This module maps Alg. 2 literally onto the 2×16×16
mesh: **each pod is one federated client**.  Per-client state carries a
leading ``num_pods`` dim sharded over the ``pod`` axis via ``shard_map``
(manual over ``pod``, auto over ``data``/``model``), so clients genuinely
diverge over E>0 local steps inside one lowered program, and the two
FedDANE aggregations appear as explicit cross-pod collectives:

  phase A:  g_t      = pmean_pods( grad F_k(anchor) )        (Alg.2 line 6)
  phase B:  w^t      = pmean_pods( w_k after local steps )   (Alg.2 line 9)

``hloanalysis.cross_pod_split`` then separates exactly these DCN-class
bytes from the intra-pod TP/FSDP traffic.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core import pytree as pt
from repro.launch import steps
from repro.models import transformer
from repro.models.param import ParamSpec, param_pspecs


def _client_pspecs(cfg: ModelConfig, mesh: Mesh):
    """Per-leaf PartitionSpecs for client-stacked params: leading 'pod'
    dim (one client per pod) + intra-pod weight rules (FSDP over 'data'
    only — the pod axis belongs to the clients)."""
    from repro.models.param import ShardingRules
    # vocab stays unsharded: the embedding gather with a vocab-sharded
    # table trips an XLA SPMD CHECK under partial-manual (pod) mode
    # (spmd_partitioner_util.cc:504); d_model FSDP keeps the table small.
    rules = ShardingRules({
        "d_model": "data", "d_ff": "model", "heads": "model",
        "kv_heads": "model", "head_dim": None, "vocab": None,
        "experts": "model", "ssm_inner": "model", "ssm_state": None,
        "layers": None, "conv": None,
    })
    base = param_pspecs(transformer.model_specs(cfg), rules, mesh)
    return jax.tree_util.tree_map(
        lambda ps: P(*(("pod",) + tuple(ps))), base)


def make_podfed_round_step(cfg: ModelConfig, mesh: Mesh, *,
                           eta: float = 1e-3, mu: float = 0.01,
                           local_steps: int = 1,
                           remat: str = "full") -> Tuple[Callable, Dict]:
    """Returns (round_fn, spec_info).  State leaves carry a leading
    num_pods dim; batch is (num_pods, local_steps, per_client_batch, ...).
    """
    num_pods = mesh.shape.get("pod", 1)

    # shard_map in_specs may only reference the MANUAL axis ('pod'); the
    # auto-axis (data/model) sharding propagates from the arrays' own
    # NamedShardings (set in abstract_podfed_args / at materialization).
    pod_leading = jax.tree_util.tree_map(
        lambda s: P("pod"), transformer.model_specs(cfg),
        is_leaf=lambda x: isinstance(x, ParamSpec))
    in_state_specs = {k: pod_leading for k in ("params", "anchor", "g_t")}

    def local_loss(p, b):
        return transformer.loss_fn(p, b, cfg, remat=remat)

    def round_body(state, batch):
        # inside shard_map(manual over 'pod'): leading dims are LOCAL (=1)
        squeeze = lambda t: jax.tree_util.tree_map(
            lambda x: x.reshape(x.shape[1:]), t)
        expand = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        params = squeeze(state["params"])
        anchor = squeeze(state["anchor"])
        batch = jax.tree_util.tree_map(lambda x: x.reshape(x.shape[1:]),
                                       batch)  # (steps, b, ...)

        first = jax.tree_util.tree_map(lambda x: x[0], batch)
        # ---- phase A: client gradient at the anchor + CROSS-POD mean ----
        g_anchor = jax.grad(local_loss)(anchor, first)
        g_t = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "pod"), g_anchor)   # Alg.2 line 6
        corr = pt.sub(g_t, g_anchor)

        # ---- phase B: E local DANE-subproblem steps (clients diverge) ---
        def local_step(w, b):
            g = jax.grad(local_loss)(w, b)
            dane = pt.add(pt.add(g, corr),
                          pt.scale(pt.sub(w, anchor), mu))
            return pt.sub(w, pt.scale(dane, eta)), None

        w_k, _ = jax.lax.scan(local_step, params, batch)

        # ---- aggregation: CROSS-POD iterate mean (Alg.2 line 9) ---------
        w_new = jax.tree_util.tree_map(
            lambda w: jax.lax.pmean(w, "pod"), w_k)
        new_state = {"params": expand(w_new), "anchor": expand(w_new),
                     "g_t": expand(g_t)}
        loss = local_loss(w_new, first)
        return new_state, {"loss": jax.lax.pmean(loss, "pod")}

    bspecs_tmpl = steps.train_batch_specs(
        cfg, InputShape("x", 1, 1, "train"))  # structure only
    batch_in_specs = jax.tree_util.tree_map(
        lambda s: P("pod"), bspecs_tmpl)

    round_fn = jax.shard_map(
        round_body, mesh=mesh,
        in_specs=(in_state_specs, batch_in_specs),
        out_specs=({k: in_state_specs[k] for k in
                    ("params", "anchor", "g_t")}, {"loss": P()}),
        axis_names={"pod"}, check_vma=False,
    )
    info = {"num_pods": num_pods, "state_pspecs": in_state_specs,
            "batch_pspec": batch_in_specs}
    return round_fn, info


def abstract_podfed_args(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                         *, local_steps: int = 1, dtype=jnp.bfloat16):
    """ShapeDtypeStructs (with shardings) for lowering the pod-fed round."""
    from jax.sharding import NamedSharding

    num_pods = mesh.shape.get("pod", 1)
    per_client = shape.global_batch // num_pods // local_steps
    assert per_client > 0, "global batch too small for pods x steps"

    specs = transformer.model_specs(cfg)
    pspecs = _client_pspecs(cfg, mesh)

    def sds(s, ps):
        return jax.ShapeDtypeStruct(
            (num_pods,) + s.shape, dtype,
            sharding=NamedSharding(mesh, ps))

    one_tree = jax.tree_util.tree_map(
        sds, specs, pspecs, is_leaf=lambda x: isinstance(x, ParamSpec))
    state = {k: one_tree for k in ("params", "anchor", "g_t")}

    inner = steps.train_batch_specs(
        cfg, InputShape(shape.name, shape.seq_len, per_client, "train"),
        dtype)
    batch = {}
    for k, s in inner.items():
        shp = (num_pods, local_steps) + s.shape
        ps = P(*(("pod", None, "data") + (None,) * (len(s.shape) - 1)))
        batch[k] = jax.ShapeDtypeStruct(
            shp, s.dtype, sharding=NamedSharding(mesh, ps))
    return state, batch
