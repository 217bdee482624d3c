"""Batched round engine: whole federated rounds as single jitted programs.

The looped path in ``FederatedTrainer`` dispatches one jitted solver /
grad call *per selected device* and aggregates host-side lists — at K
devices per round that is O(K) dispatches, O(K) host round-trips, and a
Python-level mean.  DANE's structure makes this unnecessary: every device
solves the *same* perturbed subproblem, only its data and correction
differ.  This module exploits that:

- the K selected devices' padded batch stacks are stacked along a
  leading device axis (``data.batching.stack_device_batches``; bucketed
  power-of-two shapes bound recompilation),
- the local solver and the full-gradient are ``jax.vmap``-ed over that
  axis (``client.make_batched_solver`` / ``make_batched_grad_fn``),
- the whole round — gradient gather, per-device correction, solves,
  server mean, state updates — fuses into **one jitted round program**,
  with round-state buffers donated on accelerator backends,
- inside the solver, the per-step update runs through the fused
  ``dane_update`` Pallas kernel (interpret on CPU, Mosaic on TPU)
  instead of the 4-op pytree expression.

There is no per-algorithm code here: :class:`RoundEngine` is a generic
interpreter of the registered :class:`~repro.core.strategies.
AlgorithmSpec` (see ``core/strategies``).  The spec declares the phase
structure, correction rule, and state updates; the engine compiles ONE
round program for whatever spec it is given — registering a new
algorithm requires no engine change.

Execution model
---------------
Devices advance in lockstep: step j of the scan applies batch j of every
device at once.  Devices whose (bucketed) stack is shorter than the
stacked maximum take masked identity steps, so each device's trajectory
is *exactly* the one the scalar solver would produce — the two engines
agree to float-accumulation order (parity tests pin this at atol 1e-5).

The looped path (``FederatedConfig.engine = "loop"``) remains the
authoritative reference: it is an independent interpretation of the
same spec (plain pytree ops, per-device dispatch) used to A/B the
engine and to validate the Pallas kernel end-to-end.  Semantics the
engine does not accelerate: ``sample_with_replacement=True`` for
control-variate specs (SCAFFOLD) would update duplicated device
controls once, not twice (the looped path applies duplicates
sequentially), so ``FederatedTrainer`` routes that combination to the
looped path even when ``engine="batched"``.

Both this engine and the scanned driver below keep the synchronous
round barrier: the server steps once every selected device (or the
scenario's deadline) has been accounted for.  The asynchronous
alternative — clients launching from stale anchors, the server
committing whenever ``buffer_size`` updates arrive — is the fourth
driver, ``core/async_engine.py``'s ``BufferedDriver``
(``round_driver="buffered"``), which reuses this module's batched
solver for its cohort launches and the same ``AlgorithmSpec``
interpretation contract.

Scanned multi-round driver
--------------------------
``ScannedDriver`` (``make_scanned_run``) is the layer above: it fuses
``chunk_rounds`` whole federated rounds into ONE ``jax.lax.scan``
program, removing the O(num_rounds) per-round dispatches and host
round-trips that remain when ``FederatedTrainer.run`` drives the jitted
round functions from Python.  Its execution model:

- **On-device sampling**: device selection moves from host numpy to
  ``jax.random`` (``server.sample_devices_onchip``; Gumbel top-k for
  weighted sampling without replacement), keyed off a PRNG key threaded
  through the scan carry.  The selection gathers rows of the
  *pre-stacked all-device* batch tensors (every device padded to the
  dataset-wide bucketed ``nb_max``), so shapes stay fixed across rounds
  and the whole run compiles once per chunk length.  Host and device
  samplers draw from the same distribution but different bit streams:
  cross-driver selection identity is NOT a contract (see server.py);
  per-driver seed reproducibility is.
- **On-device history**: the loss curve is accumulated as scan outputs.
  Global loss is evaluated *inside* the scan at ``eval_every`` cadence
  via ``lax.cond`` over the all-device stacked eval tensors
  (``data.batching.stack_eval_batches``); skipped rounds emit NaN that
  the host filters at chunk boundaries.  Accumulation runs in jnp
  float32 on device rather than host Python floats, so eval parity with
  the Python driver holds to float-accumulation order (pinned at
  atol 1e-5), not bit-exactly.
- **Chunked execution**: ``run()`` dispatches the scan in
  ``chunk_rounds``-sized chunks; checkpoint saves (checkpoint/store.py)
  and verbose printing interleave at chunk boundaries — the only points
  where state returns to host.

The scan body is the SAME generic spec interpretation the per-round
engine jits (``RoundEngine.round_body``), wrapped with on-device
gather/scatter of selections and algorithm state — so new registered
specs run under the scanned driver with no driver change either.

Semantic caveats: control-variate specs + ``sample_with_replacement``
stay on the Python driver (duplicated selections must update a device's
control twice, sequentially — same restriction as the batched engine,
but here the whole driver falls back); a spec's ``decay(cfg, t)`` is
computed from the traced round index, and per-round ``comm_rounds`` is
reconstructed host-side (it is a deterministic ``comm_per_round * t``
ramp).

Mesh-sharded rounds and the aggregation tree
--------------------------------------------
Both the per-round program and the scanned chunk program optionally run
their stacked client axis over a JAX mesh (``core/sharding.py``;
``FederatedConfig.mesh_devices``): the generic round body is wrapped in
``shard_map`` (``_shard_wrap``) so each of the D mesh devices solves
K/D clients, with every cross-client reduction — ``mean_k``, the masked
scenario reductions, the server pseudo-gradient aggregate, control
deltas, telemetry counts — expressed as psum/pmean collectives.  With
``FederatedConfig.edge_shards > 1`` the mesh is the 2-D hierarchical
aggregation tree (``(edge, device)`` axes) and every one of those
collectives becomes the nested leaf→edge→server reduction via
``sharding.tree_psum`` / ``tree_pmean`` — the engine code is axis-name
generic, so flat and tree meshes run the same body.  The whole round
(or whole chunk of rounds) stays ONE jitted SPMD program; K must
divide evenly over the mesh (checked early, with a clear error) so
sharded aggregation is exactly the K-mean.  ``mesh_devices=1`` builds
no mesh: every program in this module is then structurally the
pre-mesh build, bit-identical.  Parity gates: tests/test_sharding.py,
tests/_sharded_child.py (tree vs flat vs no-mesh).

Population-scale streaming (``ClientShardSource``)
--------------------------------------------------
``ScannedDriver`` has two data plans, switched by
``FederatedConfig.client_source`` (``data/shard_source.py``'s
``resolve_streaming``):

- **stacked** (the pre-population plan): ALL N clients' padded batch
  tensors are materialized once up front and each round gathers K rows
  on device.  O(N) memory — fine to a few thousand clients, impossible
  at N=1e6.
- **streaming**: nothing O(N) is ever materialized.  The host
  replicates the scan body's exact PRNG key-split schedule (same
  ``jax.random`` ops, eagerly), so per-round selections and scenario
  uniforms are bit-identical to the stacked scan; it then materializes
  ONLY the selected cohorts' batches from the
  :class:`~repro.data.shard_source.ClientShardSource` and feeds them
  through the scan's ``xs``, padded on the host to a chunk-wide
  bucketed batch count (padding rides ``valid=0`` masked identity
  steps, so trajectories match the stacked gather exactly) and moved
  to the device in one transfer per leaf a chunk.  Per-client
  persistent state (SCAFFOLD controls, codec error feedback) lives in
  host-side :class:`~repro.core.client_state.SparseClientState` stores:
  cohort rows ride ``xs`` in, updated rows ride the scan outputs back,
  and the host scatters them — a chunk is truncated at the first
  within-chunk cohort repeat so state reads never go stale.  Memory is
  O(K · chunk_rounds + eval sample), independent of N; parity with the
  stacked plan is pinned in tests/test_population.py.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FederatedConfig
from repro.core import codecs
from repro.core import pytree as pt
from repro.core import server
from repro.core import sharding
from repro.core.client import make_batched_grad_fn, make_batched_solver
from repro.core.scenarios import (availability_mask, env_channels,
                                  is_trivial, realize_env, scenario_spec)
from repro.core.strategies import (AlgorithmSpec, ControlCtx, CorrCtx,
                                   algorithm_spec, init_aux,
                                   make_server_opt, runtime_state_fields)
from repro.data.batching import (stack_device_batches, stack_eval_batches,
                                 stack_host_batches)
from repro.data.shard_source import resolve_streaming
from repro.kernels.codec import codec_aggregate, codec_aggregate_partial
from repro.kernels.flatpack import (LANES, flat_spec, pack_broadcast,
                                    pack_stacked, unpack)

#: Sentinel for "derive the mesh from ``cfg.mesh_devices``" (the
#: default) vs. an explicit ``mesh=None`` / ``mesh=Mesh`` override.
_MESH_FROM_CFG = object()


def _donate_argnums(nums: Tuple[int, ...]) -> Tuple[int, ...]:
    """Donate round-state buffers on accelerators; CPU ignores donation
    (and warns), so skip it there."""
    return nums if jax.default_backend() != "cpu" else ()


def _stack_zeros(w0, k: int):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((k,) + x.shape, x.dtype), w0)


#: (N, D) pairs already warned about — the replicated-layout fallback
#: warning fires once per distinct shape, not once per round/driver.
_FALLBACK_WARNED: set = set()


def _warn_replicated_fallback(n: int, d: int) -> None:
    """One-time warning when the all-client ``(N, ...)`` tensors cannot
    shard evenly over the mesh and silently fall back to replication.

    The per-round cohort (K clients) still shards — that divisibility
    is checked with a hard error — but the big pre-stacked batch/eval
    tensors land replicated on every mesh device, so memory does NOT
    scale down with D and benchmarks must not attribute the run to a
    fully sharded layout (run history records ``sharded: 0.0``)."""
    if (n, d) in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add((n, d))
    warnings.warn(
        f"mesh layout fallback: num_devices={n} is not divisible by "
        f"mesh_devices={d}; the all-client stacked tensors are "
        f"REPLICATED on every mesh device (per-round cohorts still "
        f"shard). Memory will not scale with the mesh; run history "
        f"records sharded=0.0 for this run.", stacklevel=3)


class RoundEngine:
    """Generic jitted interpreter of one :class:`AlgorithmSpec`.

    One instance is built per ``FederatedTrainer`` (it bakes in loss_fn,
    the spec, learning rate and epoch count); jit caching is keyed by
    the stacked batch shapes, which the data layer's power-of-two
    bucketing bounds.

    The round program signature is uniform across algorithms::

        round(w0, aux, phase_a, batches, valid, decay)
            -> (new_params, new_aux)

    - ``aux``: dict of this spec's persistent round state (see
      ``strategies.runtime_state_fields``) — ``g_prev``, ``c_server``,
      ``controls`` (K-selected stack), ``center``, ``opt``.  Donated on
      accelerator backends; ``w0`` is NOT donated (on round 1 it is the
      caller's params buffer, which examples and benchmarks reuse).
    - ``phase_a``: ``(batches, valid)`` stack for a separate
      gradient-gather selection, or ``None`` when the solve selection
      serves both phases (one shared gradient pass — full
      participation) or no fresh gather is needed.
    - ``decay``: traced scalar from ``spec.decay`` (1.0 when undeclared),
      so one compiled executable serves a decay schedule at a given
      stacked shape.

    ``round_body`` is the same function un-jitted, for callers that
    embed it in a larger traced program (the scanned driver).
    """

    def __init__(self, loss_fn: Callable, cfg: FederatedConfig,
                 spec: Optional[AlgorithmSpec] = None,
                 num_devices: Optional[int] = None,
                 mesh=_MESH_FROM_CFG):
        """Build (and jit) the round programs for one algorithm spec.

        ``loss_fn(params, batch) -> scalar`` (jit-traceable);
        ``num_devices``: total client count N, required by specs with
        control variates; ``mesh``: an explicit client-axis mesh, or
        ``None`` to force the single-device program — by default the
        mesh is derived from ``cfg.mesh_devices`` (core/sharding.py).
        """
        self.cfg = cfg
        self.spec = spec if spec is not None else algorithm_spec(
            cfg.algorithm)
        self.num_devices = num_devices
        # mesh over the stacked client axis (core/sharding.py): derived
        # from cfg.mesh_devices unless the caller passes one (or None to
        # force the single-device program).  With a mesh, the round body
        # runs under shard_map and aggregation becomes psum/pmean
        # collectives; without one, the programs below are structurally
        # the exact pre-mesh build (bit-identical numerics).
        self.mesh = sharding.mesh_for(cfg) if mesh is _MESH_FROM_CFG \
            else mesh
        # client→server wire codec (core/codecs): the trivial "none"
        # spec is a construction-time branch, so every program below is
        # structurally the exact pre-codec build (bit-identical).
        # Under a mesh the fused decode+aggregate becomes a per-shard
        # partial masked SUM followed by a psum of partials and counts
        # (see codec_agg below), so the sharded aggregate matches the
        # single-launch cohort reduction to float-association order.
        self._codec = codecs.codec_spec(cfg.codec)
        self._codec_trivial = codecs.is_trivial(self._codec)
        self._solver = make_batched_solver(
            loss_fn, learning_rate=cfg.learning_rate,
            num_epochs=cfg.local_epochs, solver=cfg.local_solver)
        self._solver_env = make_batched_solver(
            loss_fn, learning_rate=cfg.learning_rate,
            num_epochs=cfg.local_epochs, with_cutoff=True,
            solver=cfg.local_solver)
        self._grads = make_batched_grad_fn(loss_fn)
        self._server_opt = make_server_opt(self.spec, cfg)
        self.round_body = self._make_round_body()
        self.round = jax.jit(self.round_body,
                             donate_argnums=_donate_argnums((1,)))
        # Scenario-aware variant: same generic spec interpretation with
        # three extra traced inputs — an `active` (K,) solve
        # participation mask, a `work` (K,) fraction, and an
        # `active_a` availability mask over the gradient-gather
        # selection — and a telemetry dict output.  A separate program
        # so the ideal environment keeps the exact pre-scenario round
        # (bit-identical numerics, no extra ops).
        self.round_body_env = self._make_round_body(with_env=True)
        self.round_env = jax.jit(self.round_body_env,
                                 donate_argnums=_donate_argnums((1,)))

    def _make_round_body(self, with_env: bool = False) -> Callable:
        spec, cfg = self.spec, self.cfg
        mu = cfg.mu if spec.use_mu else 0.0
        opt = self._server_opt
        if spec.control_update is not None and self.num_devices is None:
            raise ValueError(
                f"spec {spec.name!r} updates control variates; "
                f"RoundEngine needs num_devices")
        n_dev = float(self.num_devices or 0)
        # Under a mesh the body below runs PER SHARD inside shard_map:
        # stacked leaves hold K/shards clients, cross-client reductions
        # go through tree_psum/tree_pmean over `axis` (one name on the
        # flat 1-D mesh, the (edge, device) tuple on the aggregation
        # tree — reduced leaf-to-edge, then edge-to-server), and
        # trace-static global counts are local_count * shards.
        # axis=None (no mesh) keeps every expression exactly pre-mesh.
        mesh = self.mesh
        axis = sharding.mesh_axes(mesh)
        shards = sharding.num_shards(mesh)
        codec, codec_trivial = self._codec, self._codec_trivial
        interp = jax.default_backend() == "cpu"

        def codec_agg(w0, params_stack, aux, new, active):
            """Wire-protocol aggregate: per-client pseudo-gradient
            deltas on the flat-packed ``(K, rows, 128)`` layout, encoded
            by the codec spec (consuming/refreshing the cohort's error-
            feedback slabs carried in ``aux["ef"]``), reduced by the
            fused dequantize+masked-mean kernel, server-decoded."""
            fspec = flat_spec(w0)
            kk = jax.tree_util.tree_leaves(params_stack)[0].shape[0]
            deltas = (pack_broadcast(fspec, w0, kk)
                      - pack_stacked(fspec, params_stack, kk)
                      ).reshape(kk, fspec.rows, LANES)
            key = aux["codec_key"]
            efs = aux.get("ef")
            # cohort slots seed per-client encode draws: under a mesh
            # each shard offsets its local arange by its LINEAR shard
            # index * K/D (row-major over the tree mesh's axes) so the
            # sharded program draws exactly the unsharded slots
            idx0 = (sharding.linear_shard_index(axis) * kk
                    if axis is not None else 0)
            vals, scales, ef_new = codecs.encode_stacked(
                codec, cfg, key, deltas, efs, idx0=idx0)
            mask = (active.astype(jnp.float32) if active is not None
                    else jnp.ones((kk,), jnp.float32))
            if axis is not None:
                # per-shard partial masked SUM, then one psum of the
                # dequantized partials + contributing counts over the
                # mesh axis, divided exactly once — the sharded half of
                # the fused aggregate (kernels/codec.py)
                part = codec_aggregate_partial(vals, scales, mask,
                                               interpret=interp)
                num = sharding.tree_psum(part, axis)
                cnt = sharding.tree_psum(mask.sum(), axis)
                agg = num / jnp.maximum(cnt, 1.0)
            else:
                agg = codec_aggregate(vals, scales, mask,
                                      interpret=interp)
                cnt = mask.sum()
            # post stages run replicated per shard off the shared round
            # key, so every shard applies the identical transform
            agg = codecs.decode_aggregate(codec, cfg, key, agg, cnt)
            if ef_new is not None:
                if active is not None:
                    # offline clients never transmitted: their error
                    # accumulator is untouched this round
                    ef_new = jnp.where(active.reshape(-1, 1, 1) > 0,
                                       ef_new, efs)
                new["ef"] = ef_new
            return pt.sub(w0, unpack(fspec, agg))

        def round_core(w0, aux, phase_a, batches, valid, decay,
                       active, work, active_a):
            g_global = g_local = None
            grad_ok = avail_n = None
            with jax.named_scope("phase_a"):
                if spec.grad_source == "fresh":
                    if with_env:
                        # offline devices serve no gradient either: g_t is
                        # the masked mean over the AVAILABLE gather
                        # selection; with none available there is no
                        # correction to broadcast (grad_ok zeros it below)
                        zeros = pt.zeros_like(w0)
                        avail_n = active_a.sum()
                        if axis is not None:
                            avail_n = sharding.tree_psum(avail_n, axis)
                        grad_ok = (avail_n > 0).astype(jnp.float32)
                    if phase_a is None:
                        # shared selection: one gradient pass serves the
                        # gather AND the per-device corrections
                        g_local = self._grads(w0, batches, valid)
                        g_global = (server.aggregate_stacked_masked(
                            g_local, active_a, zeros, axis) if with_env
                            else server.aggregate_stacked(g_local, axis))
                    else:
                        ga = self._grads(w0, phase_a[0], phase_a[1])
                        g_global = (server.aggregate_stacked_masked(
                            ga, active_a, zeros, axis) if with_env
                            else server.aggregate_stacked(ga, axis))
                        if spec.local_grad:
                            g_local = self._grads(w0, batches, valid)
                elif spec.grad_source == "stale":
                    g_global = aux["g_prev"]
                    g_local = self._grads(w0, batches, valid)

            with jax.named_scope("correction"):
                if spec.correction is not None:
                    corr = spec.correction(CorrCtx(
                        w0=w0, g_global=g_global, g_local=g_local,
                        c_server=aux.get("c_server"),
                        c_local=aux.get("controls"),
                        center=aux.get("center"), mu=mu, decay=decay))
                    if grad_ok is not None:
                        # no reachable gradient device -> no broadcast ->
                        # the round runs uncorrected (fedavg/fedprox step)
                        corr = jax.tree_util.tree_map(
                            lambda c: c * grad_ok, corr)
                else:
                    corr = _stack_zeros(w0, valid.shape[0])
            with jax.named_scope("local_solve"):
                nsteps = cfg.local_epochs * valid.sum(axis=1)       # (K,)
                if with_env:
                    # devices stop after ceil(work * total) of their valid
                    # steps — the mask keeps shapes trace-static
                    nsteps = jnp.minimum(jnp.ceil(work * nsteps), nsteps)
                    res = self._solver_env(w0, corr, mu, batches, valid,
                                           nsteps)
                else:
                    res = self._solver(w0, corr, mu, batches, valid)
            with jax.named_scope("aggregate"):
                new = dict(aux)
                if codec_trivial:
                    w_agg = (server.aggregate_stacked_masked(
                        res.params, active, w0, axis) if with_env
                        else server.aggregate_stacked(res.params, axis))
                else:
                    w_agg = codec_agg(w0, res.params, aux, new,
                                      active if with_env else None)
                if spec.updates_g_prev:
                    new["g_prev"] = (
                        server.aggregate_stacked_masked(
                            g_local, active, aux["g_prev"], axis)
                        if with_env
                        else server.aggregate_stacked(g_local, axis))
                if spec.control_update is not None:
                    c_new = spec.control_update(ControlCtx(
                        c_local=aux["controls"], c_server=aux["c_server"],
                        w0=w0, w_new=res.params,
                        inv_steps=1.0 / (jnp.maximum(nsteps, 1.0)
                                         * cfg.learning_rate)))
                    if with_env:
                        # only devices whose update reached the server
                        # refresh their control / feed the server control
                        keep = lambda cn, co: jax.tree_util.tree_map(
                            lambda n, o: jnp.where(
                                active.reshape(active.shape
                                               + (1,) * (n.ndim - 1)) > 0,
                                n, o), cn, co)
                        c_new = keep(c_new, aux["controls"])
                        delta_sum = jax.tree_util.tree_map(
                            lambda n, o: (n - o).sum(axis=0),
                            c_new, aux["controls"])
                        if axis is not None:
                            delta_sum = jax.tree_util.tree_map(
                                lambda d: sharding.tree_psum(d, axis),
                                delta_sum)
                        new["c_server"] = jax.tree_util.tree_map(
                            lambda cs, d: cs + d / n_dev,
                            aux["c_server"], delta_sum)
                    else:
                        delta = server.aggregate_stacked(
                            pt.sub(c_new, aux["controls"]),
                            axis)                             # (1/K) sum_k
                        k = jnp.float32(valid.shape[0] * shards)
                        new["c_server"] = jax.tree_util.tree_map(
                            lambda cs, d: cs + d * (k / n_dev),
                            aux["c_server"], delta)
                    new["controls"] = c_new
            with jax.named_scope("server_step"):
                w_out, opt_state = server.server_step(
                    w0, w_agg, opt, aux.get("opt"))
                if opt is not None:
                    new["opt"] = opt_state
                if spec.center_update is not None:
                    new["center"] = spec.center_update(
                        aux["center"], w_out, cfg)
            if with_env:
                k = jnp.float32(valid.shape[0] * shards)
                eff = active.sum()
                if axis is not None:
                    eff = sharding.tree_psum(eff, axis)
                # effective_a: devices that actually served the fresh
                # gradient gather (0 for stale/gradient-free specs) —
                # the honest downlink/uplink count for byte telemetry
                stats = {"intended_k": k, "effective_k": eff,
                         "dropped": k - eff,
                         "effective_a": (avail_n if avail_n is not None
                                         else jnp.float32(0.0))}
                return w_out, new, stats
            return w_out, new

        if mesh is not None:
            return self._shard_wrap(round_core, with_env)
        if with_env:
            return round_core
        return lambda w0, aux, phase_a, batches, valid, decay: \
            round_core(w0, aux, phase_a, batches, valid, decay,
                       None, None, None)

    def _shard_wrap(self, round_core: Callable,
                    with_env: bool) -> Callable:
        """Wrap ``round_core`` in a ``shard_map`` over the client axis.

        The wrapper is applied at trace time (per jit specialization),
        so the in/out specs can follow the actual argument structure:
        K-stacked tensors (batches, valid, per-client ``controls``,
        phase-A stacks, env masks) shard on their leading axis; global
        state (``w0``, ``g_prev``, ``c_server``, ``center``, opt state,
        ``decay``) and every output the server consumes replicate.
        Inside, cross-client reductions are psum/pmean collectives (see
        ``round_core``), so the whole round remains one SPMD program.
        """
        mesh = self.mesh
        dev, rep = sharding.stacked_spec(mesh), sharding.replicated_spec()
        manual = sharding.axis_name_tuple(sharding.mesh_axes(mesh))

        def wrapped(w0, aux, phase_a, batches, valid, decay,
                    active=None, work=None, active_a=None):
            sharding.check_divisible(valid.shape[0], mesh,
                                     "stacked selection size")
            # per-client stacked state shards with the clients it
            # belongs to: SCAFFOLD controls and codec error-feedback
            # slabs; everything else (w0, g_prev, c_server, opt state,
            # the shared codec round key) replicates
            aux_spec = {f: (dev if f in ("controls", "ef") else rep)
                        for f in aux}
            phase_spec = None if phase_a is None else (dev, dev)
            env = (active, work, active_a)
            env_specs = tuple(None if x is None else dev for x in env)
            in_specs = (rep, aux_spec, phase_spec, dev, dev,
                        rep) + env_specs
            out_specs: Tuple = (rep, aux_spec, rep) if with_env \
                else (rep, aux_spec)
            body = round_core if with_env else (
                lambda w0_, aux_, pa_, b_, v_, d_:
                round_core(w0_, aux_, pa_, b_, v_, d_,
                           None, None, None))
            if not with_env:
                in_specs, env = in_specs[:6], ()
            f = jax.shard_map(
                body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                axis_names=set(manual), check_vma=False)
            return f(w0, aux, phase_a, batches, valid,
                     jnp.asarray(decay, jnp.float32), *env)

        if with_env:
            return wrapped
        return lambda w0, aux, phase_a, batches, valid, decay: \
            wrapped(w0, aux, phase_a, batches, valid, decay)


def _stack_chunk(stacks, nb: int):
    """Stack a chunk's host cohorts ``(K, nb_r, ...)`` and masks into
    one ``(R, K, nb, ...)`` host array per leaf at the chunk's shared
    bucketed batch count ``nb``: batch steps cycle (the extra steps ride
    ``valid=0`` masked identity updates), the valid mask extends with
    zeros — so the padded trajectory is exactly the unpadded one and
    chunk shapes stay uniform for one scan trace."""
    def put(*xs):
        out = np.empty((len(xs), xs[0].shape[0], nb) + xs[0].shape[2:],
                       xs[0].dtype)
        for r, x in enumerate(xs):
            out[r] = x[:, np.arange(nb) % x.shape[1]]
        return out

    b = jax.tree_util.tree_map(put, *[s[0] for s in stacks])
    v = np.zeros((len(stacks), stacks[0][1].shape[0], nb), np.float32)
    for r, (_, vr) in enumerate(stacks):
        v[r, :, :vr.shape[1]] = vr
    return b, v


def _make_stacked_eval(loss_fn: Callable) -> Callable:
    """On-device global loss over the all-device stacked eval tensors.

    Mirrors ``FederatedTrainer.global_loss`` exactly: per device the mean
    batch loss over its *valid* (own) batches, then the p_k-weighted mean
    over devices — but as one traced expression usable inside the scanned
    driver's ``lax.cond``.  ``eval_loss(p, eval_data)`` takes the
    ``(batches, valid, weights)`` stacks as an argument: closed over,
    they would be baked into the program as constants."""

    def eval_loss(p, eval_data):
        eval_batches, eval_valid, eval_weights = eval_data

        def per_device(b, v):
            def accum(acc, xs):
                batch, vi = xs
                return acc + loss_fn(p, batch) * vi, None
            s, _ = jax.lax.scan(accum, jnp.float32(0.0), (b, v))
            return s / jnp.maximum(v.sum(), 1.0)

        losses = jax.vmap(per_device)(eval_batches, eval_valid)
        return ((eval_weights * losses).sum()
                / jnp.maximum(eval_weights.sum(), 1e-12))

    return eval_loss


class ScannedDriver:
    """Scan-fused multi-round driver (see module docstring).

    One instance per (loss_fn, dataset, cfg); it pre-stacks ALL devices'
    train and eval batch tensors once, builds two jitted chunk programs
    (internally-sampled and injected-selection), and exposes ``run`` with
    the same ``(history, final_params)`` contract as
    ``FederatedTrainer.run``.
    """

    def __init__(self, loss_fn: Callable, dataset, cfg: FederatedConfig,
                 engine: Optional[RoundEngine] = None):
        """Pre-stack the dataset and build the jitted chunk programs.

        ``dataset`` follows the ``FederatedTrainer`` protocol;
        ``engine`` shares an already-built :class:`RoundEngine` (and
        its jit caches + mesh) — by default one is built from ``cfg``.
        Raises for spec/config combinations the scanned scatter cannot
        express (control variates with replacement) and for selection
        sizes that cannot shard evenly over a requested mesh.
        """
        self.spec = algorithm_spec(cfg.algorithm)
        if self.spec.control_update is not None and \
                cfg.sample_with_replacement:
            raise ValueError(
                f"{cfg.algorithm} + sample_with_replacement requires "
                f"sequential per-duplicate control updates; use the "
                f"python driver")
        self.cfg = cfg
        self.dataset = dataset
        self.engine = engine if engine is not None else RoundEngine(
            loss_fn, cfg, spec=self.spec,
            num_devices=dataset.num_devices)
        self.num_devices = dataset.num_devices
        #: client-axis mesh (core/sharding.py), owned by the engine so
        #: both per-round and scanned programs share one layout choice
        self.mesh = self.engine.mesh
        if self.mesh is not None:
            if self.spec.num_selections == 0:
                sharding.check_divisible(
                    self.num_devices, self.mesh,
                    "num_devices (full-participation spec)")
            else:
                k = (cfg.devices_per_round if cfg.sample_with_replacement
                     else min(cfg.devices_per_round, self.num_devices))
                sharding.check_divisible(k, self.mesh,
                                         "devices_per_round")
        # federated-environment scenario: realized on device inside the
        # scan body (availability/latency/dropout uniforms drawn from
        # the carried PRNG key).  The trivial "ideal" spec keeps the
        # pre-scenario chunk program untouched — no env draws, no mask
        # ops, bit-identical numerics.
        self.scn = scenario_spec(cfg.scenario)
        self.scn_trivial = is_trivial(self.scn)
        self._env_channels = env_channels(self.scn)
        #: population-scale data plan (module docstring): streaming
        #: materializes selected cohorts only, per chunk, host-side.
        #: Full-participation specs touch every client every round —
        #: inherently materializing — so they run the stacked plan on
        #: either source kind (a streaming source's host stacks are
        #: stacked on the host and moved once; small N only).
        self.streaming = (resolve_streaming(
            getattr(cfg, "client_source", "auto"), dataset)
            and self.spec.num_selections > 0)
        #: whether the all-client tensors actually shard over the mesh
        #: (False on the N % D != 0 replicated fallback) — recorded in
        #: run-history telemetry so benchmarks can't misattribute runs.
        #: Streaming never builds all-client tensors; its per-round
        #: cohorts always shard (K % shards checked above), so a
        #: streaming mesh run records 1.0.
        self._layout_sharded = self.mesh is not None
        if self.streaming:
            self.batches_all = self.valid_all = None
        else:
            self.batches_all, self.valid_all = stack_device_batches(
                dataset, np.arange(self.num_devices))
        eb, ev, ew = stack_eval_batches(dataset)
        if self.mesh is not None:
            # lay the big all-client tensors out along the mesh up
            # front (leading-axis NamedSharding when N divides evenly,
            # replicated otherwise) so the chunk program starts from
            # the layout the shard-mapped round body wants instead of
            # re-sharding per round
            d = sharding.num_shards(self.mesh)
            if not self.streaming:
                if self.num_devices % d != 0:
                    self._layout_sharded = False
                    _warn_replicated_fallback(self.num_devices, d)
                self.batches_all = sharding.shard_stacked(
                    self.batches_all, self.mesh)
                self.valid_all = sharding.shard_stacked(self.valid_all,
                                                        self.mesh)
            eb = sharding.shard_stacked(eb, self.mesh)
            ev = sharding.shard_stacked(ev, self.mesh)
        self._eval_loss = _make_stacked_eval(loss_fn)
        #: the chunk programs' data arguments: the eval stacks, plus the
        #: all-client train stacks on the stacked plan.  Arguments, not
        #: closures — closed-over arrays would be compiled in as
        #: constants (gigabytes at FEMNIST's N=200).
        self._data = {"eval": (eb, ev, ew)}
        if not self.streaming:
            self._data.update(batches=self.batches_all,
                              valid=self.valid_all)
        # streaming sources publish weights=None (uniform sampling with
        # no O(N) weight vector); dense datasets keep their size-
        # proportional marginals
        w = dataset.weights
        self.probs = (jnp.asarray(w, jnp.float32)
                      if cfg.weighted_sampling and w is not None
                      else None)
        # selection sizing, shared by the chunk program and the
        # telemetry in run() (one definition, no drift)
        self.k_sel = (cfg.devices_per_round
                      if cfg.sample_with_replacement
                      else min(cfg.devices_per_round, self.num_devices))
        self.k_intended = (self.num_devices
                           if self.spec.num_selections == 0
                           else self.k_sel)
        self.comm_per_round = self.spec.comm_per_round
        self._state_fields = runtime_state_fields(self.spec, cfg)
        # jit is lazy: each traces once per distinct chunk length (and,
        # for the streaming program, per chunk-wide batch bucket).
        if self.streaming:
            self._chunk_stream = jax.jit(self._make_stream_chunk())
        else:
            self._chunk_sampled = jax.jit(self._make_chunk(inject=False))
            self._chunk_injected = jax.jit(self._make_chunk(inject=True))

    # -- scan program -----------------------------------------------------

    def _make_chunk(self, inject: bool) -> Callable:
        """Build ``chunk(carry, xs, data) -> (carry, losses)``: a
        lax.scan whose body is one whole federated round — the engine's
        generic ``round_body`` plus on-device selection gather/scatter
        from the all-client stacks in ``data`` (``self._data``).
        ``inject=True`` reads each round's selection from ``xs["sel"]``
        (tests / A-B comparisons); ``inject=False`` samples on device
        from the carried PRNG key."""
        cfg, spec = self.cfg, self.spec
        scn, trivial = self.scn, self.scn_trivial
        channels = self._env_channels
        codec = self.engine._codec
        codec_trivial = self.engine._codec_trivial
        round_body = (self.engine.round_body if trivial
                      else self.engine.round_body_env)
        n = self.num_devices
        k_sel = self.k_sel
        probs = self.probs
        has_controls = "controls" in self._state_fields
        aux_fields = tuple(f for f in self._state_fields
                           if f != "controls")
        tmap = jax.tree_util.tree_map

        def sample(key):
            return server.sample_devices_onchip(
                key, n, k_sel, p=probs,
                replace=cfg.sample_with_replacement)

        def body(data, carry, xs):
            batches_all, valid_all = data["batches"], data["valid"]

            def gather(sel):
                return tmap(lambda x: x[sel], batches_all), valid_all[sel]

            new = dict(carry)
            if inject:
                s1, s2 = xs["sel"][0], xs["sel"][1]
                env_keys = ()
                if channels:
                    keys = jax.random.split(carry["key"],
                                            1 + len(channels))
                    new["key"], env_keys = keys[0], keys[1:]
            else:
                nkeys = 3 + len(channels)
                keys = jax.random.split(carry["key"], nkeys)
                new["key"], key1, key2 = keys[0], keys[1], keys[2]
                env_keys = keys[3:]
                s1, s2 = sample(key1), sample(key2)
            # phase mapping mirrors the host loop: the first selection
            # feeds the gradient gather; the solve selection is the
            # second only for two-selection specs (and every device for
            # full-participation specs — including their control
            # gather/scatter below).
            sel_solve = s1 if spec.num_selections < 2 else s2
            decay = (spec.decay(cfg, xs["t"].astype(jnp.float32))
                     if spec.decay is not None else 1.0)
            full = spec.num_selections == 0
            with jax.named_scope("gather"):
                if full:
                    b, v = batches_all, valid_all
                    phase_a = None
                else:
                    b, v = gather(sel_solve)
                    phase_a = (gather(s1)
                               if (spec.grad_source == "fresh"
                                   and spec.num_selections == 2) else None)
                aux = {f: carry[f] for f in aux_fields}
                if has_controls:
                    # full participation touches every control: pass the
                    # carried (N, ...) stack straight through, no
                    # gather/scatter copies on the hot path
                    aux["c_server"] = carry["c_server"]
                    aux["controls"] = (carry["controls"] if full else
                                       tmap(lambda x: x[sel_solve],
                                            carry["controls"]))
            if not codec_trivial:
                # same per-round key as the host loop (domain-separated
                # fold of the round index), so lossy codec paths agree
                # across drivers under the ideal scenario
                aux["codec_key"] = codecs.round_key(cfg, xs["t"])
                if codec.error_feedback:
                    # error-feedback slabs ride the carry like SCAFFOLD
                    # controls: gather the cohort's rows, scatter the
                    # refreshed accumulators back after the round
                    aux["ef"] = (carry["ef"] if full
                                 else carry["ef"][sel_solve])
            if trivial:
                params, aux_new = round_body(
                    carry["params"], aux, phase_a, b, v, decay)
            else:
                # realize the environment on device: one per-DEVICE
                # (n,) uniform draw per declared channel (duplicate
                # selections share one outcome), interpreted by the
                # same realize_env the host driver uses (same
                # distribution, this driver's bit stream — see
                # scenarios/spec.py).  Full-participation specs solve
                # on EVERY device, so their selection is all n
                # (sel_solve is an unused k-sized draw there).
                sel_env = jnp.arange(n) if full else sel_solve
                uniforms = {c: jax.random.uniform(ek, (n,))
                            for c, ek in zip(channels, env_keys)}
                t_f = xs["t"].astype(jnp.float32)
                env = realize_env(scn, cfg, n, sel_env, t_f, uniforms)
                # availability gates the gradient-gather phase too —
                # same per-device uniforms, so one on/offline outcome
                # per device per round across both phases
                active_a = None
                if spec.grad_source == "fresh":
                    sel_a = sel_env if phase_a is None else s1
                    active_a = availability_mask(scn, cfg, n, sel_a,
                                                 t_f, uniforms)
                params, aux_new, stats = round_body(
                    carry["params"], aux, phase_a, b, v, decay,
                    env.active, env.work, active_a)
            for f in aux_fields:
                new[f] = aux_new[f]
            if has_controls:
                new["c_server"] = aux_new["c_server"]
                new["controls"] = (aux_new["controls"] if full else
                                   tmap(lambda c, cn:
                                        c.at[sel_solve].set(cn),
                                        carry["controls"],
                                        aux_new["controls"]))
            if not codec_trivial and codec.error_feedback:
                new["ef"] = (aux_new["ef"] if full else
                             carry["ef"].at[sel_solve].set(
                                 aux_new["ef"]))
            new["params"] = params
            with jax.named_scope("eval"):
                loss = jax.lax.cond(
                    xs["do_eval"],
                    lambda p: self._eval_loss(p, data["eval"]),
                    lambda p: jnp.float32(jnp.nan), params)
            if trivial:
                return new, loss
            return new, {"loss": loss,
                         "effective_k": stats["effective_k"],
                         "effective_a": stats["effective_a"]}

        def chunk(carry, xs, data):
            return jax.lax.scan(lambda c, x: body(data, c, x), carry, xs)

        return chunk

    # -- streaming program (population-scale sources) ---------------------

    def _make_stream_chunk(self) -> Callable:
        """Build the streaming ``chunk(carry, xs, data) -> (carry, ys)``.

        Same generic round-body interpretation as ``_make_chunk``, but
        every per-cohort input — batch stacks, per-client state rows,
        realized scenario masks — arrives through ``xs`` (prepared
        host-side by ``_run_streaming``) instead of being gathered
        from O(N) carries and all-client stacks; updated state rows
        leave through the scan outputs for the host to scatter back
        into the sparse stores.  The carry holds ONLY global state
        (params, g_prev, c_server, center, opt) — nothing in the
        compiled program scales with N.
        """
        cfg, spec = self.cfg, self.spec
        trivial = self.scn_trivial
        codec = self.engine._codec
        codec_trivial = self.engine._codec_trivial
        round_body = (self.engine.round_body if trivial
                      else self.engine.round_body_env)
        has_controls = "controls" in self._state_fields
        aux_fields = tuple(f for f in self._state_fields
                           if f != "controls")

        def body(data, carry, xs):
            new = dict(carry)
            decay = (spec.decay(cfg, xs["t"].astype(jnp.float32))
                     if spec.decay is not None else 1.0)
            b, v = xs["b"], xs["v"]
            phase_a = (xs["ba"], xs["va"]) if "ba" in xs else None
            aux = {f: carry[f] for f in aux_fields}
            if has_controls:
                aux["c_server"] = carry["c_server"]
                aux["controls"] = xs["controls"]
            if not codec_trivial:
                aux["codec_key"] = codecs.round_key(cfg, xs["t"])
                if codec.error_feedback:
                    aux["ef"] = xs["ef"]
            if trivial:
                params, aux_new = round_body(
                    carry["params"], aux, phase_a, b, v, decay)
            else:
                params, aux_new, stats = round_body(
                    carry["params"], aux, phase_a, b, v, decay,
                    xs["active"], xs["work"], xs.get("active_a"))
            for f in aux_fields:
                new[f] = aux_new[f]
            ys = {}
            if has_controls:
                new["c_server"] = aux_new["c_server"]
                ys["controls"] = aux_new["controls"]
            if not codec_trivial and codec.error_feedback:
                ys["ef"] = aux_new["ef"]
            new["params"] = params
            with jax.named_scope("eval"):
                ys["loss"] = jax.lax.cond(
                    xs["do_eval"],
                    lambda p: self._eval_loss(p, data["eval"]),
                    lambda p: jnp.float32(jnp.nan), params)
            if not trivial:
                ys["effective_k"] = stats["effective_k"]
                ys["effective_a"] = stats["effective_a"]
            return new, ys

        def chunk(carry, xs, data):
            return jax.lax.scan(lambda c, x: body(data, c, x), carry, xs)

        return chunk

    def _stream_round(self, key, t: int, sel_row):
        """Replicate ONE round of the scan body's key-split schedule
        host-side — the same ``jax.random`` split/sample/uniform ops
        the stacked chunk traces, run eagerly, so selections and
        scenario draws are bit-identical to the stacked scan.

        Returns ``(next_key, row)``: ``row`` carries round ``t``'s two
        phase selections plus (non-trivial scenarios) the realized
        ``active``/``work``/``active_a`` masks — everything is
        cohort-sized; the transient ``(n,)`` uniforms never leave this
        frame.
        """
        cfg, spec, scn = self.cfg, self.spec, self.scn
        n, channels = self.num_devices, self._env_channels
        env_keys = ()
        if sel_row is not None:
            if channels:
                keys = jax.random.split(key, 1 + len(channels))
                key, env_keys = keys[0], keys[1:]
            s1, s2 = np.asarray(sel_row[0]), np.asarray(sel_row[1])
        else:
            keys = jax.random.split(key, 3 + len(channels))
            s1 = np.asarray(server.sample_devices_onchip(
                keys[1], n, self.k_sel, p=self.probs,
                replace=cfg.sample_with_replacement))
            s2 = np.asarray(server.sample_devices_onchip(
                keys[2], n, self.k_sel, p=self.probs,
                replace=cfg.sample_with_replacement))
            key, env_keys = keys[0], keys[3:]
        sel_solve = s1 if spec.num_selections < 2 else s2
        row = {"t": t, "s1": s1, "sel_solve": sel_solve}
        if not self.scn_trivial:
            uniforms = {c: jax.random.uniform(ek, (n,))
                        for c, ek in zip(channels, env_keys)}
            t_f = jnp.float32(t)
            sel_env = jnp.asarray(sel_solve)
            env = realize_env(scn, cfg, n, sel_env, t_f, uniforms)
            row["active"] = np.asarray(env.active)
            row["work"] = np.asarray(env.work)
            if spec.grad_source == "fresh":
                sel_a = (jnp.asarray(s1) if spec.num_selections == 2
                         else sel_env)
                row["active_a"] = np.asarray(availability_mask(
                    scn, cfg, n, sel_a, t_f, uniforms))
        return key, row

    def _put_xs(self, xs: Dict[str, Any]) -> Dict[str, Any]:
        """Move a streaming chunk's ``xs`` to the device, one transfer
        per leaf.  On a mesh the per-round cohort leaves ``(R, K, ...)``
        shard their client axis as the shard-mapped round body takes it
        and ``t`` / ``do_eval`` replicate."""
        if self.mesh is None:
            return jax.device_put(xs)
        cohort = sharding.chunk_stacked_sharding(self.mesh)
        rep = sharding.replicated_sharding(self.mesh)
        return jax.device_put(xs, {k: rep if k in ("t", "do_eval")
                                   else cohort for k in xs})

    def _init_stream_carry(self, params):
        """The streaming carry: params + the spec's GLOBAL state only.
        Per-client state lives host-side in ``SparseClientState``
        stores (returned alongside), so nothing in the carry — or the
        compiled chunk — scales with N."""
        aux0 = init_aux(self.spec, self.cfg, params,
                        self.num_devices, stacked=False)
        controls_store = aux0.pop("controls", None)
        carry = {"params": params}
        carry.update(aux0)
        ef_store = None
        if self.engine._codec.error_feedback:
            ef_store = codecs.init_ef(
                self.engine._codec, flat_spec(params),
                self.num_devices, stacked=False)
        return carry, controls_store, ef_store

    def _run_streaming(self, params, num_rounds: int, eval_every: int,
                       verbose: bool, checkpoint_dir: Optional[str],
                       sel) -> Tuple[Dict[str, List[float]], Any]:
        """Chunked streaming run (see module docstring): host schedule
        replication -> cohort materialization from the shard source ->
        one jitted scan per chunk -> host scatter of state rows."""
        cfg, spec = self.cfg, self.spec
        chunk_rounds = cfg.chunk_rounds if cfg.chunk_rounds > 0 \
            else num_rounds
        t_all = np.arange(num_rounds)
        eval_mask = (t_all % eval_every == 0) | (t_all == num_rounds - 1)
        hist = self._new_hist()
        intended = self.k_intended
        n_elems = sum(int(np.prod(np.asarray(x.shape)))
                      for x in jax.tree_util.tree_leaves(params))
        gather_full = (float(intended)
                       if spec.grad_source == "fresh" else 0.0)
        carry, controls_store, ef_store = self._init_stream_carry(params)
        stateful = controls_store is not None or ef_store is not None
        phase2 = spec.grad_source == "fresh" and spec.num_selections == 2
        key = jax.random.PRNGKey(cfg.seed)
        tmap = jax.tree_util.tree_map
        off = 0
        while off < num_rounds:
            # host schedule: replicate the key stream round by round.
            # Stateful specs (controls / error feedback) truncate the
            # chunk at the first within-chunk cohort repeat so xs state
            # rows are never stale; the repeated round restarts the
            # next chunk from its saved key, losing no draws.
            with jax.profiler.TraceAnnotation("stream.schedule"):
                rows: List[Dict[str, Any]] = []
                seen: set = set()
                while off + len(rows) < min(off + chunk_rounds, num_rounds):
                    t = off + len(rows)
                    key_next, row = self._stream_round(
                        key, t, None if sel is None else sel[t])
                    ids = [int(i) for i in row["sel_solve"]]
                    if stateful and rows and not seen.isdisjoint(ids):
                        break
                    seen.update(ids)
                    rows.append(row)
                    key = key_next
            hi = off + len(rows)
            # materialize ONLY the chunk's cohorts, padded to one
            # chunk-wide bucketed batch count (padding rides valid=0
            # masked identity steps — trajectories are exactly the
            # stacked gather's)
            with jax.profiler.TraceAnnotation("stream.cohorts"):
                stacks = [stack_host_batches(self.dataset, r["sel_solve"])
                          for r in rows]
                stacks_a = ([stack_host_batches(self.dataset, r["s1"])
                             for r in rows] if phase2 else None)
            # the whole chunk's xs is assembled on the host and moved to
            # the device in one transfer per leaf
            with jax.profiler.TraceAnnotation("stream.pad"):
                nb = max(int(s[1].shape[1]) for s in stacks)
                if stacks_a is not None:
                    nb = max(nb, max(int(s[1].shape[1]) for s in stacks_a))
                xs: Dict[str, Any] = {
                    "t": np.asarray([r["t"] for r in rows], np.int32),
                    "do_eval": eval_mask[off:hi]}
                xs["b"], xs["v"] = _stack_chunk(stacks, nb)
                if stacks_a is not None:
                    xs["ba"], xs["va"] = _stack_chunk(stacks_a, nb)
                if controls_store is not None:
                    xs["controls"] = tmap(
                        lambda *x: jnp.stack(x),
                        *[controls_store.gather(r["sel_solve"])
                          for r in rows])
                if ef_store is not None:
                    xs["ef"] = jnp.stack(
                        [ef_store.gather(r["sel_solve"]) for r in rows])
                if not self.scn_trivial:
                    xs["active"] = np.stack([r["active"] for r in rows])
                    xs["work"] = np.stack([r["work"] for r in rows])
                    if spec.grad_source == "fresh":
                        xs["active_a"] = np.stack(
                            [r["active_a"] for r in rows])
                xs = self._put_xs(xs)
            with jax.profiler.TraceAnnotation("stream.dispatch"):
                carry, ys = self._chunk_stream(carry, xs, self._data)
            with jax.profiler.TraceAnnotation("stream.readback"):
                ys_h = jax.device_get(ys)
                # scatter updated state rows back, in round order (later
                # rounds of the chunk never touch earlier rounds' clients —
                # the truncation above guarantees it)
                for i, r in enumerate(rows):
                    if controls_store is not None:
                        controls_store.scatter(
                            r["sel_solve"],
                            tmap(lambda x, i=i: x[i], ys_h["controls"]))
                    if ef_store is not None:
                        ef_store.scatter(r["sel_solve"], ys_h["ef"][i])
                losses = np.asarray(ys_h["loss"])
                if self.scn_trivial:
                    eff = np.full(hi - off, intended, dtype=np.float64)
                    eff_a = np.full(hi - off, gather_full, dtype=np.float64)
                else:
                    eff = np.asarray(ys_h["effective_k"], dtype=np.float64)
                    eff_a = np.asarray(ys_h["effective_a"], dtype=np.float64)
                self._emit_rounds(hist, off, hi, losses, eff, eff_a,
                                  eval_mask, n_elems, verbose)
                if checkpoint_dir is not None:
                    from repro.checkpoint.store import save_checkpoint
                    save_checkpoint(checkpoint_dir,
                                    {"params": carry["params"], "round": hi},
                                    step=hi)
            off = hi
        return hist, carry["params"]

    # -- host-side chunked run --------------------------------------------

    def _new_hist(self) -> Dict[str, List[float]]:
        """The run-history dict both drivers fill (one schema)."""
        hist: Dict[str, List[float]] = {"round": [], "comm_rounds": [],
                                        "loss": [], "intended_k": [],
                                        "effective_k": [], "dropped": [],
                                        "bytes_up": [], "bytes_down": []}
        if self.mesh is not None:
            # layout telemetry: 1.0 when the stacked client tensors
            # shard over the mesh, 0.0 on the replicated N % D fallback
            hist["sharded"] = []
        return hist

    def _emit_rounds(self, hist, off: int, hi: int, losses, eff, eff_a,
                     eval_mask, n_elems: int, verbose: bool) -> None:
        """Append one chunk's realized telemetry + eval points to the
        run history (shared by the stacked and streaming runs)."""
        cfg = self.cfg
        intended = self.k_intended
        for i, t in enumerate(range(off, hi)):
            if self.mesh is not None:
                hist["sharded"].append(
                    1.0 if self._layout_sharded else 0.0)
            hist["intended_k"].append(float(intended))
            hist["effective_k"].append(float(eff[i]))
            hist["dropped"].append(float(intended - eff[i]))
            up, down = codecs.round_bytes(
                self.spec, self.engine._codec, cfg, n_elems,
                float(eff_a[i]), float(eff[i]))
            hist["bytes_up"].append(up)
            hist["bytes_down"].append(down)
            if not eval_mask[t]:
                continue
            hist["round"].append(t + 1)
            hist["comm_rounds"].append((t + 1) * self.comm_per_round)
            hist["loss"].append(float(losses[i]))
            if verbose:
                print(f"[{cfg.algorithm}] round {t + 1:4d} "
                      f"comm {(t + 1) * self.comm_per_round:4d} "
                      f"loss {float(losses[i]):.4f}")

    def _init_carry(self, params) -> Dict[str, Any]:
        """The scan carry: params + PRNG key + the spec's persistent
        state (``init_aux``, stacked layout).  Under a mesh, the
        ``(N, ...)`` control stack is placed leading-axis-sharded so
        the carry keeps the round body's layout across chunks."""
        carry = {"params": params,
                 "key": jax.random.PRNGKey(self.cfg.seed)}
        carry.update(init_aux(self.spec, self.cfg, params,
                              self.num_devices, stacked=True))
        if self.engine._codec.error_feedback:
            carry["ef"] = codecs.init_ef(
                self.engine._codec, flat_spec(params),
                self.num_devices, stacked=True)
        if self.mesh is not None:
            for f in ("controls", "ef"):
                if f in carry:
                    carry[f] = sharding.shard_stacked(carry[f],
                                                      self.mesh)
        return carry

    def run(self, params, num_rounds: int, eval_every: int = 1,
            verbose: bool = False, checkpoint_dir: Optional[str] = None,
            selections=None) -> Tuple[Dict[str, List[float]], Any]:
        """Chunked scanned run; same contract as ``FederatedTrainer.run``.

        ``selections``: optional int array ``(num_rounds, 2, K)`` (or
        ``(num_rounds, K)``, broadcast to both phases) overriding the
        on-device sampler — used to make the two drivers' sampling
        comparable in parity tests.
        """
        cfg = self.cfg
        sel = None
        if selections is not None:
            sel = jnp.asarray(np.asarray(selections), jnp.int32)
            if sel.ndim == 2:
                sel = jnp.stack([sel, sel], axis=1)
            if sel.shape[0] < num_rounds:
                raise ValueError(
                    f"selections covers {sel.shape[0]} rounds "
                    f"< num_rounds={num_rounds}")
        if self.streaming:
            return self._run_streaming(params, num_rounds, eval_every,
                                       verbose, checkpoint_dir, sel)
        chunk_rounds = cfg.chunk_rounds if cfg.chunk_rounds > 0 \
            else num_rounds
        t_all = np.arange(num_rounds)
        eval_mask = (t_all % eval_every == 0) | (t_all == num_rounds - 1)
        hist = self._new_hist()
        intended = self.k_intended
        # wire bytes per round (codecs.round_bytes): reconstructed
        # host-side from the scan's realized participation telemetry
        n_elems = sum(int(np.prod(np.asarray(x.shape)))
                      for x in jax.tree_util.tree_leaves(params))
        gather_full = (float(intended)
                       if self.spec.grad_source == "fresh" else 0.0)
        chunk_fn = (self._chunk_injected if sel is not None
                    else self._chunk_sampled)
        carry = self._init_carry(params)
        for off in range(0, num_rounds, chunk_rounds):
            hi = min(off + chunk_rounds, num_rounds)
            with jax.profiler.TraceAnnotation("scan.dispatch"):
                xs = {"t": jnp.asarray(t_all[off:hi], jnp.int32),
                      "do_eval": jnp.asarray(eval_mask[off:hi])}
                if sel is not None:
                    xs["sel"] = sel[off:hi]
                carry, ys = chunk_fn(carry, xs, self._data)
            with jax.profiler.TraceAnnotation("scan.readback"):
                # chunk boundary: the only host round-trip
                if self.scn_trivial:
                    losses = np.asarray(jax.device_get(ys))
                    eff = np.full(hi - off, intended, dtype=np.float64)
                    eff_a = np.full(hi - off, gather_full, dtype=np.float64)
                else:
                    ys = jax.device_get(ys)
                    losses = np.asarray(ys["loss"])
                    eff = np.asarray(ys["effective_k"], dtype=np.float64)
                    eff_a = np.asarray(ys["effective_a"], dtype=np.float64)
                self._emit_rounds(hist, off, hi, losses, eff, eff_a,
                                  eval_mask, n_elems, verbose)
                if checkpoint_dir is not None:
                    from repro.checkpoint.store import save_checkpoint
                    save_checkpoint(checkpoint_dir,
                                    {"params": carry["params"], "round": hi},
                                    step=hi)
        return hist, carry["params"]


def make_scanned_run(loss_fn: Callable, dataset, cfg: FederatedConfig,
                     engine: Optional[RoundEngine] = None) -> ScannedDriver:
    """Factory for the scan-fused multi-round driver.

    Returns a :class:`ScannedDriver` whose ``run(params, num_rounds, ...)``
    executes rounds as chunked ``lax.scan`` programs with on-device
    sampling and in-scan eval.  ``engine`` lets a trainer share its
    already-built :class:`RoundEngine` (and so its jit caches)."""
    return ScannedDriver(loss_fn, dataset, cfg, engine=engine)
