"""FedBuff-style asynchronous buffered round driver (the fourth driver).

The three synchronous paths (host loop, batched ``RoundEngine``,
``ScannedDriver``) all run a *round barrier*: the server waits for every
selected device, then steps.  ``BufferedDriver``
(``FederatedConfig.round_driver="buffered"``) removes the barrier and
reinterprets the scenario layer's latency process as an **event queue**
(Nguyen et al. 2022, FedBuff):

- At any moment ``K = devices_per_round`` clients are in flight, each
  solving the spec's local subproblem from the server params *as they
  were at its launch* (a possibly **stale anchor**).
- A finished client's update lands in a double-buffered, jitted staging
  area as a pseudo-gradient ``anchor - w_local``.  Whenever
  ``M = buffer_size`` updates have been buffered the server **commits**:
  the buffer is reduced with :func:`repro.core.server.aggregate_buffered`
  under :func:`repro.core.server.staleness_weight` mixing weights and
  applied through the shared :func:`repro.core.server.server_step`
  (server optimizers included), then freed clients relaunch from the new
  params.
- The same scenario specs drive the simulation, via
  :func:`repro.core.scenarios.realize_event_env`: the latency
  inverse-CDF *is* the arrival-time process (no deadline — a straggler
  is merely stale), availability/dropout mean the update is never
  delivered, and ``max_staleness`` plays the deadline's role at the
  server.

Algorithm generality
--------------------
The driver is a generic :class:`~repro.core.strategies.AlgorithmSpec`
interpreter like the synchronous paths — no per-algorithm branches.
The spec phases map onto the event queue as follows:

- **FedDANE's two-phase gather** (``grad_source="fresh"``) runs at
  *cohort launch* against the launch anchor: a fresh gather selection is
  drawn, availability-masked, and the aggregated gradient enters the
  cohort's correction.  Under staleness the gathered ``g`` is exactly as
  stale as the anchor it was taken at — the experiment the paper could
  not run.
- **Stale-gradient pipelining** (``grad_source="stale"``) reads the
  ``g_prev`` carried at launch time; commits refresh it with the
  staleness-weighted mean of the committed clients' local gradients.
- **Control variates** (scaffold) keep *sparse* per-client state: a
  dict holding only clients that have ever committed (zeros otherwise).
  Corrections read the launch-time snapshot; commits write back in
  arrival order (last-writer-wins under duplicate completions), and the
  server control absorbs ``sum(deltas)/N`` per commit — the synchronous
  rule, applied per commit.  Under ``sample_with_replacement`` a client
  may appear twice in ONE cohort: those positions are solved in
  sequential occurrence layers (``_solve_duplicates``), each reading
  the control the previous duplicate refreshed — the python driver's
  per-duplicate semantics, so degenerate parity includes replacement
  sampling.
- **Prox centers** (sdane) and time-dependent ``decay`` advance on the
  server's commit counter, the async analogue of the round index.

Mesh sharding (``mesh_devices > 1``) composes via masked padding:
cohort solves and commit buffers are padded up to the next multiple of
the mesh size — padded solve rows carry all-zero valid masks (identity
steps) and padded commit rows carry weight 0 (dropped by the psum-ed
weighted mean) — so every launch and every commit runs as ONE
shard-mapped SPMD program regardless of the varying cohort sizes.

Degenerate-parity contract (pinned by tests/test_async_engine.py): with
``buffer_size == K``, a latency-free scenario (cohorts stay aligned) and
fresh anchors (staleness 0, where both weight families give 1.0), each
commit IS a synchronous round — the trajectory matches the python
driver at atol 1e-5 for every registered algorithm.

Determinism: one host ``np.random.default_rng(cfg.seed)`` stream drives
sampling and environment draws in a fixed per-cohort order (selections
first, then one ``(N,)`` uniform per scenario channel), and simultaneous
arrivals resolve by launch sequence number — a fixed seed reproduces
the entire event stream, commit for commit (see docs/determinism.md).
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
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
from repro.core.scenarios import (env_channels, is_trivial,
                                  realize_event_env, scenario_spec)
from repro.core.strategies import (ControlCtx, CorrCtx, algorithm_spec,
                                   init_aux, make_server_opt)
from repro.data.batching import stack_device_batches
from repro.kernels.flatpack import (LANES, flat_spec, pack,
                                    pack_broadcast, pack_stacked, unpack)

#: Safety factor on the event budget: a run may process at most
#: ``HORIZON_FACTOR * num_rounds * max(K, M)`` arrivals before the
#: driver gives up and returns the partial history (the "empty buffer at
#: the horizon" guarantee — a config whose updates are all dropped or
#: all beyond ``max_staleness`` terminates instead of spinning).
HORIZON_FACTOR = 64


@dataclass(order=True)
class _Flight(object):
    """One in-flight client solve, ordered by (completion time, launch
    sequence) — the deterministic event-queue ordering."""

    done: float
    seq: int
    client: int = field(compare=False)
    anchor_version: int = field(compare=False)
    launch: float = field(compare=False)
    delivered: bool = field(compare=False)
    delta: Any = field(compare=False)          # anchor - w_local (pytree)
    g_local: Any = field(compare=False, default=None)
    c_new: Any = field(compare=False, default=None)
    c_delta: Any = field(compare=False, default=None)
    arrival: float = field(compare=False, default=0.0)


class _CommitBuffer(object):
    """Double-buffered, device-resident commit staging area.

    Arrivals are staged into the active ``(M, ...)``-stacked buffer with
    ONE jitted dynamic-index scatter per update; at commit the full
    buffer is handed to the jitted aggregate+step program and the other
    buffer becomes active, so staging the next commit's arrivals never
    touches the tensors the reduction is consuming.
    """

    def __init__(self, params, m: int):
        """Allocate both ``(m, ...)`` staging buffers shaped like
        ``params`` and compile the scatter."""
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros((m,) + x.shape, x.dtype), params)
        self._bufs = [zeros, jax.tree_util.tree_map(jnp.copy, zeros)]
        self._active = 0
        self._scatter = jax.jit(
            lambda buf, i, d: jax.tree_util.tree_map(
                lambda b, x: b.at[i].set(x), buf, d))

    def stage(self, slot: int, delta) -> None:
        """Write ``delta`` into row ``slot`` of the active buffer."""
        self._bufs[self._active] = self._scatter(
            self._bufs[self._active], jnp.int32(slot), delta)

    def swap(self):
        """Return the (full) active buffer and flip to the other one."""
        full = self._bufs[self._active]
        self._active = 1 - self._active
        return full


class BufferedDriver(object):
    """Asynchronous buffered multi-round driver (module docstring).

    Construction mirrors :class:`~repro.core.engine.ScannedDriver`:
    ``BufferedDriver(loss_fn, dataset, cfg)``; ``run()`` has the
    trainer-compatible signature and returns ``(history, params)`` where
    ``num_rounds`` counts server *commits*.  The history carries the
    synchronous telemetry fields plus per-commit ``staleness_mean`` /
    ``staleness_max`` / ``buffer_wait`` / ``anchor_age`` / ``sim_time``.
    """

    def __init__(self, loss_fn: Callable, dataset, cfg: FederatedConfig,
                 engine=None):
        """Resolve specs and compile the cohort solve / gather / commit
        programs.  ``engine`` is accepted (and ignored) for signature
        compatibility with the other drivers — the buffered path always
        solves cohorts on the batched vmapped solver."""
        self.spec = algorithm_spec(cfg.algorithm)
        self.loss_fn = loss_fn
        self.dataset = dataset
        self.cfg = cfg
        self.scn = scenario_spec(cfg.scenario)
        self._scn_trivial = is_trivial(self.scn)
        self._env_channels = env_channels(self.scn)
        self._has_work = self.scn.work_fraction is not None
        n = dataset.num_devices
        if self.spec.num_selections == 0:
            self._pool = n
        elif cfg.sample_with_replacement:
            self._pool = cfg.devices_per_round
        else:
            self._pool = min(cfg.devices_per_round, n)
        self._m = cfg.buffer_size or self._pool
        # client→server wire codec (core/codecs): encode happens at
        # cohort LAUNCH (client semantics — the error-feedback state
        # updates when the client transmits), the flight then carries
        # its DECODED per-client delta so the staging/commit machinery
        # below is untouched; server-side post-aggregate transforms
        # (dp_gauss noise) run inside the jitted commit program.
        self._codec = codecs.codec_spec(cfg.codec)
        self._codec_trivial = codecs.is_trivial(self._codec)
        # client-axis mesh (core/sharding.py): cohort sizes vary between
        # launches (refills of m < K clients) and the buffer size need
        # not divide the mesh, so BOTH SPMD programs — the shard-mapped
        # cohort solve and the shard-mapped commit — run on buffers
        # padded up to the next multiple of D with masked lanes: padded
        # solve rows carry valid=0 (identity steps, sliced off on
        # return), padded commit rows carry weight 0 (dropped by the
        # psum-ed weighted mean).  mesh_devices=1 builds no mesh and
        # every program below is structurally the pre-mesh build.
        self.mesh = sharding.mesh_for(cfg)
        self._shards = sharding.num_shards(self.mesh)
        self._m_pad = -(-self._m // self._shards) * self._shards
        self.rng = np.random.default_rng(cfg.seed)
        self._solver = make_batched_solver(
            loss_fn, learning_rate=cfg.learning_rate,
            num_epochs=cfg.local_epochs, with_cutoff=self._has_work,
            solver=cfg.local_solver)
        if self.mesh is not None:
            dev = sharding.stacked_spec(self.mesh)
            rep = sharding.replicated_spec()
            manual = sharding.axis_name_tuple(
                sharding.mesh_axes(self.mesh))
            in_specs = (rep, dev, rep, dev, dev)
            if self._has_work:
                in_specs += (dev,)
            self._jsolve = jax.jit(jax.shard_map(
                self._solver, mesh=self.mesh, in_specs=in_specs,
                out_specs=dev, axis_names=set(manual), check_vma=False))
        else:
            self._jsolve = jax.jit(self._solver)
        self._grads = jax.jit(make_batched_grad_fn(loss_fn))
        self._server_opt = make_server_opt(self.spec, cfg)
        self._commit_fn = self._make_commit()
        self._gref = jax.jit(server.aggregate_buffered)
        self._eval_loss = _make_eval_loss(loss_fn)
        self._sample_queue: List[np.ndarray] = []

    # -- compiled pieces --------------------------------------------------

    def _make_commit(self):
        """The jitted commit program: staleness-weighted buffer reduce +
        server (optimizer) step, one dispatch per commit.  Codecs with a
        server-side post-aggregate transform (dp_gauss noise) get a
        variant taking the commit's codec key and effective count; the
        trivial codec keeps the exact pre-codec program.  Under a mesh
        the program is shard-mapped over the (padded) buffer axis: the
        weighted reduce psums numerator and weight sum over the mesh,
        the server step runs replicated — one SPMD program per commit.
        """
        opt = self._server_opt
        codec, cfg = self._codec, self.cfg
        mesh = self.mesh
        # one axis name on the flat mesh, the (edge, device) tuple on
        # the aggregation tree — aggregate_buffered reduces through
        # sharding.tree_psum either way
        axis = sharding.mesh_axes(mesh)
        self._commit_takes_key = (not self._codec_trivial
                                  and codec.post_aggregate is not None)

        if self._commit_takes_key:
            def commit(w, opt_state, buf, weights, key, count):
                pg = server.aggregate_buffered(buf, weights,
                                               axis_name=axis)
                fspec = flat_spec(w)
                flat = codec.post_aggregate(
                    cfg, key, pack(fspec, pg), jnp.maximum(count, 1.0))
                pg = unpack(fspec, flat)
                return server.server_step(w, pt.sub(w, pg), opt,
                                          opt_state)
        else:
            def commit(w, opt_state, buf, weights):
                pg = server.aggregate_buffered(buf, weights,
                                               axis_name=axis)
                return server.server_step(w, pt.sub(w, pg), opt,
                                          opt_state)

        if mesh is not None:
            dev = sharding.stacked_spec(mesh)
            rep = sharding.replicated_spec()
            in_specs = (rep, rep, dev, dev)
            if self._commit_takes_key:
                in_specs += (rep, rep)
            commit = jax.shard_map(
                commit, mesh=mesh, in_specs=in_specs, out_specs=(rep, rep),
                axis_names=set(sharding.axis_name_tuple(axis)),
                check_vma=False)
        return jax.jit(commit)

    # -- sampling / environment -------------------------------------------

    def _sample(self, m: int) -> np.ndarray:
        """Draw an ``m``-client selection from the host rng — same
        sampler (and, degenerately, same stream order) as the python
        driver's ``_sample``."""
        p = self.dataset.weights if self.cfg.weighted_sampling else None
        return server.sample_devices(
            self.rng, self.dataset.num_devices, m, p=p,
            replace=self.cfg.sample_with_replacement)

    def _cohort_selections(
            self, m: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """(solve cohort, gather selection) for a launch of ``m``
        clients: selections follow the spec's phase structure exactly as
        in the synchronous drivers; injected ``selections`` rows (tests)
        are consumed one row per cohort launch."""
        spec = self.spec
        if self._sample_queue:
            row = np.asarray(self._sample_queue.pop(0))
            phases = [row] if row.ndim == 1 else list(row)
            if spec.num_selections == 2:
                s1 = np.asarray(phases[0], dtype=np.int64)
                s2 = np.asarray(phases[-1], dtype=np.int64)[:m]
                return s2, s1
            return np.asarray(phases[0], dtype=np.int64)[:m], None
        if spec.num_selections == 2:
            # gather keeps the algorithm's full width K; the solve
            # cohort only refills the freed slots
            s1 = self._sample(self.cfg.devices_per_round)
            return self._sample(m), s1
        return self._sample(m), None

    def _launch_uniforms(self) -> Optional[Dict[str, Any]]:
        """One ``(N,)`` uniform per declared scenario channel, drawn per
        cohort launch from the host stream (the ideal scenario draws
        nothing — the stream stays exactly the python driver's)."""
        if self._scn_trivial:
            return None
        n = self.dataset.num_devices
        return {c: jnp.asarray(self.rng.random(n), jnp.float32)
                for c in self._env_channels}

    # -- the cohort solve -------------------------------------------------

    def _solve_cohort(self, w, corr, mu, b, v, limit):
        """One batched local solve of an m-client cohort, mesh-aware.

        Under a mesh the stacked solve inputs are padded up to the next
        multiple of D with zero rows — a padded row's all-zero valid
        mask makes the solver take identity steps (the PR-1 masked-lane
        contract), and the padding is sliced off the result — so every
        cohort size runs as ONE SPMD program on the shard-mapped
        solver.  Without a mesh (``_shards == 1``) no padding happens
        and this is exactly the pre-mesh ``_jsolve`` call.
        """
        m = v.shape[0]
        m_pad = -(-m // self._shards) * self._shards
        if m_pad != m:
            def zpad(x):
                widths = [(0, m_pad - m)] + [(0, 0)] * (x.ndim - 1)
                return jnp.pad(x, widths)
            b = jax.tree_util.tree_map(zpad, b)
            v = zpad(jnp.asarray(v))
            corr = jax.tree_util.tree_map(zpad, corr)
            if limit is not None:
                limit = np.concatenate(
                    [np.asarray(limit),
                     np.zeros((m_pad - m,), np.asarray(limit).dtype)])
        if limit is not None:
            res = self._jsolve(w, corr, mu, b, v,
                               jnp.asarray(limit, jnp.int32))
        else:
            res = self._jsolve(w, corr, mu, b, v)
        if m_pad != m:
            res = jax.tree_util.tree_map(lambda x: x[:m], res)
        return res

    def _solve_duplicates(self, cohort, w, aux, b, v, limit, g_local,
                          corr_for, mu):
        """Sequential per-duplicate solves for control-variate specs
        under ``sample_with_replacement``.

        Cohort position ``i`` belongs to occurrence layer
        ``L = (earlier positions holding the same client)``; layers are
        solved in order, each reading the LIVE control refreshed by the
        previous layer — so a client appearing twice in one cohort gets
        two sequential control updates, exactly the python driver's
        ``_loop_round`` semantics (its corrections likewise read the
        launch-time ``c_server`` snapshot but the client's refreshed
        ``c_local``).  Commit-time writeback stays last-writer-wins and
        ``sum(c_delta)`` telescopes to the same server-control update.
        Each layer is a (padded) batched solve via ``_solve_cohort``,
        so this path composes with mesh sharding too.  Returns the
        ``(m, ...)`` stacks in cohort-position order so codec slots and
        flight rows are position-addressed as in the plain path.
        """
        spec, cfg = self.spec, self.cfg
        m = len(cohort)
        tmap = jax.tree_util.tree_map
        zeros = pt.zeros_like(w)
        live = {int(k): aux["controls"].get(int(k), zeros)
                for k in cohort}
        occ = np.zeros((m,), np.int64)
        seen: Dict[int, int] = {}
        for i, k in enumerate(cohort):
            occ[i] = seen.get(int(k), 0)
            seen[int(k)] = int(occ[i]) + 1
        rows_p: List[Any] = [None] * m
        rows_ns: List[Any] = [None] * m
        rows_cn: List[Any] = [None] * m
        rows_cd: List[Any] = [None] * m
        for layer in range(int(occ.max()) + 1):
            idx = np.nonzero(occ == layer)[0]
            c_stack = tmap(lambda *xs: jnp.stack(xs),
                           *[live[int(cohort[i])] for i in idx])
            b_l = tmap(lambda x: x[idx], b)
            v_l = jnp.asarray(v)[idx]
            g_l = (tmap(lambda x: x[idx], g_local)
                   if g_local is not None else None)
            corr = corr_for(c_stack, g_l, len(idx))
            res = self._solve_cohort(
                w, corr, mu, b_l, v_l,
                None if limit is None else np.asarray(limit)[idx])
            inv_steps = 1.0 / (jnp.maximum(res.num_steps, 1)
                               * cfg.learning_rate)
            c_new = spec.control_update(ControlCtx(
                c_local=c_stack, c_server=aux["c_server"], w0=w,
                w_new=res.params, inv_steps=inv_steps))
            c_delta = pt.sub(c_new, c_stack)
            for j, i in enumerate(idx):
                rows_p[i] = tmap(lambda x, j=j: x[j], res.params)
                rows_ns[i] = res.num_steps[j]
                rows_cn[i] = tmap(lambda x, j=j: x[j], c_new)
                rows_cd[i] = tmap(lambda x, j=j: x[j], c_delta)
                live[int(cohort[i])] = rows_cn[i]
        stack = lambda rows: tmap(lambda *xs: jnp.stack(xs), *rows)
        return (stack(rows_p), jnp.stack(rows_ns), stack(rows_cn),
                stack(rows_cd))

    # -- the cohort launch ------------------------------------------------

    def _launch(self, cohort: np.ndarray, s1: Optional[np.ndarray],
                w, aux: Dict[str, Any], version: int, now: float,
                seq0: int) -> List[_Flight]:
        """Solve ``cohort`` against the anchor ``w`` (the server params
        at launch) and return one :class:`_Flight` per client with its
        completion time and commit payload.

        All launch-time reads — the gather gradient, ``g_prev``,
        controls, the prox center, the decay schedule — snapshot the
        server state AS OF this launch; everything the commit needs
        later rides in the flight record, so out-of-order commits never
        reach back into mutated state.
        """
        spec, cfg = self.spec, self.cfg
        m = len(cohort)
        uniforms = self._launch_uniforms()
        if uniforms is not None:
            env = realize_event_env(
                self.scn, cfg, self.dataset.num_devices,
                jnp.asarray(cohort), version, uniforms)
            delivered = np.asarray(env.delivered) > 0
            work = np.asarray(env.work)
            latency = np.asarray(env.latency)
        else:
            delivered = np.ones((m,), bool)
            work = None
            latency = np.ones((m,), np.float64)

        mu = cfg.mu if spec.use_mu else 0.0
        decay = (spec.decay(cfg, version)
                 if spec.decay is not None else 1.0)

        # phase A: the gradient gather, against THIS launch's anchor
        g_global = None
        gather_n = 0.0
        if spec.grad_source == "fresh":
            gather = np.asarray(s1 if s1 is not None else cohort)
            if self.scn.availability is not None and uniforms is not None:
                p = np.asarray(self.scn.availability(
                    cfg, self.dataset.num_devices, version))
                av = np.asarray(uniforms["avail"])[gather] < p[gather]
                gather = gather[av]
            gather_n = float(len(gather))
            if len(gather) > 0:
                gb, gv = stack_device_batches(self.dataset, gather)
                g_stack = self._grads(w, gb, gv)
                g_global = jax.tree_util.tree_map(
                    lambda x: x.mean(axis=0), g_stack)
        elif spec.grad_source == "stale":
            g_global = aux.get("g_prev")

        b, v = stack_device_batches(self.dataset, cohort)
        g_local = self._grads(w, b, v) if spec.local_grad else None

        def corr_for(c_stack_, g_local_, mm):
            if spec.correction is not None and not (
                    spec.grad_source == "fresh" and g_global is None):
                return spec.correction(CorrCtx(
                    w0=w, g_global=g_global, g_local=g_local_,
                    c_server=aux.get("c_server"), c_local=c_stack_,
                    center=aux.get("center"), mu=mu, decay=decay))
            return jax.tree_util.tree_map(
                lambda x: jnp.zeros((mm,) + x.shape, x.dtype), w)

        if self._has_work:
            total = cfg.local_epochs * np.asarray(v).sum(axis=1)
            wf = work if work is not None else np.ones((m,))
            limit = np.minimum(total, np.ceil(wf * total))
        else:
            limit = None

        c_new = c_delta = None
        if (spec.control_update is not None
                and len(np.unique(cohort)) < m):
            # duplicate arrivals within one cohort (replacement
            # sampling): sequential occurrence-layer solves, reading
            # the control refreshed by the previous duplicate
            res_params, num_steps, c_new, c_delta = \
                self._solve_duplicates(cohort, w, aux, b, v, limit,
                                       g_local, corr_for, mu)
        else:
            c_stack = None
            if spec.control_update is not None:
                zeros = pt.zeros_like(w)
                c_stack = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *[aux["controls"].get(int(k), zeros)
                      for k in cohort])
            corr = corr_for(c_stack, g_local, m)
            res = self._solve_cohort(w, corr, mu, b, v, limit)
            res_params, num_steps = res.params, res.num_steps
            if spec.control_update is not None:
                inv_steps = 1.0 / (jnp.maximum(num_steps, 1)
                                   * cfg.learning_rate)
                c_new = spec.control_update(ControlCtx(
                    c_local=c_stack, c_server=aux["c_server"], w0=w,
                    w_new=res_params, inv_steps=inv_steps))
                c_delta = pt.sub(c_new, c_stack)

        # codec encode, client-side at launch: the flight carries the
        # DECODED delta (per-client post_decode is valid by the spec's
        # linearity contract) so staging/commit stay codec-blind; the
        # error-feedback accumulator refreshes only for deliveries that
        # will actually cross the wire.
        dec = None
        if not self._codec_trivial:
            codec = self._codec
            fspec = flat_spec(w)
            key = codecs.round_key(cfg, version)
            deltas = (pack_broadcast(fspec, w, m)
                      - pack_stacked(fspec, res_params, m)
                      ).reshape(m, fspec.rows, LANES)
            efs = None
            if codec.error_feedback:
                zero = jnp.zeros((fspec.rows, LANES), jnp.float32)
                efs = jnp.stack([aux["ef"].get(int(k), zero)
                                 for k in cohort])
            vals, scales, ef_new = codecs.encode_stacked(
                codec, cfg, key, deltas, efs)
            dec = vals * scales[:, None, None]
            if codec.post_decode is not None:
                dec = jax.vmap(
                    lambda x: codec.post_decode(cfg, key, x))(dec)
            if ef_new is not None:
                for i, k in enumerate(cohort):
                    if delivered[i]:
                        aux["ef"][int(k)] = ef_new[i]

        # wire bytes at launch: anchor (+ correction) broadcast to the
        # cohort, anchor broadcast to and dense gradients back from the
        # THINNED gather responders.  The encoded update uplink accrues
        # at arrival in run()'s event loop.
        dense = codecs.DENSE_BYTES * self._n_elems
        corr_down = 1.0 if spec.correction is not None else 0.0
        self._bytes_down += dense * gather_n + dense * (1.0
                                                        + corr_down) * m
        self._bytes_up += dense * gather_n

        flights = []
        for i, k in enumerate(cohort):
            row = jax.tree_util.tree_map(lambda x, i=i: x[i], res_params)
            flights.append(_Flight(
                done=now + float(latency[i]), seq=seq0 + i,
                client=int(k), anchor_version=version, launch=now,
                delivered=bool(delivered[i]),
                delta=(pt.sub(w, row) if dec is None
                       else unpack(fspec, dec[i])),
                g_local=(jax.tree_util.tree_map(
                    lambda x, i=i: x[i], g_local)
                    if spec.updates_g_prev else None),
                c_new=(jax.tree_util.tree_map(
                    lambda x, i=i: x[i], c_new)
                    if c_new is not None else None),
                c_delta=(jax.tree_util.tree_map(
                    lambda x, i=i: x[i], c_delta)
                    if c_delta is not None else None)))
        return flights

    # -- evaluation -------------------------------------------------------

    def global_loss(self, params) -> float:
        """f(w) = sum_k p_k F_k(w) over the eval split (eq. 1)."""
        total, wsum = 0.0, 0.0
        for wk, batches in self.dataset.eval_batches():
            total += wk * float(self._eval_loss(params, batches))
            wsum += wk
        return total / max(wsum, 1e-12)

    # -- the event loop ---------------------------------------------------

    def run(self, params, num_rounds: int, eval_every: int = 1,
            verbose: bool = False, checkpoint_dir: Optional[str] = None,
            selections=None) -> Tuple[Dict[str, List[float]], Any]:
        """Simulate until ``num_rounds`` server commits (or the event
        horizon) and return ``(history, final_params)``.

        The rng is re-seeded from ``cfg.seed`` per call (like the
        scanned driver), so each ``run()`` reproduces the same event
        stream.  ``selections`` follows the trainer contract — one
        ``(2, K)`` / ``(K,)`` row consumed per *cohort launch* (a refill
        of m < K clients uses the row's first m solve entries).
        """
        cfg, spec = self.cfg, self.spec
        self.rng = np.random.default_rng(cfg.seed)
        self._sample_queue = (
            [np.asarray(r) for r in np.asarray(selections)]
            if selections is not None else [])

        w = params
        aux: Dict[str, Any] = init_aux(
            spec, cfg, params, self.dataset.num_devices, stacked=False)
        if "controls" in aux:
            aux["controls"] = {}          # sparse: zeros until first commit
        if self._codec.error_feedback:
            aux["ef"] = {}                # sparse: zeros until first launch
        opt_state = aux.get("opt")
        self._n_elems = sum(
            int(np.prod(np.asarray(x.shape)))
            for x in jax.tree_util.tree_leaves(params))
        self._bytes_up = self._bytes_down = 0.0
        dense = codecs.DENSE_BYTES * self._n_elems
        enc = (self._codec.uplink_bytes(cfg, self._n_elems)
               if self._codec.uplink_bytes is not None else dense)
        grad_up = dense if spec.updates_g_prev else 0.0
        # under a mesh the staging buffer is padded to the even-shard
        # contract; rows >= self._m are never staged and always commit
        # with weight 0, so they drop out of the psum-ed weighted mean
        buffer = _CommitBuffer(params, self._m_pad)
        pending: List[_Flight] = []       # metadata of staged updates
        inflight: List[_Flight] = []      # heap by (done, seq)
        version = 0                       # commits so far
        now = 0.0
        seq = 0
        consumed = 0                      # arrivals since last commit
        budget = HORIZON_FACTOR * max(1, num_rounds) * max(self._pool,
                                                           self._m)
        hist: Dict[str, List[float]] = {
            "round": [], "comm_rounds": [], "loss": [],
            "intended_k": [], "effective_k": [], "dropped": [],
            "staleness_mean": [], "staleness_max": [],
            "buffer_wait": [], "anchor_age": [], "sim_time": [],
            "bytes_up": [], "bytes_down": []}
        chunk = cfg.chunk_rounds if cfg.chunk_rounds > 0 else num_rounds

        def launch(cohort_hint: Optional[List[int]] = None) -> None:
            nonlocal seq
            m = self._pool - len(inflight)
            if m <= 0 or version >= num_rounds:
                return
            if spec.num_selections == 0:
                # full participation: relaunch exactly the freed clients
                cohort = np.asarray(
                    cohort_hint
                    if cohort_hint is not None
                    else range(self.dataset.num_devices), dtype=np.int64)
                s1 = None
            else:
                cohort, s1 = self._cohort_selections(m)
            for f in self._launch(cohort, s1, w, aux, version, now, seq):
                heapq.heappush(inflight, f)
            seq += len(cohort)

        def commit() -> None:
            nonlocal w, opt_state, version, consumed
            stal = np.asarray(
                [version - f.anchor_version for f in pending], np.float32)
            weights = server.staleness_weight(cfg.staleness_fn,
                                              jnp.asarray(stal))
            if self._m_pad != self._m:
                # masked padding lanes: weight 0 = no contribution
                weights = jnp.pad(weights,
                                  (0, self._m_pad - self._m))
            if self._commit_takes_key:
                w, opt_state = self._commit_fn(
                    w, opt_state, buffer.swap(), weights,
                    codecs.round_key(cfg, version),
                    jnp.float32(len(pending)))
            else:
                w, opt_state = self._commit_fn(w, opt_state,
                                               buffer.swap(), weights)
            if spec.updates_g_prev:
                aux["g_prev"] = self._gref(
                    jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs),
                        *[f.g_local for f in pending]), weights)
            if spec.control_update is not None:
                for f in pending:         # arrival order: last writer wins
                    aux["controls"][f.client] = f.c_new
                csum = pending[0].c_delta
                for f in pending[1:]:
                    csum = pt.add(csum, f.c_delta)
                aux["c_server"] = pt.add(
                    aux["c_server"],
                    pt.scale(csum, 1.0 / self.dataset.num_devices))
            if spec.center_update is not None:
                aux["center"] = spec.center_update(aux["center"], w, cfg)
            version += 1
            hist["intended_k"].append(float(consumed))
            hist["effective_k"].append(float(len(pending)))
            hist["dropped"].append(float(consumed - len(pending)))
            hist["staleness_mean"].append(float(stal.mean()))
            hist["staleness_max"].append(float(stal.max()))
            hist["buffer_wait"].append(
                now - min(f.arrival for f in pending))
            hist["anchor_age"].append(
                float(np.mean([now - f.launch for f in pending])))
            hist["sim_time"].append(now)
            hist["bytes_up"].append(self._bytes_up)
            hist["bytes_down"].append(self._bytes_down)
            self._bytes_up = self._bytes_down = 0.0
            pending.clear()
            consumed = 0
            if (version - 1) % eval_every == 0 or version == num_rounds:
                loss = self.global_loss(w)
                hist["round"].append(float(version))
                hist["comm_rounds"].append(
                    float(version * spec.comm_per_round))
                hist["loss"].append(loss)
                if verbose:
                    print(f"[{cfg.algorithm}/buffered] commit "
                          f"{version:4d} t={now:8.2f} loss {loss:.4f}")
            if checkpoint_dir is not None and (
                    version % chunk == 0 or version == num_rounds):
                from repro.checkpoint.store import save_checkpoint
                save_checkpoint(checkpoint_dir,
                                {"params": w, "round": version},
                                step=version)

        launch()
        while version < num_rounds and inflight and budget > 0:
            group: List[_Flight] = [heapq.heappop(inflight)]
            now = group[0].done
            while inflight and inflight[0].done == now:
                group.append(heapq.heappop(inflight))
            for f in group:               # seq order within the instant
                if version >= num_rounds:
                    break
                budget -= 1
                consumed += 1
                f.arrival = now
                stale = version - f.anchor_version
                if f.delivered:
                    # the encoded update crossed the wire — staleness-
                    # dropped arrivals still spent the uplink bytes
                    self._bytes_up += enc + grad_up
                if not f.delivered or (cfg.max_staleness > 0
                                       and stale > cfg.max_staleness):
                    continue
                buffer.stage(len(pending), f.delta)
                pending.append(f)
                if len(pending) == self._m:
                    commit()
            launch(cohort_hint=[f.client for f in group])
        return hist, w


def _make_eval_loss(loss_fn: Callable) -> Callable:
    """One jitted per-device eval-loss fn (the trainer's helper,
    rebuilt here to keep this module import-cycle-free)."""

    @jax.jit
    def f(p, b):
        def body(acc, batch):
            return acc + loss_fn(p, batch), None
        s, _ = jax.lax.scan(body, 0.0, b)
        nb = jax.tree_util.tree_leaves(b)[0].shape[0]
        return s / nb

    return f
