"""Device-mesh sharding for federated rounds (`"device"` + `"edge"` axes).

The paper's setting is massively distributed remote *clients*; the
simulation's dominant cost is the K stacked local solves each round.
Every jitted round program stacks those solves on a leading device axis
(``RoundEngine``; ``ScannedDriver`` scans whole rounds of them) — and
that axis is embarrassingly parallel.  This module maps it onto a JAX
mesh:

- :func:`make_device_mesh` builds the client mesh.  The default is 1-D
  with the single axis :data:`DEVICE_AXIS` (the name refers to the
  paper's "remote devices", which the simulation shards over the
  *hardware* devices of the mesh — K/D clients per chip).  With
  ``edge_shards > 1`` the same leaf devices are grouped under an outer
  :data:`EDGE_AXIS` into a 2-D ``(edge, device)`` mesh — the
  **hierarchical aggregation tree**: every cross-client reduction runs
  as nested collectives, leaf devices reducing within their edge
  aggregator first, edge partials then reducing to the server
  (:func:`tree_psum` / :func:`tree_pmean`).  One SPMD round aggregates
  through the tree instead of a single flat collective — the topology
  of a real edge-aggregated federated deployment, expressed in the
  mesh.
- :func:`stacked_spec` / :func:`replicated_spec` are the two
  ``PartitionSpec`` layouts every round tensor falls into: K-stacked
  batch tensors, per-client solver states and ``(K,)`` masks shard on
  their leading axis (over BOTH mesh axes when the tree is on); global
  state (params ``w0``, ``g_prev``, ``c_server``, ``center``,
  server-opt state) replicates.
- :func:`shard_stacked` / :func:`replicate` place concrete arrays
  (the scanned driver's all-device ``(N, ...)`` batch tensors and
  control carries) so the chunk program starts from the layout the
  shard-mapped round body wants.

``core/engine.py`` wraps the round body in ``jax.shard_map`` over this
mesh and expresses every cross-client reduction — ``mean_k``, masked
scenario reductions, the server pseudo-gradient step's aggregate — through
:func:`tree_psum` / :func:`tree_pmean`, so the whole round stays ONE
jitted SPMD program whether the reduction is flat or a tree.

Exactness of the tree
---------------------
Shards carry equal client counts (``check_divisible``), so the tree
mean — mean within each edge, then mean of edge means — equals the
flat mean exactly (to float association), and nested psums are plain
reorderings of the flat psum.  ``edge_shards=1`` builds the exact
pre-tree 1-D mesh: no structural change, bit-identical programs.
Parity gate: tests/_sharded_child.py (edge_shards in {2, 4} vs 1 vs
no mesh on the forced-host 8-device CPU story).

Resolution contract
-------------------
``FederatedConfig.mesh_devices`` is ``1`` (no mesh — every path keeps
its exact pre-mesh program, bit-identical numerics), a positive int
(validated against ``jax.device_count()`` at trainer/engine build, not
at config construction — configs are a leaf layer with no device
state), or ``"auto"`` (all visible devices); it always counts LEAF
devices — ``edge_shards`` groups them without changing the total.  On
CPU-only hosts, run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get an
8-way mesh of host threads — that is how the parity tests and the CI
docs/bench jobs exercise the sharded path without accelerators.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

#: Name of the mesh axis carrying the stacked federated clients.
DEVICE_AXIS = "device"

#: Name of the outer edge-aggregator axis of the 2-D tree mesh.
EDGE_AXIS = "edge"

#: An axis-name argument: one mesh axis or the (edge, device) tuple.
AxisName = Union[str, Tuple[str, ...]]

#: The hint appended to every "not enough devices" error.
_CPU_HINT = ("on a CPU-only host, set XLA_FLAGS="
             "--xla_force_host_platform_device_count=<n> before the "
             "first JAX import to split the host into <n> devices")


def resolve_mesh_devices(mesh_devices) -> int:
    """Resolve a ``FederatedConfig.mesh_devices`` value to a mesh size.

    ``"auto"`` resolves to ``jax.device_count()``; an int is validated
    against it (``1 <= mesh_devices <= device_count``).  Returns the
    resolved int; ``1`` means "no mesh" everywhere downstream.
    """
    avail = jax.device_count()
    if mesh_devices == "auto":
        return avail
    if isinstance(mesh_devices, bool) or not isinstance(
            mesh_devices, int):
        raise ValueError(
            f"mesh_devices must be a positive int or 'auto', got "
            f"{mesh_devices!r}")
    n = mesh_devices
    if n < 1:
        raise ValueError(f"mesh_devices must be >= 1, got {n}")
    if n > avail:
        raise ValueError(
            f"mesh_devices={n} exceeds jax.device_count()={avail}; "
            f"{_CPU_HINT}")
    return n


def make_device_mesh(num_devices: int, edge_shards: int = 1) -> Mesh:
    """The client mesh over ``num_devices`` LEAF devices.

    ``edge_shards=1``: the 1-D :data:`DEVICE_AXIS` mesh every sharded
    round program used pre-tree.  ``edge_shards=E``: the same leaf
    devices regrouped as a 2-D ``(E, num_devices / E)`` mesh with axes
    ``(EDGE_AXIS, DEVICE_AXIS)`` — the hierarchical aggregation tree.
    """
    if edge_shards <= 1:
        return jax.make_mesh((num_devices,), (DEVICE_AXIS,),
                             axis_types=(AxisType.Auto,))
    if num_devices % edge_shards != 0:
        raise ValueError(
            f"edge_shards={edge_shards} must divide the resolved "
            f"mesh_devices={num_devices} (each edge aggregates an "
            f"equal leaf-device group)")
    return jax.make_mesh((edge_shards, num_devices // edge_shards),
                         (EDGE_AXIS, DEVICE_AXIS),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def mesh_for(cfg) -> Optional[Mesh]:
    """The mesh a ``FederatedConfig`` asks for, or ``None``.

    Resolves ``cfg.mesh_devices`` (validating against the live device
    count) and returns ``None`` at 1 — the single-device programs are
    kept structurally untouched, not run under a trivial mesh, so
    ``mesh_devices=1`` stays bit-exact with the pre-mesh build.
    ``cfg.edge_shards > 1`` shapes the result into the 2-D tree mesh
    (and is rejected without a real mesh to group).
    """
    n = resolve_mesh_devices(getattr(cfg, "mesh_devices", 1))
    edge = getattr(cfg, "edge_shards", 1)
    if n == 1:
        if edge > 1:
            raise ValueError(
                f"edge_shards={edge} needs a real client mesh; "
                f"mesh_devices resolved to 1 (set mesh_devices>1 or "
                f"'auto' — {_CPU_HINT})")
        return None
    return make_device_mesh(n, edge)


def mesh_axes(mesh: Optional[Mesh]) -> Optional[AxisName]:
    """The collective axis-name argument for ``mesh``: ``None`` (no
    mesh), :data:`DEVICE_AXIS` (flat 1-D), or the ordered
    ``(EDGE_AXIS, DEVICE_AXIS)`` tuple (tree).  Feed the result to
    :func:`tree_psum` / :func:`tree_pmean` / ``shard_map``'s
    ``manual_axes``."""
    if mesh is None:
        return None
    if EDGE_AXIS in mesh.axis_names:
        return (EDGE_AXIS, DEVICE_AXIS)
    return DEVICE_AXIS


def axis_name_tuple(axis_name: AxisName) -> Tuple[str, ...]:
    """Normalize an axis-name argument to a tuple of mesh axis names."""
    return (axis_name,) if isinstance(axis_name, str) else tuple(
        axis_name)


def num_shards(mesh: Optional[Mesh]) -> int:
    """Total leaf shards of the client axis (product over mesh axes);
    1 without a mesh."""
    if mesh is None:
        return 1
    out = 1
    for n in mesh.shape.values():
        out *= n
    return out


def tree_psum(x, axis_name: AxisName):
    """``psum`` through the aggregation tree: innermost level first
    (leaf devices reduce within their edge aggregator), then each
    outer level (edge partials reduce to the server).  A plain flat
    ``psum`` for a single axis name — and a pure reordering of it for
    the tuple, so flat and tree agree to float association."""
    for name in reversed(axis_name_tuple(axis_name)):
        x = jax.lax.psum(x, name)
    return x


def tree_pmean(x, axis_name: AxisName):
    """``pmean`` through the aggregation tree (mean of edge means).
    Exact — every shard carries the same client count
    (``check_divisible``), so mean-of-means equals the flat mean."""
    for name in reversed(axis_name_tuple(axis_name)):
        x = jax.lax.pmean(x, name)
    return x


def linear_shard_index(axis_name: AxisName):
    """This shard's linear index along the stacked client axis — the
    row-major flattening of the mesh coordinates, matching how
    :func:`stacked_spec` lays a leading axis over ``(edge, device)``.
    Generalizes ``jax.lax.axis_index`` to the tree mesh (the codec
    cohort-slot offsets depend on it)."""
    idx = 0
    for name in axis_name_tuple(axis_name):
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def stacked_spec(mesh: Optional[Mesh] = None) -> PartitionSpec:
    """Leading-axis layout for K-stacked round tensors (batch stacks,
    per-client solver state, ``(K,)`` masks): each mesh device holds
    K/D clients' rows.  Under the tree mesh the leading axis shards
    over BOTH axes (edge-major, then device within the edge)."""
    if mesh is not None and EDGE_AXIS in mesh.axis_names:
        return PartitionSpec((EDGE_AXIS, DEVICE_AXIS))
    return PartitionSpec(DEVICE_AXIS)


def chunk_stacked_sharding(mesh: Mesh) -> NamedSharding:
    """:func:`stacked_spec` one axis in, bound to ``mesh``: the layout
    of per-round stacks ``(R, K, ...)`` scanned over their leading
    round axis (the streaming chunk's cohorts and masks)."""
    return NamedSharding(mesh, PartitionSpec(None, *stacked_spec(mesh)))


def replicated_spec() -> PartitionSpec:
    """Fully-replicated layout for global round state (``w0``,
    ``g_prev``, ``c_server``, ``center``, opt state, scalars)."""
    return PartitionSpec()


def stacked_sharding(mesh: Mesh) -> NamedSharding:
    """:func:`stacked_spec` bound to ``mesh`` for ``jax.device_put``."""
    return NamedSharding(mesh, stacked_spec(mesh))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """:func:`replicated_spec` bound to ``mesh`` for ``jax.device_put``."""
    return NamedSharding(mesh, replicated_spec())


def check_divisible(k: int, mesh: Mesh, what: str) -> None:
    """Raise if a stacked axis of size ``k`` cannot shard evenly over
    ``mesh`` — sharded rounds keep exact parity by giving every mesh
    device (leaf of the aggregation tree) the same number of clients."""
    d = num_shards(mesh)
    if k % d != 0:
        raise ValueError(
            f"{what}={k} is not divisible by mesh_devices={d}; the "
            f"sharded round program gives each mesh device k/D clients "
            f"— pick a selection size (or mesh size) with k % D == 0")


def shard_stacked(tree, mesh: Mesh):
    """Place a stacked pytree with its leading axis over the mesh.

    Leaves whose leading axis does not divide evenly (e.g. an ``(N,
    ...)`` all-client carry with ``N % D != 0``) are replicated instead
    — layout is a performance choice, never a correctness constraint
    outside the shard-mapped round body itself.
    """
    d = num_shards(mesh)
    st, rep = stacked_sharding(mesh), replicated_sharding(mesh)

    def put(x):
        ok = getattr(x, "ndim", 0) >= 1 and x.shape[0] % d == 0
        return jax.device_put(x, st if ok else rep)

    return jax.tree_util.tree_map(put, tree)


def replicate(tree, mesh: Mesh):
    """Place a pytree fully replicated over the mesh."""
    return jax.device_put(tree, replicated_sharding(mesh))
