"""Placement: resolve a `ShardingSpec` into concrete device placement.

*Where* a federation runs is spec data like everything else
(`FederationSpec.sharding`); this module turns that data into a
`Placement` — a `jax.sharding.Mesh` plus one `NamedSharding` per
`FleetState` leaf *group*:

  device group      leaves with leading dim n_devices (twins, rep,
                    channel), partitioned over ``device_axis``
  cluster group     leaves with leading dim n_clusters (the stacked
                    per-cluster parameters, cluster timestamps, and the
                    scan's per-cluster event-time vector), partitioned
                    over ``cluster_axis``
  replicated        everything else — the global model, the Eqn-12 queue
                    scalar, the round counter, the RNG key

The single-device fallback (``mesh=()``) resolves to ``SINGLE_DEVICE``,
whose shardings are all None: the engine then builds exactly the
pre-placement jits, so the default spec is bit-identical to the old
behavior.  A 1-device mesh (``mesh=(1,)``) builds a real `Mesh` and goes
through the sharded jit path — the placement-parity test pins that this
too reproduces the unsharded trace bit for bit.

Two sharded implementations consume a `Placement`:

* ``impl='gspmd'`` (the PR-5 path): jit ``in_shardings`` /
  ``out_shardings`` on the fused round and the lax.scan-over-rounds;
  XLA's SPMD partitioner infers the collectives.  Membership gathers are
  not shard-aligned under k-means, so the partitioner inserts cross-shard
  all-gathers — this path measures partitioning overhead, not capacity.
* ``impl='shard_map'`` (the cluster-major engine,
  `repro.api.cluster_engine`): the fleet is statically re-indexed so each
  cluster's member slots are contiguous, every leaf co-shards over one
  mesh axis (``shard_map_placement`` below), and the round is an explicit
  `jax.shard_map` whose only collectives are two psums.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .spec import GSPMD_IMPL, ShardingSpec

# FleetState field -> leaf-group membership (leading-dim semantics)
DEVICE_GROUP = ("twins", "rep", "channel")
CLUSTER_GROUP = ("cluster_params", "cluster_ts")


@dataclasses.dataclass(frozen=True)
class Placement:
    """A resolved mesh + the axis each FleetState leaf group shards on."""
    mesh: Optional[Mesh] = None
    device_axis: Optional[str] = None
    cluster_axis: Optional[str] = None

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    # ------------------------------------------------------------------ #
    def sharding(self, axis: Optional[str] = None) -> Optional[NamedSharding]:
        """NamedSharding partitioning the leading dim over ``axis``
        (None = replicated).  Returns None on the single-device fallback."""
        if self.mesh is None:
            return None
        spec = PartitionSpec() if axis is None else PartitionSpec(axis)
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> Optional[NamedSharding]:
        return self.sharding(None)

    def group_axis(self, field: str) -> Optional[str]:
        if field in DEVICE_GROUP:
            return self.device_axis
        if field in CLUSTER_GROUP:
            return self.cluster_axis
        return None

    def state_shardings(self, state) -> Any:
        """A pytree of NamedShardings matching a `FleetState` (any NamedTuple
        whose field names follow the leaf-group convention)."""
        out = {}
        for field in state._fields:
            sh = self.sharding(self.group_axis(field))
            out[field] = jax.tree.map(lambda _: sh, getattr(state, field))
        return type(state)(**out)

    def tree_replicated(self, tree) -> Any:
        repl = self.replicated()
        return jax.tree.map(lambda _: repl, tree)

    def shard_state(self, state) -> Any:
        """Commit a FleetState's leaves to their group shardings."""
        if not self.is_sharded:
            return state
        return jax.device_put(state, self.state_shardings(state))

    def replicate(self, tree) -> Any:
        """Commit every leaf of ``tree`` replicated over the mesh."""
        if not self.is_sharded:
            return tree
        return jax.device_put(tree, self.tree_replicated(tree))


SINGLE_DEVICE = Placement()


def _mesh_devices(mesh_shape) -> np.ndarray:
    """The device array backing a mesh, or a readable error.  Spans *all*
    processes under `jax.distributed` (multi-controller SPMD)."""
    need = math.prod(mesh_shape)
    devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"sharding: mesh {tuple(mesh_shape)} needs {need} devices but "
            f"the {devices[0].platform} backend exposes {len(devices)}; on "
            "a CPU host, force a device pool with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return np.asarray(devices[:need]).reshape(mesh_shape)


def resolve(sharding: ShardingSpec, *, n_devices: int, n_clusters: int,
            impl: Optional[str] = None) -> Placement:
    """`ShardingSpec` -> `Placement` over this process's visible devices.

    ``impl`` overrides the spec's resolved implementation for validation
    purposes — the plain `DeviceScaleEngine` passes ``'gspmd'`` so a
    shard_map-defaulted spec forced onto the fallback path still gets the
    strict divisibility check that path requires.

    Raises with a readable error when the mesh does not divide the fleet
    (``impl='gspmd'``; delegated to ``ShardingSpec.validate``) or needs
    more devices than the backend exposes.
    """
    if not sharding.is_sharded:
        return SINGLE_DEVICE
    if impl is not None and impl != sharding.resolved_impl():
        sharding = dataclasses.replace(sharding, impl=impl)
    sharding.validate(n_devices, n_clusters)
    axes = sharding.resolved_axes()
    mesh = Mesh(_mesh_devices(sharding.mesh), axes)
    return Placement(mesh=mesh, device_axis=sharding.device_axis,
                     cluster_axis=sharding.resolved_cluster_axis(axes))


def shard_map_placement(sharding: ShardingSpec) -> Placement:
    """The cluster-major placement: one 1-D mesh axis carrying *both* leaf
    groups (fleet rows are cluster-major, so device and cluster dims
    co-shard by construction).  Used by `repro.api.cluster_engine`."""
    assert sharding.is_sharded and len(sharding.mesh) == 1
    axes = sharding.resolved_axes()
    mesh = Mesh(_mesh_devices(sharding.mesh), axes)
    return Placement(mesh=mesh, device_axis=axes[0], cluster_axis=axes[0])
