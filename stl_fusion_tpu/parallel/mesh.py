"""Mesh helpers — the device topology the sharded graph runs on.

The TPU-native replacement for the reference's server-pool scaling story
(RpcCallRouter consistent-hash routing across hosts,
samples/MultiServerRpc/Program.cs:58-76): instead of routing calls between
processes over WebSockets, the dependency graph itself is sharded over a
``jax.sharding.Mesh`` and invalidation frontiers ride ICI collectives.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["graph_mesh", "shard_map_compat", "P", "Mesh", "NamedSharding"]

GRAPH_AXIS = "graph"


def shard_map_compat(mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` as a decorator with the varying-manual-axes check
    off by default (the wave bodies mix replicated and sharded carries in
    ways the checker cannot follow)."""
    def deco(f):
        return shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
        )

    return deco


def graph_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the graph axis (edge/node sharding dimension)."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (GRAPH_AXIS,))
