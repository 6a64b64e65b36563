"""Collective primitives: rabit's API surface, XLA-native.

The reference's BSP apps call rabit::Allreduce<Sum/Max>, Broadcast and
checkpoint primitives (reference learn/solver/lbfgs.h:172,252,302,
learn/kmeans/kmeans.cc:160-190). On TPU those are `jax.lax.psum/pmax` under
`shard_map` over a mesh axis; this module wraps them so solver code reads
like the reference while compiling to ICI collectives.

Two call styles:
- inside a shard_map'ped function: `allreduce_sum(x, axis)` etc. — thin
  lax wrappers;
- host-level, eager: `Communicator.allreduce(array)` — runs a tiny jitted
  psum over the mesh for host-orchestrated loops (L-BFGS line search,
  k-means outer iterations), the analog of rabit's blocking calls.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from wormhole_tpu.parallel.mesh import DATA_AXIS


def allreduce_sum(x, axis: str = DATA_AXIS):
    return jax.lax.psum(x, axis_name=axis)


def allreduce_max(x, axis: str = DATA_AXIS):
    return jax.lax.pmax(x, axis_name=axis)


def allreduce_min(x, axis: str = DATA_AXIS):
    return jax.lax.pmin(x, axis_name=axis)


def broadcast(x, root: int = 0, axis: str = DATA_AXIS):
    """Every shard gets root's value (rabit::Broadcast parity)."""
    src = jax.lax.all_gather(x, axis)  # small payloads only
    return jax.tree_util.tree_map(lambda g: g[root], src)


class Communicator:
    """Host-level BSP collectives over one mesh axis.

    Plays rabit's blocking Allreduce/Broadcast for host-orchestrated solver
    loops. Arrays are data-sharded or replicated jax Arrays; the reduction
    compiles once per shape and runs as an ICI collective.
    """

    def __init__(self, mesh: Mesh, axis: str = DATA_AXIS):
        self.mesh = mesh
        self.axis = axis
        self._sum_fns: dict[int, Callable] = {}  # per-instance compile cache

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    def _sum_fn(self, ndim: int):
        fn = self._sum_fns.get(ndim)
        if fn is None:
            spec = P(self.axis, *([None] * (ndim - 1)))

            @jax.jit
            @functools.partial(
                shard_map,
                mesh=self.mesh,
                in_specs=spec,
                out_specs=P(*([None] * (ndim - 1))),
            )
            def reduce_sum(x):
                # each shard holds a (1, *tail) block of the stacked
                # contributions; the psum of the squeezed block is the
                # fully-reduced (*tail) result, replicated everywhere
                return jax.lax.psum(x[0], self.axis)

            fn = self._sum_fns[ndim] = reduce_sum
        return fn

    def allreduce_shards(self, x):
        """Sum per-shard contributions: x's leading dim is the axis size
        (one slice per shard); returns the reduced (*tail) array
        replicated everywhere — rabit::Allreduce<Sum> semantics."""
        x = jnp.asarray(x)
        return self._sum_fn(x.ndim)(x)
