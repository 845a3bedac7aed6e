"""Collective helpers over the named mesh (shard_map wrappers).

The reference's collectives are native TCP rings (LGBM_NetworkInit allreduce,
VW spanning-tree, horovod ring — SURVEY.md §2.7 items 2-4). Here every
collective is an XLA op over mesh axes; these helpers wrap the common shapes
so estimator code never touches lax primitives directly.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import MeshContext

__all__ = ["psum_over", "pmean_over", "all_gather_over",
           "data_parallel_map", "ring_permute"]


def psum_over(mesh_ctx: MeshContext, axis: str | Sequence[str] = "data"):
    """Return fn(x)->x summed over `axis`, runnable under jit on the mesh."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def inner(x):
        return jax.lax.psum(x, axes)

    return functools.partial(_run_collective, mesh_ctx, inner)


def _run_collective(mesh_ctx: MeshContext, fn, x):
    sharded = jax.shard_map(fn, mesh=mesh_ctx.mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)
    return sharded(x)


def pmean_over(mesh_ctx: MeshContext, axis: str = "data"):
    def inner(x):
        return jax.lax.pmean(x, axis)

    return functools.partial(_run_collective, mesh_ctx, inner)


def all_gather_over(mesh_ctx: MeshContext, axis: str = "data", tiled: bool = True):
    def inner(x):
        return jax.lax.all_gather(x, axis, tiled=tiled)

    def run(x):
        sharded = jax.shard_map(inner, mesh=mesh_ctx.mesh,
                            in_specs=P(axis), out_specs=P(), check_vma=False)
        return sharded(x)

    return run


def ring_permute(mesh_ctx: MeshContext, axis: str = "seq", shift: int = 1):
    """Neighbor exchange along a mesh axis ring — building block for ring
    attention / pipeline microbatch handoff."""
    n = mesh_ctx.axis_sizes[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    def inner(x):
        return jax.lax.ppermute(x, axis, perm)

    def run(x):
        sharded = jax.shard_map(inner, mesh=mesh_ctx.mesh,
                            in_specs=P(axis), out_specs=P(axis), check_vma=False)
        return sharded(x)

    return run


def data_parallel_map(mesh_ctx: MeshContext, fn: Callable, reduce: str | None = "mean"):
    """jit `fn(batch)->val` with batch sharded over data axes; optionally psum/
    pmean the result — the one-liner DP pattern replacing horovod DP."""

    @functools.partial(jax.jit)
    def wrapped(batch):
        out = fn(batch)
        return out

    def run(batch: Any):
        placed = mesh_ctx.shard_batch(batch)
        out = wrapped(placed)
        if reduce == "mean":
            return jax.tree.map(lambda x: jnp.mean(x, axis=0) if jnp.ndim(x) > 0 else x, out)
        return out

    return run
