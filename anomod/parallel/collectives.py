"""Explicit collective patterns over the device mesh.

XLA inserts collectives from sharding annotations for the main paths; this
module provides the explicit shard_map building blocks for state merging:

  - ``ring_allreduce``: ppermute-based ring all-reduce (the bandwidth-optimal
    ICI pattern, written out instead of ``psum`` where overlap with compute
    matters or where the reduction isn't a plain sum).
  - ``allgather_merge_tdigests``: t-digest shard states are NOT sum-mergeable,
    so shards all-gather their centroid sets over the mesh axis and rebuild —
    the sketch-state analog of gradient synchronization.
  - ``pmax_merge_hll``: HLL registers merge exactly with an elementwise max.
"""

from __future__ import annotations

from typing import Optional


def ring_allreduce(x, axis: str):
    """Ring all-reduce via ppermute (call inside shard_map over ``axis``)."""
    import jax

    n = jax.lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        acc, buf = carry
        buf = jax.lax.ppermute(buf, axis, perm)
        return acc + buf, buf

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (x, x))
    return acc


def reduce_scatter_state(x, axis: str):
    """psum_scatter: merge shard states AND leave each shard holding only
    its slice of the result — half the ICI traffic of psum when the
    consumer is itself sharded over the same axis (the pod-scale pattern
    for huge [S*W, F] aggregate states: merge once, keep 1/D locally).
    Call inside shard_map; the axis size must divide the leading dim."""
    import jax
    return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)


def pmax_merge_hll(registers, axis: str):
    """Exact HLL merge across shards (call inside shard_map)."""
    import jax
    return jax.lax.pmax(registers, axis)


def allgather_merge_tdigests(mean, weight, axis: str, k: Optional[int] = None):
    """Merge per-shard t-digests: all_gather centroids, weighted rebuild.

    mean/weight: [..., K] per-shard centroid arrays inside shard_map.
    Returns a merged digest replicated on every shard.
    """
    import jax
    import jax.numpy as jnp

    from anomod.ops.tdigest import tdigest_build

    k = k or mean.shape[-1]
    all_mean = jax.lax.all_gather(mean, axis, axis=-1, tiled=True)
    all_weight = jax.lax.all_gather(weight, axis, axis=-1, tiled=True)
    d = tdigest_build(all_mean, k=k, weights=all_weight, xp=jnp)
    return d.mean, d.weight
