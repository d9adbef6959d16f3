"""Ring attention — sequence-parallel exact attention over a mesh axis.

Long-context is first-class in this framework: a full experiment corpus is a
span sequence far larger than one chip's HBM wants to hold at attention
granularity.  Ring attention shards the sequence across the mesh's data axis
and rotates K/V blocks around the ring with ``jax.lax.ppermute`` (ICI
neighbor exchange — each step overlaps a block's worth of compute with a
block transfer), accumulating the exact softmax with the online
(max/denominator-carrying) recurrence.  After P steps every query block has
attended to every key block: numerically identical to full attention, with
per-chip memory O(L/P · L/P) instead of O(L²).

No reference counterpart (SURVEY.md §5: long-context/sequence parallelism
absent there); the design follows the public blockwise-attention recipe, on
XLA collectives instead of NCCL.
"""

from __future__ import annotations

import functools

import numpy as np


def full_attention(q, k, v):
    """Reference dense softmax attention.  [L, H, D] -> [L, H, D]."""
    import jax.numpy as jnp
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    p = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    return jnp.einsum("hqk,khd->qhd", p, v)


def ring_attention_local(q, k, v, axis_name: str):
    """Exact attention over the ring — call inside shard_map.

    Args are the *local* blocks [L/P, H, D]; the full sequence is the
    concatenation over the ``axis_name`` mesh axis.  Returns the local output
    block [L/P, H, D].
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)            # ring size
    perm = [(i, (i + 1) % n) for i in range(n)]
    scale = 1.0 / np.sqrt(q.shape[-1])
    Lq, H, D = q.shape

    def block(q, kb, vb, num, den, m):
        """One online-softmax accumulation step against K/V block (kb, vb)."""
        scores = jnp.einsum("qhd,khd->qhk", q, kb) * scale   # [Lq, H, Lk]
        m_new = jnp.maximum(m, scores.max(axis=-1))          # [Lq, H]
        p = jnp.exp(scores - m_new[..., None])
        correction = jnp.exp(m - m_new)
        num = num * correction[..., None] + jnp.einsum("qhk,khd->qhd", p, vb)
        den = den * correction + p.sum(axis=-1)
        return num, den, m_new

    def body(_, carry):
        kb, vb, num, den, m = carry
        num, den, m = block(q, kb, vb, num, den, m)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return kb, vb, num, den, m

    def _varying(x):
        # fresh constants are unvarying over the mesh axis; the loop carry
        # must match the varying outputs (shard_map vma checking)
        return lax.pcast(x, (axis_name,), to="varying")

    num0 = jnp.zeros_like(q)
    den0 = _varying(jnp.zeros((Lq, H), q.dtype))
    m0 = _varying(jnp.full((Lq, H), -jnp.inf, q.dtype))
    _, _, num, den, _ = lax.fori_loop(0, n, body, (k, v, num0, den0, m0))
    return num / den[..., None]


def make_sharded_attention(local_fn, mesh, axis: str = "data"):
    """Shared jit/shard_map wrapper for every sequence-parallel attention
    plane: q/k/v [L, H, D] sharded on L over ``axis``, output sharded the
    same way, ``local_fn(q, k, v, axis_name)`` runs on the local blocks.
    One copy so a shard_map/sharding API migration lands everywhere."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(axis, None, None)

    @functools.partial(jax.jit, out_shardings=NamedSharding(mesh, spec))
    def attend(q, k, v):
        fn = jax.shard_map(
            functools.partial(local_fn, axis_name=axis),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)

    return attend


def make_ring_attention(mesh, axis: str = "data"):
    """Jitted global-array form: q/k/v [L, H, D] sharded on L over ``axis``.

    L must divide evenly by the mesh axis size (pad upstream; static shapes
    keep XLA on one compiled program).
    """
    return make_sharded_attention(ring_attention_local, mesh, axis)
