"""Pallas TPU kernel for HyperLogLog register updates.

Computes murmur-style hashing, bucket/rank extraction, and the register max
entirely in VMEM across grid steps: per block, a [B, m] one-hot of bucket ids
carries each item's rank, a VPU max-reduce collapses it to [m], and the
register vector accumulates with ``jnp.maximum`` (revisited output block).

Used for single-sketch (global) cardinalities; the per-lane variant stays on
the XLA scatter-max path (anomod.ops.hll / anomod.replay hll plane).
"""

from __future__ import annotations


def make_pallas_hll_fn(p: int = 10, block: int = 2048, interpret: bool = False):
    """Returns fn(items int32 [N]) -> registers int32 [2^p]; N % block == 0."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = 1 << p

    def kernel(items_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            out_ref[:] = jnp.zeros_like(out_ref)

        x = items_ref[:].astype(jnp.uint32)
        # murmur3 fmix32 avalanche (matches anomod.ops.hll._avalanche32)
        def fmix(v):
            v = v ^ (v >> jnp.uint32(16))
            v = v * jnp.uint32(0x85EBCA6B)
            v = v ^ (v >> jnp.uint32(13))
            v = v * jnp.uint32(0xC2B2AE35)
            return v ^ (v >> jnp.uint32(16))

        h = fmix(x)
        bucket = (h >> jnp.uint32(32 - p)).astype(jnp.int32)      # [B]
        h2 = fmix(h ^ jnp.uint32(0x9E3779B9))
        # branchless clz via bit shifts (Mosaic has no uint32->float cast)
        v = h2
        hi = jnp.zeros_like(bucket)                               # msb index
        for s in (16, 8, 4, 2, 1):
            t = v >> jnp.uint32(s)
            nz = t != jnp.uint32(0)
            hi = jnp.where(nz, hi + s, hi)
            v = jnp.where(nz, t, v)
        clz = jnp.where(h2 != jnp.uint32(0), 31 - hi, jnp.int32(32))
        rank = jnp.minimum(clz + 1, jnp.int32(32))                # [B]
        # [B, m] one-hot carrying ranks, VPU max-reduce over B
        m_iota = jax.lax.broadcasted_iota(jnp.int32, (block, m), 1)
        cand = jnp.where(m_iota == bucket[:, None], rank[:, None], 0)
        out_ref[:] = jnp.maximum(out_ref[:], jnp.max(cand, axis=0))

    @jax.jit
    def run(items):
        n = items.shape[0]
        assert n % block == 0, f"item count {n} must be a multiple of {block}"
        return pl.pallas_call(
            kernel,
            grid=(n // block,),
            in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
            out_specs=pl.BlockSpec((m,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((m,), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(items)

    return run
