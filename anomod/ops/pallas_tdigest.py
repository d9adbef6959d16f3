"""Pallas TPU kernel for fixed-K t-digest build/merge (BASELINE.json mandate).

Work split (TPU-first): the t-digest *scale pass* — sort by value, cumulative
weight, k1 scale function k(q) = K·(asin(2q−1)/π + ½) — is cheap elementwise/
sort work that XLA fuses well (and Mosaic lacks asin), so it stays in jax;
the *reduction pass* — bucketed segment mean/weight over every (service,
edge, metric) lane — is the bandwidth-heavy part and runs here as one fused
kernel: per lane, a [K, L] one-hot built in VMEM contracts against the
[L, 2] (weight, weight·value) plane on the MXU, producing the [K, 2]
centroid state without materializing the one-hot in HBM (the jax path's
[R, L, K] broadcast is the thing this kernel deletes).

Merge = concatenate centroid sets and rebuild with the same kernel (the
classic weighted-rebuild merge of anomod.ops.tdigest.tdigest_merge).

Numerics match anomod.ops.tdigest.tdigest_build exactly (same bucket rule,
same mean = Σwv/Σw), so the numpy oracle is the parity reference; interpret
mode covers CPU test runs.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np


@functools.lru_cache(maxsize=64)
def make_pallas_tdigest_fn(n_centroids: int, length: int,
                           interpret: bool = False):
    """Returns fn(bucket[R, L] int32, w[R, L] f32, wv[R, L] f32)
    -> (mean[R, K] f32, weight[R, K] f32).

    ``bucket`` holds precomputed scale-function buckets in [0, K); rows are
    independent digest lanes (vmap is the grid, not program logic).  Padding
    slots carry w == 0 and any in-range bucket.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K = n_centroids
    L = length
    # Mosaic requires the sublane (second-to-last) block dim to be a
    # multiple of 8 or the full array dim; one digest lane per block
    # violates that (caught by the compiled-parity TPU suite — interpret
    # mode accepts any block shape), so each block carries SUB=8 lanes and
    # the kernel unrolls the per-lane MXU contraction across sublanes.
    SUB = 8

    def kernel(bucket_ref, w_ref, wv_ref, mean_ref, weight_ref):
        # [L, K] centroid iota shared by every sublane's one-hot
        iota = jax.lax.broadcasted_iota(jnp.int32, (L, K), 1)
        for r in range(SUB):
            bucket = bucket_ref[r]                  # [L] int32
            w = w_ref[r]                            # [L]
            wv = wv_ref[r]                          # [L]
            # one-hot in VMEM; contract on the MXU: [K, L] @ [L, 2]
            onehot = (iota == bucket[:, None]).astype(jnp.float32)
            rhs = jnp.stack([w, wv], axis=1)        # [L, 2]
            acc = jax.lax.dot_general(
                onehot, rhs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)  # [K, 2]
            wk = acc[:, 0]
            weight_ref[r] = wk
            mean_ref[r] = jnp.where(
                wk > 0, acc[:, 1] / jnp.where(wk > 0, wk, 1.0), 0.0)

    @jax.jit
    def run(bucket, w, wv):
        R = bucket.shape[0]
        assert bucket.shape == w.shape == wv.shape == (R, L)
        pad = (-R) % SUB
        if pad:  # padding lanes carry w == 0 -> zero weight, zero mean
            bucket = jnp.pad(bucket, ((0, pad), (0, 0)))
            w = jnp.pad(w, ((0, pad), (0, 0)))
            wv = jnp.pad(wv, ((0, pad), (0, 0)))
        Rp = R + pad
        out_shape = (jax.ShapeDtypeStruct((Rp, K), jnp.float32),
                     jax.ShapeDtypeStruct((Rp, K), jnp.float32))
        mean, weight = pl.pallas_call(
            kernel,
            grid=(Rp // SUB,),
            in_specs=[pl.BlockSpec((SUB, L), lambda i: (i, 0))] * 3,
            out_specs=[pl.BlockSpec((SUB, K), lambda i: (i, 0))] * 2,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(bucket.astype(jnp.int32), w.astype(jnp.float32),
          wv.astype(jnp.float32))
        return mean[:R], weight[:R]

    return run


def _scale_pass(values, weights, k: int):
    """jax prolog: sort by value, cumulative weight, k1 scale buckets."""
    import jax.numpy as jnp

    order = jnp.argsort(values, axis=-1)
    v = jnp.take_along_axis(values, order, axis=-1)
    w = jnp.take_along_axis(weights, order, axis=-1)
    cum = jnp.cumsum(w, axis=-1)
    total = cum[..., -1:]
    q = (cum - 0.5 * w) / jnp.where(total > 0, total, 1.0)
    z = jnp.clip(2.0 * q - 1.0, -1.0, 1.0)
    s = (jnp.arcsin(z) / np.pi + 0.5) * k
    bucket = jnp.clip(s.astype(jnp.int32), 0, k - 1)
    return bucket, w, w * v


def tdigest_build_pallas(values, k: int = 64, weights=None,
                         interpret: bool = False):
    """Drop-in Pallas variant of tdigest.tdigest_build (leading dims = lanes).

    Returns a TDigest NamedTuple with [..., K] mean/weight arrays.
    """
    import jax.numpy as jnp

    from anomod.ops.tdigest import TDigest

    values = jnp.asarray(values, jnp.float32)
    if weights is None:
        weights = jnp.ones_like(values)
    lead = values.shape[:-1]
    L = values.shape[-1]
    bucket, w, wv = _scale_pass(values, jnp.asarray(weights, jnp.float32), k)
    R = int(np.prod(lead)) if lead else 1
    fn = make_pallas_tdigest_fn(k, L, interpret=interpret)
    mean, weight = fn(bucket.reshape(R, L), w.reshape(R, L), wv.reshape(R, L))
    return TDigest(mean=mean.reshape(*lead, k), weight=weight.reshape(*lead, k))


def tdigest_by_segment_pallas(values, segment_ids, n_segments: int,
                              k: int = 64, interpret=None):
    """Per-segment digests through the Mosaic kernel — the TPU featurization
    fast path with the same contract as tdigest.tdigest_by_segment.

    Host :func:`anomod.ops.tdigest.segment_pad` staging (lane dim rounded to
    128 for TPU layout + compile-cache stability), then ONE fused build over
    all segment lanes.  ``interpret=None`` auto-selects: compiled on a TPU
    backend, interpret mode elsewhere (so the same call works on the CPU
    test mesh).
    """
    import numpy as _np

    from anomod.ops.tdigest import segment_pad

    if interpret is None:
        import jax
        interpret = jax.default_backend() != "tpu"
    padded, weights = segment_pad(_np.asarray(values, _np.float32),
                                  _np.asarray(segment_ids), n_segments,
                                  pad_to=128)
    return tdigest_build_pallas(padded, k=k, weights=weights,
                                interpret=interpret)


def tdigest_merge_pallas(a, b, interpret: bool = False):
    """Merge two digest lanes by weighted rebuild through the kernel."""
    import jax.numpy as jnp

    k = a.mean.shape[-1]
    values = jnp.concatenate([a.mean, b.mean], axis=-1)
    weights = jnp.concatenate([a.weight, b.weight], axis=-1)
    return tdigest_build_pallas(values, k=k, weights=weights,
                                interpret=interpret)
