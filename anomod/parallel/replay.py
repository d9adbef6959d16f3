"""Pod-sharded span replay: shard_map over the data axis, psum state merge.

Each chip scans its shard of the span stream with the single-chip replay
kernel (anomod.replay); the tiny per-chip state ([S*W, F] aggregates +
[S*W, H] histograms) is ``psum``-merged over ICI at the end — the TPU-native
version of the reference's per-worker collection + host-side merge
(trace_collector.py:519-547's ThreadPoolExecutor + list append).
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from anomod.replay import (N_FEATS, ReplayConfig, ReplayState,
                           ThroughputResult, make_chunk_step, pallas_block)
from anomod.schemas import SpanBatch


def make_sharded_replay_fn(cfg: ReplayConfig, mesh, axis: str = "data",
                           kernel: str = "xla", with_hll: bool = False,
                           merge: str = "replicated"):
    """Pod-sharded replay over the mesh's data axis.

    ``kernel`` selects the per-shard aggregation: "xla" scans chunks with
    the shared :func:`anomod.replay.make_chunk_step` (identical
    split-precision scheme to the single-chip path), "pallas" flattens the
    shard and runs the fused kernel (anomod.ops.pallas_replay — the
    single-chip fast path, composed with shard_map + psum; interpret mode
    off-TPU).

    ``with_hll`` adds the per-service distinct-trace HLL plane: each shard
    scatter-maxes its trace ids into [n_services, 2^p] registers, merged
    over ICI with one ``pmax`` (register-exact — the sketch-state
    allreduce BASELINE.json mandates, in the production replay path).

    ``merge`` selects the agg/hist reduction: "replicated" (one ``psum``,
    every device holds the full merged state) or "scattered"
    (``psum_scatter``: half the ICI traffic, each device keeps only its
    SW/D slice of the segment axis — the pod-scale mode for aggregate
    states too large to replicate; requires SW % n_devices == 0).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if kernel not in ("xla", "pallas"):
        raise ValueError(f"unknown replay kernel {kernel!r}")
    if merge not in ("replicated", "scattered"):
        raise ValueError(f"unknown merge mode {merge!r}")
    SW, H = cfg.sw, cfg.n_hist_buckets
    n_dev = int(mesh.shape[axis])
    if merge == "scattered" and SW % n_dev != 0:
        raise ValueError(
            f"merge='scattered' needs SW ({SW}) divisible by the "
            f"{axis} axis size ({n_dev})")
    if kernel == "pallas":
        from anomod.ops.pallas_replay import make_pallas_replay_fn
        interpret = mesh.devices.ravel()[0].platform != "tpu"
        pfn = make_pallas_replay_fn(cfg.sw, cfg.n_hist_buckets,
                                    block=pallas_block(cfg.chunk_size),
                                    interpret=interpret)

    def _shard_hll(chunks):
        # whole-shard register build: one scatter-max over the flat shard
        # through the shared plane definition (anomod.replay)
        from anomod.replay import hll_scatter_update
        regs = jnp.zeros((cfg.n_services, cfg.hll_m), jnp.int32)
        return hll_scatter_update(regs, chunks["sid"].reshape(-1),
                                  chunks["tid"].reshape(-1), cfg)

    def shard_body(chunks):  # runs per-device on its [N/D, C] shard
        if kernel == "pallas":
            from anomod.replay import stage_pallas_planes
            sid, planes = stage_pallas_planes(chunks, xp=jnp)
            acc = pfn(sid, planes)
            state = ReplayState(agg=acc[:, :N_FEATS], hist=acc[:, N_FEATS:])
        else:
            # the carry is device-varying from step 1 on, so the initial
            # zeros must be cast to varying over the data axis too
            state = ReplayState(
                agg=jax.lax.pcast(jnp.zeros((SW, N_FEATS), jnp.float32),
                                  (axis,), to="varying"),
                hist=jax.lax.pcast(jnp.zeros((SW, H), jnp.float32),
                                   (axis,), to="varying"))
            state, _ = jax.lax.scan(make_chunk_step(cfg), state, chunks)
        hll = None
        if with_hll:
            from anomod.parallel.collectives import pmax_merge_hll
            hll = pmax_merge_hll(_shard_hll(chunks), axis)
        # merge shard states over ICI
        if merge == "scattered":
            from anomod.parallel.collectives import reduce_scatter_state
            return ReplayState(agg=reduce_scatter_state(state.agg, axis),
                               hist=reduce_scatter_state(state.hist, axis),
                               hll=hll)
        return ReplayState(agg=jax.lax.psum(state.agg, axis),
                           hist=jax.lax.psum(state.hist, axis),
                           hll=hll)

    # the pallas kernel's internal constants (iota tiles, zero-init) carry
    # no mesh varying-axes metadata, so shard_map's static vma checker
    # rejects the mix unconditionally (interpret or compiled, with or
    # without a declared output vma); JAX's documented workaround is
    # check_vma=False — psum merge semantics are unchanged, only the
    # static checker is off for this variant
    kwargs = {"check_vma": False} if kernel == "pallas" else {}
    state_spec = P(axis) if merge == "scattered" else P()
    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=({k: P(axis) for k in
                   ("sid", "dur", "dur_raw", "err", "s5", "valid",
                    "tid")},),
        out_specs=ReplayState(agg=state_spec, hist=state_spec,
                              hll=P() if with_hll else None),
        **kwargs)
    return jax.jit(fn)


def stage_sharded(batch: SpanBatch, mesh, cfg: ReplayConfig):
    """Stage + device-put the span columns sharded over the mesh's data
    axis; returns (dev_chunks, n_real_spans)."""
    import jax
    from anomod.replay import stage_columns
    from anomod.parallel.mesh import shard_chunks

    n_dev = mesh.devices.size
    chunks_np, n = stage_columns(batch, cfg)
    sharded = shard_chunks(chunks_np, n_dev, dead_sid=cfg.sw)
    # flatten back to [N_total, C] with device-major order for sharding
    flat = {k: v.reshape(-1, v.shape[-1]) for k, v in sharded.items()}
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("data"))
    return {k: jax.device_put(v, sharding) for k, v in flat.items()}, n


def sharded_throughput(batch: SpanBatch, mesh,
                       cfg: Optional[ReplayConfig] = None,
                       repeats: int = 3,
                       kernel: str = "xla") -> ThroughputResult:
    """Stage, shard, compile, and time the multi-chip replay."""
    import jax

    cfg = cfg or ReplayConfig(n_services=len(batch.services))
    dev_chunks, n = stage_sharded(batch, mesh, cfg)
    fn = make_sharded_replay_fn(cfg, mesh, kernel=kernel)
    t0 = time.perf_counter()
    out = fn(dev_chunks)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(dev_chunks)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    # same wall_s contract as the single-chip path: median of the raw
    # per-repeat walls, with the full trail on raw_wall_s
    wall = sorted(times)[len(times) // 2]
    return ThroughputResult(n_spans=n, wall_s=wall,
                            spans_per_sec=n / wall, compile_s=compile_s,
                            kernel=kernel, raw_wall_s=tuple(times),
                            state=np.concatenate(
                                [np.asarray(out.agg), np.asarray(out.hist)],
                                axis=1))
