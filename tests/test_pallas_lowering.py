"""Every Pallas kernel must LOWER for the TPU at its production shapes.

Interpret mode accepts block shapes and primitives Mosaic refuses, so a
kernel can pass every CPU test and still be unable to reach the chip (two
did: the fused lane kernel's ``(1, blk)`` block and the window gather's
in-kernel ``dynamic_slice``).  The TPU lowering runs on a CPU host, so
this catches that class of refusal in tier-1.  It does not replace the
compiled run: VMEM limits and Mosaic's own checks appear only on the
chip (tpu_tests/, chip_smoke.py).
"""

import importlib
import pkgutil

import jax
import jax.numpy as jnp
import pytest
from jax import ShapeDtypeStruct as SDS

import anomod.ops
from anomod.config import (DEFAULT_SERVE_BUCKETS,
                           DEFAULT_SERVE_LANE_BUCKETS)
from anomod.serve.engine import serve_plane_cfg

F32, I32 = jnp.float32, jnp.int32

#: bench replay plane: 45 TT services x 32 windows, block 4096
BENCH_SW, BENCH_BLOCK, N_HIST = 1440, 4096, 16
SERVE = serve_plane_cfg()


def lower_for_tpu(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))


def _case_replay():
    from anomod.ops.pallas_replay import make_pallas_replay_fn
    n = 8 * BENCH_BLOCK
    for reps in (1, 64):
        fn = make_pallas_replay_fn(BENCH_SW, N_HIST, block=BENCH_BLOCK,
                                   inner_repeats=reps)
        lower_for_tpu(fn, SDS((n,), I32), SDS((6, n), F32))


def _case_replay_sorted():
    """The replay cell's own call (272 copies of 128 staged blocks: eight
    blocks a grid step, the loop's SMEM reads of the window ids and the
    dynamic column slice), a block count no power of two divides (one
    block a step, no loop), and the on-device replication of
    ``measure_throughput`` (four a step).  Shapes only: lowering allocates
    nothing."""
    from anomod.ops.pallas_replay import make_pallas_replay_sorted_fn
    for n_blocks, reps in ((272 * 128, 1), (11_605, 1), (12, 4096)):
        t = n_blocks * BENCH_BLOCK
        fn = make_pallas_replay_sorted_fn(BENCH_SW, N_HIST, block=BENCH_BLOCK,
                                          inner_repeats=reps)
        text = lower_for_tpu(fn, SDS((t,), I32), SDS((6, t), F32),
                             SDS((n_blocks,), I32)).as_text()
        assert text.count("tpu_custom_call") == 1


def _case_lane_delta():
    """Every (width, lane-bucket) shape of the default serve grid."""
    from anomod.ops.pallas_replay import make_pallas_lane_delta_fn
    fn = make_pallas_lane_delta_fn(SERVE.sw, SERVE.n_hist_buckets)
    widths = sorted({b for b in DEFAULT_SERVE_BUCKETS
                     if b <= SERVE.chunk_size} | {SERVE.chunk_size})
    for width in widths:
        for lanes in DEFAULT_SERVE_LANE_BUCKETS:
            lower_for_tpu(fn, SDS((lanes, width), I32),
                          SDS((lanes, 6, width), F32))


def _case_window_gather():
    """The pool's power-of-two request grid over a 256-slot pool."""
    from anomod.ops.pallas_replay import make_pallas_window_gather_fn
    fn = make_pallas_window_gather_fn(SERVE.n_services, SERVE.n_windows, 6)
    for t in (1, 2, 16, 256):
        lower_for_tpu(fn, SDS((257, SERVE.sw, 6), F32), SDS((t,), I32),
                      SDS((t,), I32))


def _case_tdigest():
    """The replay digest plane (one lane per bench segment) and the
    compiled suite's 96-lane shape."""
    from anomod.ops.pallas_tdigest import make_pallas_tdigest_fn
    for rows, length in ((BENCH_SW, 1024), (96, 1024), (256, 8064)):
        fn = make_pallas_tdigest_fn(64, length)
        lower_for_tpu(fn, SDS((rows, length), I32),
                      SDS((rows, length), F32), SDS((rows, length), F32))


def _case_hll():
    from anomod.ops.pallas_hll import make_pallas_hll_fn
    fn = make_pallas_hll_fn(p=10, block=2048)
    lower_for_tpu(fn, SDS((65536,), I32))


CASES = {
    "make_pallas_replay_fn": _case_replay,
    "make_pallas_replay_sorted_fn": _case_replay_sorted,
    "make_pallas_lane_delta_fn": _case_lane_delta,
    "make_pallas_window_gather_fn": _case_window_gather,
    "make_pallas_tdigest_fn": _case_tdigest,
    "make_pallas_hll_fn": _case_hll,
}


def test_every_kernel_in_ops_has_a_lowering_case():
    """A new ``make_pallas_*_fn`` must come with its production shapes."""
    found = set()
    for mod in pkgutil.iter_modules(anomod.ops.__path__):
        m = importlib.import_module(f"anomod.ops.{mod.name}")
        found |= {n for n in vars(m)
                  if n.startswith("make_pallas_") and n.endswith("_fn")}
    assert found == set(CASES)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_tpu_at_production_shapes(name):
    CASES[name]()


def test_lowering_refuses_what_mosaic_refuses():
    """The check has teeth: the seed's lane-kernel BlockSpec — a (1, blk)
    block of an [L, W] array — is refused here, on the CPU."""
    from jax.experimental import pallas as pl

    def run(x):
        return pl.pallas_call(
            lambda x_ref, o_ref: o_ref.__setitem__(slice(None), x_ref[:]),
            grid=(8,),
            in_specs=[pl.BlockSpec((1, 256), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 256), lambda i: (i, 0)),
            out_shape=SDS((8, 256), F32))(x)

    with pytest.raises(Exception, match="divisible by 8 and 128"):
        lower_for_tpu(run, SDS((8, 256), F32))


def test_reduce_precision_still_has_no_mosaic_lowering():
    """Why the hi/lo moment split exists twice: ``replay._split_hi_lo``
    needs ``lax.reduce_precision`` (XLA:TPU elides a convert pair), and
    the Pallas TPU lowering of JAX 0.9.0 refuses that primitive, so
    ``pallas_replay._build_rhs_t`` keeps the pair.  The day this stops
    raising, make ``_build_rhs_t`` call ``_split_hi_lo`` and delete this."""
    from jax.experimental import pallas as pl

    from anomod.replay import _split_hi_lo

    def body(x_ref, o_ref):
        hi, lo = _split_hi_lo(x_ref[:])
        o_ref[:] = hi.astype(F32) + lo.astype(F32)

    def run(x):
        return pl.pallas_call(body, out_shape=SDS((8, 128), F32))(x)

    with pytest.raises(NotImplementedError, match="reduce_precision"):
        lower_for_tpu(run, SDS((8, 128), F32))
