"""The device pool's row ops compiled for a described v5e, with no chip.

What PR 27 found on the chip (PERF.md section 6): a ``[slots, SW, F]``
plane is resident slot-MINOR on the TPU, so the donated scatter fold
transposed the whole pool and back on every dispatch.  The planes are
flat rows held at a multiple of the lane tile now, and the programs
below must stay free of any op over a whole plane except the in-place
update itself.  The TPU's compiler is installed here and compiles for a
chip that is described and not attached; ``tpu_tests/test_pool_layout.py``
is the same guard on the chip, with times.

All of it in this one file, the topology described inside a fixture:
only one process at a time may load the TPU's library.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax import ShapeDtypeStruct as SDS

from anomod.replay import N_FEATS, ReplayConfig, TenantStatePool

#: the benchmark's fleet cell: 34,500 tenants and the dead row, TT shape
FLEET_ROWS, LANES = 34501, 32
SHAPES = {"tt": (45, 32), "sn": (12, 32)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def optimizing():
    """tests/conftest.py turns most XLA optimizations off for the CPU
    suite; the programs read here are the chip's, so they are compiled
    as the chip compiles them, and never through the persistent cache
    (an entry compiled for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = (jax.config.read("jax_disable_most_optimizations"),
           jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_disable_most_optimizations", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_disable_most_optimizations", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])
    compilation_cache.reset_cache()


def plane_ops(text: str, rows: int):
    """(name, opcode) of every instruction of the entry computation whose
    result has ``rows`` leading rows, parameters and tuples aside."""
    entry = text[text.index("ENTRY"):]
    found = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([a-z\-]+)\(", line)
        if m and m.group(3) not in ("parameter", "tuple", "bitcast",
                                    "get-tuple-element") \
                and f"[{rows}," in m.group(2):
            found.append((m.group(1), m.group(3)))
    return found


def _programs(shape, sharding):
    cfg = ReplayConfig(n_services=shape[0], n_windows=shape[1],
                       window_us=5_000_000, chunk_size=4096)
    pool = TenantStatePool(cfg, capacity=1, engine="jax")

    def sds(dims, dtype=jnp.float32):
        return SDS(dims, dtype, sharding=sharding)

    agg = sds((FLEET_ROWS, pool.agg.shape[1]))
    hist = sds((FLEET_ROWS, pool.hist.shape[1]))
    row_a = sds((cfg.sw, N_FEATS))
    row_h = sds((cfg.sw, cfg.n_hist_buckets))
    i32 = jnp.int32
    return {
        "scatter": (pool._scatter_fn,
                    (agg, hist, sds((LANES,), i32),
                     sds((LANES,) + row_a.shape), sds((LANES,) + row_h.shape))),
        "put": (pool._put_fn, (agg, hist, sds((), i32), row_a, row_h)),
        "roll": (pool._roll_fn, (agg, hist, sds((), i32), sds((), i32))),
        "gather_window": (pool._gather_window_fn,
                          (agg, sds((1024,), i32), sds((1024,), i32))),
    }, (agg, hist)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("op", ["scatter", "put", "roll", "gather_window"])
def test_pool_op_compiles_with_no_whole_plane_op(one_chip, optimizing,
                                                 op, shape):
    programs, (agg, hist) = _programs(SHAPES[shape], one_chip)
    fn, args = programs[op]
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    # the planes are resident row-major: a tenant's row is contiguous
    for plane in (agg, hist) if op != "gather_window" else (agg,):
        dims = ",".join(map(str, plane.shape))
        assert f"f32[{dims}]{{1,0:T(8,128)}}" in text.split("ENTRY")[0]
    found = plane_ops(text, FLEET_ROWS)
    mem = compiled.memory_analysis()
    if op == "gather_window":
        assert found == []
        return
    # one in-place update per plane and nothing else plane-sized: no
    # copy, no transpose, no temporary the size of a plane
    assert sorted(code for _, code in found) == (
        ["fusion", "fusion"] if op == "scatter"
        else ["dynamic-update-slice"] * 2), found
    assert mem.temp_size_in_bytes < 4 * agg.shape[1] * 1024
    assert mem.alias_size_in_bytes >= 4 * FLEET_ROWS * (
        agg.shape[1] + hist.shape[1])


# -- the sequence model's latent pool (PR 28) ---------------------------------

def _compiled_seq_step(one_chip, grid_size):
    """``(compiled step, the pool's shape)`` of ``k2-fleet-overload`` at
    its published widths and its real pool, at the token grid's least or
    largest size (``grid_size``: ``min`` or ``max``)."""
    import json
    import os

    import numpy as np

    from anomod.models import latent_moe as lm

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-k2-ep32-share.json")) as f:
        spec = json.load(f)
    cfg = lm.DecoderConfig.from_dict(spec)
    assert cfg.pool_row_width == 640 and cfg.pool_blocks == 3484

    def sds(shape, dtype):
        return SDS(tuple(shape), dtype, sharding=one_chip)

    flat = {}
    for name, leaf in lm.param_shapes(cfg).items():
        flat[name] = (
            {k: sds(s, jnp.float32 if k in lm.F32_LEAVES else jnp.bfloat16)
             for k, (s, _) in leaf.items()} if isinstance(leaf, dict)
            else sds(leaf[0], jnp.float32 if name in lm.F32_LEAVES
                     else jnp.bfloat16))
    tokens = grid_size(spec["assumed"]["token_grid"])
    caps = lm.plan_caps(cfg, tokens, 2 * spec["fleet"]["n_tenants"] + 64)
    plan = jax.tree_util.tree_map(
        lambda a: sds(np.shape(a), jnp.int32), lm.empty_plan(cfg, caps, 0))
    pool = sds((cfg.num_hidden_layers, cfg.pool_blocks, cfg.block_tokens,
                cfg.pool_row_width), jnp.bfloat16)
    h_last = sds((spec["fleet"]["n_tenants"] + 1, cfg.hidden_size),
                 jnp.bfloat16)
    step = jax.jit(lambda p, pool, h, plan: lm.append_step(
        cfg, p, pool, h, plan), donate_argnums=(1, 2))
    return step.lower(flat, pool, h_last, plan).compile(), pool.shape


def test_seq_step_writes_the_latent_pool_in_place(one_chip, optimizing):
    """The serving step of ``k2-fleet-overload`` at its published widths
    and its real pool, compiled for the chip: the donated pool comes back
    in its own buffer, the program's temporaries stay far under the
    pool's size (no pool-shaped copy: a ``[.., 128, 576]`` row would be
    laid out token-minor and copied whole around every write; the row is
    held at 640 columns), and everything fits the chip beside the
    weights."""
    import numpy as np

    from anomod.models import latent_moe as lm

    compiled, pool_shape = _compiled_seq_step(one_chip, min)
    mem = compiled.memory_analysis()
    # the attention kernels' device ops carry their call's name in the
    # metadata a trace keeps (what `mla_append_roofline` finds them by)
    assert f'/{lm.ATTENTION_SCOPE}/while/body/' in compiled.as_text()
    from anomod.models import seqcommon
    flat = f"bf16[{pool_shape[0] * pool_shape[1] * pool_shape[2]},640]"
    writes = pool_writes(compiled.as_text(), flat)
    assert writes and all(
        w.endswith(f"/{seqcommon.PROJ_SCOPE}/scatter") for w in writes)
    pool_bytes = 2 * int(np.prod(pool_shape))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


def test_seq_step_absorbed_form_is_one_mosaic_kernel(one_chip, optimizing):
    """The same step at the grid's LARGEST size (PR 30): the absorbed form
    lowers through Mosaic as one custom call a layer stack that carries
    the attention's call name (what ``mla_append_roofline`` finds it by)
    and no copy of the pool is made for it; the only loops left under
    that name are the expanded form's two; the pool is still aliased and
    the program fits the chip."""
    import numpy as np

    from anomod.models import latent_moe as lm

    compiled, pool_shape = _compiled_seq_step(one_chip, max)
    text = compiled.as_text()
    flat_pool = f"bf16[{pool_shape[0] * pool_shape[1]},"
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"/{lm.ATTENTION_SCOPE}/" in line]
    # one in the dense layers' scan, one in the routed layers'
    assert len(kernels) == 2 and all("/pallas_call" in k for k in kernels)
    assert flat_pool in text and not re.search(
        rf"= {re.escape(flat_pool)}[^=\n]* copy\(", text)
    loops = re.findall(rf'while\([^\n]*op_name="[^"]*/{lm.ATTENTION_SCOPE}/'
                       r'([^"]*)"', text)
    assert sorted(loops) == ["while", "while", "while/body/while",
                             "while/body/while"], loops
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool_shape))
    assert mem.alias_size_in_bytes >= pool_bytes
    # at twice the other test's tokens: 2.07e9 of temporaries (1.78e9
    # with the loop: the kernel's [tokens, heads, latent] result beside
    # its head-major copy for the value half), and the program fits the
    # chip's 16.9e9 with 0.5e9 left for the sketch planes' state
    assert mem.temp_size_in_bytes < 0.55 * pool_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


def pool_writes(text: str, shape: str) -> list:
    """The ``op_name`` of every fusion of a compiled program whose result
    is the flat pool ``shape``: the in-place row writes.  Written on one
    flattened index (``seqcommon.write_rows``, PR 38) the scatter keeps
    its call's name; a write on two indices is rewritten by the compiler
    into an op with no metadata, which no trace reduction can place."""
    lines = [line for line in text.splitlines()
             if re.search(rf"= {re.escape(shape)}[^=\n]* fusion\(", line)]
    return [(re.search(r'op_name="([^"]*)"', line) or [None, ""])[1]
            for line in lines]


def mosaic_kernels(text: str, scope: str) -> int:
    """The Mosaic custom calls of a compiled program whose metadata names
    the call ``scope``; no loop may be left under that name."""
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"/{scope}/" in line]
    assert all("/pallas_call" in k for k in kernels)
    assert not re.search(rf'while\([^\n]*op_name="[^"]*/{scope}/', text)
    return len(kernels)


# -- the hybrid model's two kinds of cache (PR 34) ----------------------------

@pytest.mark.parametrize("grid_size", [min, max], ids=["min", "max"])
def test_hybrid_step_writes_the_slot_pool_and_the_kv_pool_in_place(
        one_chip, optimizing, grid_size):
    """The serving step of ``n3s-fleet-overload`` at its published widths
    and its real pools (640 state slots of 10.8 MB, 524,288 cached
    tokens), compiled for the chip at the token grid's least and largest
    size: every donated pool comes back in its own buffer, no op copies a
    slot pool whole (a state is ``[128, 8192]``, a slot's convolution
    tail one row of 30,720), the recurrence is the Mosaic kernel (PR 35:
    one custom call a Mamba layer under the scan's call name, which is
    what ``ssm_scan_roofline`` finds it by, and no loop left there), so is
    attention (PR 37: one custom call an attention layer under its call
    name, which is what ``gqa_append_roofline`` finds it by, no loop), and
    everything fits the chip beside the weights."""
    import json
    import os

    import numpy as np

    from anomod.models import hybrid_ssm_moe as hm
    from anomod.ops import gqa_attention, ssm_scan

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3-super-ep8-share.json")) as f:
        spec = json.load(f)
    cfg = hm.HybridConfig.from_dict(spec)

    def sds(shape, dtype):
        return SDS(tuple(shape), dtype, sharding=one_chip)

    params = {}
    for name, leaf in hm.param_shapes(cfg).items():
        floats = lambda k, rule: jnp.float32 if (
            k in hm.F32_LEAVES or rule is None or rule == "bias"
            or callable(rule)) else jnp.bfloat16
        params[name] = (
            {k: sds(s, floats(k, r)) for k, (s, r) in leaf.items()}
            if isinstance(leaf, dict) else sds(leaf[0], floats(name,
                                                               leaf[1])))
    n_tenants = spec["fleet"]["n_tenants"]
    tokens = grid_size(spec["assumed"]["token_grid"])
    caps = hm.plan_caps(cfg, tokens, 2 * n_tenants + 64)
    plan = jax.tree_util.tree_map(
        lambda a: sds(np.shape(a), jnp.int32), hm.empty_plan(cfg, caps, 0))
    state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: hm.init_state(cfg, n_tenants)))
    assert state["ssm"].shape == (5, 640, 128, 8192)
    assert state["conv"].shape == (5, 640, 30720)
    assert state["pool"].shape == (1, 4096, 128, 512)
    step = jax.jit(lambda p, s, plan: hm.append_step(cfg, p, s, plan),
                   donate_argnums=(1,))
    compiled = step.lower(params, state, plan).compile()
    text = compiled.as_text()
    for shape in ("bf16[5,640,128,8192]", "bf16[5,640,30720]",
                  "bf16[3200,30720]", "bf16[524288,512]"):
        assert not re.search(rf"= {re.escape(shape)}[^=\n]* copy\(", text)
    for scope, layers in ((ssm_scan.SCOPE, cfg.count("mamba")),
                          (gqa_attention.SCOPE, cfg.count("attn"))):
        assert mosaic_kernels(text, scope) == layers
    from anomod.models import seqcommon
    for shape, scope, layers in (
            ("bf16[3200,30720]", hm.CONV_SCOPE, cfg.count("mamba")),
            ("bf16[524288,512]", seqcommon.PROJ_SCOPE, cfg.count("attn"))):
        writes = pool_writes(text, shape)
        assert len(writes) >= layers and all(
            w.endswith(f"/{scope}/scatter") for w in writes), writes
    mem = compiled.memory_analysis()
    held = sum(2 * int(np.prod(a.shape)) for a in state.values())
    assert held > 7.4e9 and mem.alias_size_in_bytes >= held
    # 1.07e9 / 1.86e9 of temporaries at 4,096 / 8,192 tokens (the Mamba
    # projection's float32 output is 0.3e9 / 0.6e9 of it; the scan's own
    # are its operands laid out by group, 0.04e9); weights 5.5e9 and pools
    # 7.5e9
    assert mem.temp_size_in_bytes < (1.2e9 if tokens == 4096 else 2.0e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


# -- the window-and-full model's two K/V pools (PR 36) ------------------------

@pytest.mark.parametrize("grid_size", [min, max], ids=["min", "max"])
def test_swa_step_writes_both_kv_pools_in_place(one_chip, optimizing,
                                                grid_size):
    """The serving step of ``lxs2-fleet-overload`` at its published widths
    and its real pools, compiled for the chip at the token grid's least
    and largest size: both donated K/V pools come back in their own
    buffers, no op copies a pool whole (a row is 2,048 columns: 16 lane
    tiles), each kind's attention is the Mosaic kernel (PR 37: one custom
    call a layer of the kind under the kind's call name, which is what
    ``full_append_roofline`` and ``swa_append_roofline`` find it by, and
    no loop left there), and everything fits the chip beside the 7.74 GB
    of weights."""
    import json
    import os

    import numpy as np

    from anomod.models import swa_moe as wm
    from anomod.ops import gqa_attention

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-xs2-pp8-stage.json")) as f:
        spec = json.load(f)
    cfg = wm.SwaMoeConfig.from_dict(spec)

    def sds(shape, dtype):
        return SDS(tuple(shape), dtype, sharding=one_chip)

    params = {}
    for name, leaf in wm.param_shapes(cfg).items():
        floats = lambda k, rule: jnp.float32 if (
            k in wm.F32_LEAVES or rule is None) else jnp.bfloat16
        params[name] = (
            {k: sds(s, floats(k, r)) for k, (s, r) in leaf.items()}
            if isinstance(leaf, dict) else sds(leaf[0], floats(name,
                                                               leaf[1])))
    n_tenants = spec["fleet"]["n_tenants"]
    tokens = grid_size(spec["assumed"]["token_grid"])
    caps = wm.plan_caps(cfg, tokens, 2 * n_tenants + 64)
    plan = jax.tree_util.tree_map(
        lambda a: sds(np.shape(a), jnp.int32), wm.empty_plan(cfg, caps, 0))
    state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: wm.init_state(cfg, n_tenants)))
    a = spec["assumed"]
    n_full, n_win = a["pool_tokens"] // 128, a["window_blocks"]
    assert state["pool"].shape == (2, n_full, 128, 2048)
    assert state["wpool"].shape == (3, n_win, 128, 2048)
    step = jax.jit(lambda p, s, plan: wm.append_step(cfg, p, s, plan),
                   donate_argnums=(1,))
    compiled = step.lower(params, state, plan).compile()
    text = compiled.as_text()
    for rows in (2 * n_full, 3 * n_win):
        for shape in (f"bf16[{rows},128,2048]", f"bf16[{rows * 128},2048]"):
            assert not re.search(rf"= {re.escape(shape)}[^=\n]* copy\(",
                                 text), shape
        writes = pool_writes(text, f"bf16[{rows * 128},2048]")
        from anomod.models import seqcommon
        assert writes and all(
            w.endswith(f"/{seqcommon.PROJ_SCOPE}/scatter") for w in writes)
    for scope, kind in ((gqa_attention.SCOPE, wm.FULL),
                        (gqa_attention.SWA_SCOPE, wm.SWA)):
        assert mosaic_kernels(text, scope) == cfg.count(kind)
    mem = compiled.memory_analysis()
    held = sum(2 * int(np.prod(s.shape)) for s in state.values())
    weights = 2 * 3_869_857_792 + 2 * 4 * 524_288   # the routers in float32
    assert mem.alias_size_in_bytes >= held
    assert abs(mem.argument_size_in_bytes - held - weights) < 0.05e9
    print("MEM", tokens, mem.argument_size_in_bytes, mem.temp_size_in_bytes,
          mem.alias_size_in_bytes, held)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
