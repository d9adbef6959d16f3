"""The paged grouped-query attention kernel Mosaic-compiled on the chip at
the real shapes of its three call sites (``lxs2-fleet-overload``'s full
layers: 48 heads over 8 key-value heads; its sliding layers: 64 heads,
window 512; ``n3s-fleet-overload``'s: 32 heads over 2; ``head_dim`` 128,
blocks of 128) on an 8,192-token step with the cells' chunk mix (a power
law over 2,048 tenants: most chunks one to four tokens, the busiest over a
thousand, sessions up to thousands of tokens long) against dense float32
attention over the same pool, with its time a layer printed.
``tests/test_swa_moe.py`` holds the interpreter to the dense form in
tier-1 at a tiny size; ``tests/test_pool_layout_compile.py`` compiles the
whole steps for a described chip.  Only this one runs what Mosaic made."""

import json
import os
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: tokens of the step, its grid size, the block and the head's width
TOKENS, GRID, BLOCK, D = 6000, 8192, 128, 128
#: limit on the widest gap to dense float32 attention over the widest
#: value: operands, probabilities and results are bfloat16 (2 ** -8)
GAP = 0.02


@pytest.fixture(scope="module")
def chunks():
    """``(start, n, off, table)`` of the step: chunk sizes by the power
    law, a session some chunks old (the busiest thousands of tokens), its
    blocks anywhere in the pool's second layer."""
    rng = np.random.default_rng(37)
    share = np.arange(1, 2049) ** -1.2
    n = rng.multinomial(TOKENS, share / share.sum())
    n = n[n > 0].astype(np.int64)
    assert (n <= 4).sum() > len(n) // 2 and n.max() > 1000
    start = np.minimum(n * rng.integers(0, 50, len(n)), 8192 - n)
    start[rng.random(len(n)) < 0.2] = 0              # fresh sessions
    off = np.cumsum(n) - n
    blocks = -(-(start + n) // BLOCK)
    rows = 1 + rng.permutation(int(blocks.sum()))
    table = np.zeros((len(n), 64), np.int32)
    at = np.cumsum(blocks) - blocks
    for s in range(len(n)):
        table[s, :blocks[s]] = rows[at[s]:at[s] + blocks[s]]
    return start, n, off, table, 1 + int(blocks.sum())


def dense(q, pool, start, n, off, table, kv, window):
    """Float32 numpy: every chunk against its session's keys, a key-value
    head's query heads in one product."""
    T, H, d = q.shape
    R = H // kv
    out = np.zeros((T, H, d), np.float32)
    for s in range(len(n)):
        total = start[s] + n[s]
        rows = pool[table[s, :-(-total // BLOCK)]].reshape(-1, 2, kv, d)
        keys, values = (rows[:total, i].transpose(1, 0, 2) for i in (0, 1))
        qs = q[off[s]:off[s] + n[s]].reshape(n[s], kv, R * d).transpose(
            1, 0, 2).reshape(kv, n[s] * R, d)
        sc = qs @ keys.transpose(0, 2, 1) * d ** -0.5     # [kv, n R, total]
        q_pos = np.repeat(start[s] + np.arange(n[s]), R)[:, None]
        k_pos = np.arange(total)[None, :]
        see = k_pos <= q_pos
        if window is not None:
            see &= k_pos > q_pos - window
        sc = np.where(see, sc, -np.inf)
        p = np.exp(sc - sc.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        out[off[s]:off[s] + n[s]] = (p @ values).reshape(
            kv, n[s], R * d).transpose(1, 0, 2).reshape(n[s], H, d)
    return out


@pytest.mark.parametrize("heads, kv, window", [
    (48, 8, None), (64, 8, 512), (32, 2, None)],
    ids=["lxs2_full", "lxs2_sliding", "n3s"])
def test_the_compiled_kernel_equals_dense_attention(chunks, heads, kv,
                                                    window):
    import jax
    import jax.numpy as jnp

    from anomod.ops import gqa_attention as ga

    start, n, off, table, rows = chunks
    chip = jax.devices()[0]
    assert chip.platform == "tpu"
    ks = jax.random.split(jax.random.PRNGKey(heads), 2)
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (GRID, heads, D), bf)
    # two layers' rows: the step reads the second's
    pool = jax.random.normal(ks[1], (2 * rows, BLOCK, 2 * kv * D), bf)
    items = ga.empty_items(ga.items_needed(len(n), GRID),
                           ga.blocks_needed(64, BLOCK, window))
    n_items = ga.fill_items(items, start, n, off, table, BLOCK, window)
    walked = int(items["table"][:n_items, 0, ga.NBLK].sum())
    fn = jax.jit(lambda q, pool, items, row0: ga.append_attention(
        q, pool, items, row0, kv, D ** -0.5, BLOCK, window))
    args = jax.device_put((q, pool, items, jnp.int32(rows)), chip)
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
    got = fn(*args)
    got.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        out = fn(*args)
    out.block_until_ready()
    ms = (time.perf_counter() - t0) * 100
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    got = f32(got)
    want = dense(f32(q), f32(pool)[rows:], start, n, off, table, kv, window)
    gap, top = np.abs(got - want).max(), np.abs(want).max()
    line = {"heads": heads, "kv": kv, "window": window,
            "chunks": len(n), "items": n_items, "blocks_walked": walked,
            "ms_a_layer": round(ms, 3), "gap_over_top": float(gap / top)}
    print("GQA", json.dumps(line))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "gqa_attention.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    assert np.isfinite(got).all() and gap < GAP * top
    assert not got[n.sum():].any()
