"""Grouped-query append-attention over a block-paged K/V cache (XLA).

The cache holds, per token, the keys of the ``kv`` heads and then their
values side by side (``[k_0 .. k_kv | v_0 .. v_kv]``, a row of ``2 * kv *
head_dim`` columns: a multiple of the 128-lane tile, or the device lays
the pool out token-minor and every in-place write copies it whole).  A
step appends a packed batch of new tokens of many sessions; their rows
are in the pool already when attention runs, so every key is read from
the pool through the session's block table and masked by position (a key
at position ``p`` of the same session is visible to the query at position
``>= p`` and, under a ``window``, ``< p + window``: ``window`` keys, the
query's own among them).  No positional encoding is applied here: a caller
that has one applies it before the keys are cached.

Work list: (chunk, run of ``KV_BLOCKS`` cached blocks) pairs, query tiles
of ``Q_TILE`` tokens inside, each tile's ``heads / kv`` query heads of a
group scored against the group's one key head; two nested loops with
traced bounds and an online softmax state over the packed tokens.
:func:`pair_runs` builds a list that holds, for each run, only the query
tiles that can see one of its keys (under a window: never a block that no
query of the chunk sees).
"""

from __future__ import annotations

Q_TILE = 64        # query tokens of a tile
KV_BLOCKS = 4      # cached blocks read at once
NEG = -1e30        # the running maximum's floor (finite: no NaN from -inf)
#: the names of the calls that hold the attention loops: full layers,
#: and layers under a sliding window
SCOPE = "anomod_seq_gqa"
SWA_SCOPE = "anomod_seq_swa"


def pairs_needed(segments: int, pool_tokens: int, block: int) -> int:
    """Rows of a step's pair list that always suffice: a run for each
    ``KV_BLOCKS`` blocks the pool can hold and one more a chunk."""
    return segments + pool_tokens // (KV_BLOCKS * block) + 1


def window_pairs_needed(segments: int, tokens: int, window: int,
                        block: int) -> int:
    """Rows of :func:`pair_runs`' list under ``window`` that always
    suffice at ``tokens`` packed tokens: a chunk of ``n`` tokens walks the
    blocks of ``n + window - 1`` keys, whatever their place in a block."""
    runs_a_chunk = -(-(window + 2 * block) // (KV_BLOCKS * block)) + 1
    return runs_a_chunk * segments + tokens // (KV_BLOCKS * block) + 1


def pair_runs(start, n, off, block: int, window: int = None) -> dict:
    """The work list of chunks ``(start position, n tokens, first packed
    token off)`` (int64 arrays ``[S]``): for each chunk the runs of
    ``KV_BLOCKS`` blocks from the first block one of its queries sees
    (under ``window``: the block of position ``start - window + 1``) to
    the block of its last token, and for each run the query tiles that
    see one of its keys (a tile before the run's first key sees none;
    under ``window`` neither does one wholly past its last key's reach).
    Returns ``seg``, ``q0``, ``n_tiles``, ``blk0`` (int64 ``[P]``)."""
    import numpy as np
    S = len(n)
    b_lo = np.zeros(S, np.int64) if window is None \
        else np.maximum(start - window + 1, 0) // block
    runs = -(-((start + n - 1) // block + 1 - b_lo) // KV_BLOCKS)
    seg = np.repeat(np.arange(S), runs)
    P = len(seg)
    blk0 = b_lo[seg] + (np.arange(P) - np.repeat(np.cumsum(runs) - runs,
                                                 runs)) * KV_BLOCKS
    # positions relative to the chunk's first query
    first_key = blk0 * block - start[seg]
    t_lo = np.maximum(first_key // Q_TILE, 0)
    t_hi = -(-n[seg] // Q_TILE) - 1
    if window is not None:
        reach = first_key + KV_BLOCKS * block - 1 + window - 1
        t_hi = np.minimum(t_hi, reach // Q_TILE)
    return {"seg": seg, "q0": off[seg] + t_lo * Q_TILE,
            "n_tiles": t_hi - t_lo + 1, "blk0": blk0}


def append_attention(q, q_pos, q_seg, pool, seg_blocks, pairs, kv: int,
                     scale: float, block: int, window: int = None):
    """``q`` ``[T + Q_TILE, H, d]``, ``q_pos`` / ``q_seg`` ``[T +
    Q_TILE]``, ``pool`` ``[rows, block, 2 * kv * d]`` (a layer's rows are
    addressed by ``seg_blocks`` with the layer's offset added);
    ``pairs``: ``seg``, ``q0``, ``n_tiles``, ``blk0`` ``[P]`` and
    ``n_pairs``; ``window``: a query sees the ``window`` newest keys up
    to its own.  Returns ``[T + Q_TILE, H, d]`` (rows of no chunk are
    zero)."""
    import jax
    import jax.numpy as jnp
    T1, H, d = q.shape
    R = H // kv
    f32 = jnp.float32
    n_kv = KV_BLOCKS * block
    kv_lane = jnp.arange(n_kv, dtype=jnp.int32)
    blk_lane = jnp.arange(KV_BLOCKS, dtype=jnp.int32)
    max_blocks = seg_blocks.shape[1]
    qg = q.reshape(T1, kv, R, d)

    def pair_body(i, state):
        seg, q0, n_tiles, blk0 = (pairs[k][i] for k in
                                  ("seg", "q0", "n_tiles", "blk0"))
        cols = jnp.minimum(blk0 + blk_lane, max_blocks - 1)
        rows = pool[seg_blocks[seg, cols]].reshape(n_kv, 2, kv, d)
        keys, values = rows[:, 0], rows[:, 1]
        kv_pos = blk0 * block + kv_lane

        def tile_body(t, state):
            m, l, acc = state
            at = q0 + t * Q_TILE
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, Q_TILE)
            s = jnp.einsum("qgrd,kgd->grqk", cut(qg), keys,
                           preferred_element_type=f32) * scale
            see = ((cut(q_seg) == seg)[:, None]
                   & (kv_pos[None, :] <= cut(q_pos)[:, None]))
            if window is not None:
                see = see & (kv_pos[None, :] > cut(q_pos)[:, None] - window)
            see = see[None, None]
            s = jnp.where(see, s, NEG)
            m_old = jnp.moveaxis(cut(m), 0, -1)           # [kv, R, Q]
            m_new = jnp.maximum(m_old, s.max(axis=-1))
            p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_new = jnp.moveaxis(cut(l), 0, -1) * alpha + p.sum(axis=-1)
            a_new = cut(acc) * jnp.moveaxis(alpha, -1, 0)[..., None] \
                + jnp.einsum("grqk,kgd->qgrd", p.astype(q.dtype), values,
                             preferred_element_type=f32)
            put = lambda a, x: jax.lax.dynamic_update_slice_in_dim(
                a, x, at, 0)
            return (put(m, jnp.moveaxis(m_new, -1, 0)),
                    put(l, jnp.moveaxis(l_new, -1, 0)), put(acc, a_new))

        return jax.lax.fori_loop(0, n_tiles, tile_body, state)

    m0 = jnp.full((T1, kv, R), NEG, f32)
    l0 = jnp.zeros((T1, kv, R), f32)
    a0 = jnp.zeros((T1, kv, R, d), f32)
    _, l, acc = jax.lax.fori_loop(0, pairs["n_pairs"], pair_body,
                                  (m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype).reshape(
        T1, H, d)
