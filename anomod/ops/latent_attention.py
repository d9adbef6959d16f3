"""Append-attention over a block-paged LATENT cache, in two forms.

The cache holds, per token and layer, the normed latent ``c_kv`` and the
rotated shared key ``k_pe`` side by side (``[c_kv | k_pe]``, nothing per
head).  A step appends a packed batch of new tokens of many sessions;
their latents are in the pool already when attention runs, so both forms
read every key from the pool through the session's block table and mask
by position (a key at position ``p`` of the same session is visible to
the query at position ``>= p``).

- **absorbed** (few new tokens against a long cache): the query is taken
  through ``W_kvb``'s key half once (``q_lat``), scored against the
  latents directly, and the latent result goes through the value half.
  Work list: groups of up to ``GROUP`` consecutive tokens of one session;
  ``BATCH`` groups run side by side, block by block, online softmax.
- **expanded** (a long appended chunk): the keys and values of a run of
  cached blocks are materialised from ``W_kvb`` once and shared by every
  query tile of the chunk.  Work list: (chunk, run of ``KV_BLOCKS``
  blocks) pairs, query tiles of ``Q_TILE`` inside.

Both are equal in exact arithmetic.  The loops' trip counts are data
(``lax.fori_loop`` with traced bounds); every shape is static.  Which
form a chunk takes is decided on the host from sizes alone
(:func:`absorbed_is_cheaper`).
"""

from __future__ import annotations

GROUP = 8          # tokens of one session in an absorbed group
BATCH = 64         # absorbed groups side by side
Q_TILE = 256       # query tokens of an expanded tile
KV_BLOCKS = 8      # cached blocks expanded at once
NEG = -1e30        # the running maximum's floor (finite: no NaN from -inf)


def absorbed_is_cheaper(n_new, n_total, heads: int, nope: int, rope: int,
                        v_dim: int, latent: int, block: int):
    """Whether the absorbed form spends fewer FLOPs than the expanded one
    on ``n_new`` appended tokens of a session that then holds ``n_total``
    (whole numbers or arrays of them), as the two loops spend them, rows
    and keys padded to their tiles: absorbed pays ``W_kvb`` per new token
    and latent-wide scores per pair, expanded pays ``W_kvb`` per cached
    token and head-wide scores per pair.  Against a long cache the forms
    cross near ``W_kvb / (latent-wide - head-wide)`` appended tokens."""
    w_kvb = 2 * heads * latent * (nope + v_dim)
    rows = -(-n_new // GROUP) * GROUP
    keys = -(-n_total // block) * block
    absorbed = rows * w_kvb + rows * keys * 2 * heads * (2 * latent + rope)
    tile = -(-n_new // Q_TILE) * Q_TILE
    run = KV_BLOCKS * block
    keys = -(-n_total // run) * run
    expanded = keys * w_kvb + tile * keys * 2 * heads * (nope + rope + v_dim)
    return absorbed <= expanded


def absorbed_attention(q_cat, q_pos, pool, seg_blocks, groups, w_v, scale,
                       latent: int, block: int):
    """``q_cat`` ``[T + GROUP, H, row]`` (``[q_lat | q_pe | 0]`` at the
    pool's row width), ``q_pos`` ``[T + GROUP]``, ``pool`` ``[rows, block,
    row]`` (``[c_kv | k_pe | 0]``; a layer's rows are addressed by
    ``seg_blocks`` with the layer's offset added), ``groups``:
    ``tok0``, ``ntok``, ``seg``, ``nblk`` ``[G]`` and ``n_batches``.
    Returns ``[T + GROUP, H, v]`` float32-accumulated outputs in the
    activations' dtype (rows of no group are zero)."""
    import jax
    import jax.numpy as jnp
    T1, H, _ = q_cat.shape
    V = w_v.shape[-1]
    f32 = jnp.float32
    out0 = jnp.zeros((T1, H, V), q_cat.dtype)
    lane = jnp.arange(GROUP, dtype=jnp.int32)
    kv_lane = jnp.arange(block, dtype=jnp.int32)

    def batch_body(b, out):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, b * BATCH, BATCH)
        tok0, ntok, seg, nblk = (sl(groups[k]) for k in
                                 ("tok0", "ntok", "seg", "nblk"))
        rows = tok0[:, None] + lane[None, :]              # [B, G]
        live = lane[None, :] < ntok[:, None]
        rows = jnp.where(live, rows, T1 - 1)              # the trash row
        q = q_cat[rows]                                   # [B, G, H, C]
        pos = q_pos[rows]
        blocks_of = seg_blocks[seg]                       # [B, MB]

        def kv_body(j, carry):
            m, l, acc = carry
            lat = pool[blocks_of[:, j]]                   # [B, block, C]
            s = jnp.einsum("bghc,bkc->bghk", q, lat,
                           preferred_element_type=f32) * scale
            see = (live[:, :, None] & (j < nblk)[:, None, None]
                   & ((j * block + kv_lane)[None, None, :]
                      <= pos[:, :, None]))[:, :, None, :]
            s = jnp.where(see, s, NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bghk,bkc->bghc", p.astype(q_cat.dtype), lat[..., :latent],
                preferred_element_type=f32)
            return m_new, l, acc

        m0 = jnp.full((BATCH, GROUP, H), NEG, f32)
        l0 = jnp.zeros((BATCH, GROUP, H), f32)
        a0 = jnp.zeros((BATCH, GROUP, H, latent), f32)
        _, l, acc = jax.lax.fori_loop(0, nblk.max(), kv_body, (m0, l0, a0))
        o_lat = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_cat.dtype)
        o = jnp.einsum("bghc,chv->bghv", o_lat, w_v,
                       preferred_element_type=f32).astype(q_cat.dtype)
        return out.at[rows.reshape(-1)].set(o.reshape(-1, H, V))

    out = jax.lax.fori_loop(0, groups["n_batches"], batch_body, out0)
    return out.at[T1 - 1].set(0)


def expanded_attention(q_nope, q_pe, q_pos, q_seg, pool, seg_blocks, pairs,
                       w_kvb, scale, latent: int, rope: int, block: int):
    """``q_nope`` ``[T + Q_TILE, H, nope]``, ``q_pe`` ``[T + Q_TILE, H,
    rope]``, ``q_pos`` / ``q_seg`` ``[T + Q_TILE]``, ``w_kvb`` ``[latent,
    H, nope + v]``; ``pairs``: ``seg``, ``q0``, ``n_tiles``, ``blk0``
    ``[P]`` and ``n_pairs``.  Returns ``[T + Q_TILE, H, v]`` (rows of no
    expanded chunk are zero)."""
    import jax
    import jax.numpy as jnp
    T1, H, nope = q_nope.shape
    V = w_kvb.shape[-1] - nope
    f32 = jnp.float32
    n_kv = KV_BLOCKS * block
    kv_lane = jnp.arange(n_kv, dtype=jnp.int32)
    blk_lane = jnp.arange(KV_BLOCKS, dtype=jnp.int32)
    max_blocks = seg_blocks.shape[1]

    def pair_body(i, state):
        seg, q0, n_tiles, blk0 = (pairs[k][i] for k in
                                  ("seg", "q0", "n_tiles", "blk0"))
        cols = jnp.minimum(blk0 + blk_lane, max_blocks - 1)
        lat = pool[seg_blocks[seg, cols]].reshape(n_kv, -1)
        kv = jnp.einsum("kc,chd->khd", lat[:, :latent], w_kvb,
                        preferred_element_type=f32
                        ).astype(q_nope.dtype)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k_pe = lat[:, latent:latent + rope]
        kv_pos = blk0 * block + kv_lane

        def tile_body(t, state):
            m, l, acc = state
            at = q0 + t * Q_TILE
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, Q_TILE)
            s = (jnp.einsum("qhd,khd->hqk", cut(q_nope), k_nope,
                            preferred_element_type=f32)
                 + jnp.einsum("qhr,kr->hqk", cut(q_pe), k_pe,
                              preferred_element_type=f32)) * scale
            see = ((cut(q_seg) == seg)[:, None]
                   & (kv_pos[None, :] <= cut(q_pos)[:, None]))[None]
            s = jnp.where(see, s, NEG)
            m_old = cut(m).T                              # [H, Q]
            m_new = jnp.maximum(m_old, s.max(axis=-1))
            p = jnp.where(see, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_new = cut(l).T * alpha + p.sum(axis=-1)
            a_new = cut(acc) * alpha.T[..., None] + jnp.einsum(
                "hqk,khv->qhv", p.astype(q_nope.dtype), v,
                preferred_element_type=f32)
            put = lambda a, x: jax.lax.dynamic_update_slice_in_dim(
                a, x, at, 0)
            return put(m, m_new.T), put(l, l_new.T), put(acc, a_new)

        return jax.lax.fori_loop(0, n_tiles, tile_body, state)

    m0 = jnp.full((T1, H), NEG, f32)
    l0 = jnp.zeros((T1, H), f32)
    a0 = jnp.zeros((T1, H, V), f32)
    _, l, acc = jax.lax.fori_loop(0, pairs["n_pairs"], pair_body,
                                  (m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_nope.dtype)
