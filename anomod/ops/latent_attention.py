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
  Work list: groups of up to ``GROUP`` consecutive tokens of one session,
  the longest walks first.  One Pallas TPU kernel, a grid step a group:
  the online softmax state stays in VMEM while the group walks its own
  cached blocks, ``STEP`` at a time (:func:`absorbed_attention`).
- **expanded** (a long appended chunk): the keys and values of a run of
  cached blocks are materialised from ``W_kvb`` once and shared by every
  query tile of the chunk.  Work list: (chunk, run of ``KV_BLOCKS``
  blocks) pairs, query tiles of ``Q_TILE`` inside; two nested XLA loops.

Both are equal in exact arithmetic.  The trip counts are data (the
kernel's grid bound and walks, ``lax.fori_loop`` with traced bounds);
every shape is static.  Which form a chunk takes is decided on the host
from sizes alone (:func:`absorbed_is_cheaper`).
"""

from __future__ import annotations

GROUP = 8          # tokens of one session in an absorbed group
BATCH = 64         # the rounding of a plan's group rows
STEP = 2           # cached blocks of an absorbed group's walk in one run
Q_TILE = 256       # query tokens of an expanded tile
KV_BLOCKS = 8      # cached blocks expanded at once
NEG = -1e30        # the running maximum's floor (finite: no NaN from -inf)


def absorbed_is_cheaper(n_new, n_total, heads: int, nope: int, rope: int,
                        v_dim: int, latent: int, block: int):
    """Whether the absorbed form spends fewer FLOPs than the expanded one
    on ``n_new`` appended tokens of a session that then holds ``n_total``
    (whole numbers or arrays of them), as the two forms spend them, rows
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


def absorbed_attention(q_lat, q_pe, q_pos, q_live, pool, seg_blocks, groups,
                       w_v, scale, block: int):
    """``q_lat`` ``[T + GROUP, H, latent]`` and ``q_pe`` ``[T + GROUP, H,
    row - latent]`` (zero past the rope: together a query at the pool's
    row width), ``q_pos`` ``[T + GROUP]``, ``q_live`` ``[T + GROUP]`` (the
    rows some group holds), ``pool`` ``[rows, block, row]`` (``[c_kv |
    k_pe | 0]``; a layer's rows are addressed by ``seg_blocks`` with the
    layer's offset added), ``groups``: ``tok0``, ``ntok``, ``seg``,
    ``nblk`` ``[G]`` and ``n_groups``.  Returns ``[T + GROUP, H, v]``
    float32-accumulated outputs in the activations' dtype (rows of no
    group are zero).

    One Pallas kernel, a grid step a group (the grid's bound is the
    plan's ``n_groups``): the group's ``GROUP * H`` query rows and its
    online softmax state ``(m, l, acc)`` stay in VMEM while the group's
    cached blocks stream from the pool where they lie, ``STEP`` blocks a
    run, one run in flight while another is worked on (the next group's
    first among them); the normalised ``[GROUP * H, latent]`` result is
    written once, live rows only.  ``W_kvb``'s value half is applied
    outside."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T1, H, latent = q_lat.shape
    W, dtype = pool.shape[-1], q_lat.dtype
    group, step = GROUP, STEP
    R, n_kv = group * H, step * block
    f32 = jnp.float32
    # a group's row of the block table is one SMEM block: [G, 1, MB], as
    # a block's last two dimensions must be the array's own
    table = seg_blocks[groups["seg"]][:, None, :]
    G, _, MB = table.shape

    def kernel(tok0, ntok, nblk, pos0, q_lat_ref, q_pe_ref, tbl_ref, nxt_ref,
               pool_ref, out_ref, kv_buf, kv_sem, stage, out_sem, m_ref,
               l_ref, acc_ref, slot_ref):
        g = pl.program_id(0)
        last = pl.num_programs(0) - 1

        def kv_copies(tbl, blocks, at, slot):
            # the run's blocks past the group's last are that last again:
            # every row of the buffer is a cached row (finite), masked
            return [pltpu.make_async_copy(
                pool_ref.at[tbl[0, jnp.minimum(at * step + i, blocks - 1)]],
                kv_buf.at[slot, pl.ds(i * block, block)], kv_sem.at[slot])
                for i in range(step)]

        def scores(q, keys):
            return jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                       preferred_element_type=f32)

        def out_copies(of):
            return [(i < ntok[of], pltpu.make_async_copy(
                stage.at[pl.ds(i * H, H)], out_ref.at[tok0[of] + i],
                out_sem)) for i in range(group)]

        @pl.when(g == 0)
        def _():
            slot_ref[0] = 0
            for c in kv_copies(tbl_ref, nblk[0], 0, 0):
                c.start()

        m_ref[...] = jnp.full(m_ref.shape, NEG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
        blocks = nblk[g]
        # the last key a row sees: its own position, inside the group's
        # blocks; none for a row of no token
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // H
        sees_to = jnp.where(lane < ntok[g], jnp.minimum(
            pos0[g] + lane, blocks * block - 1), -1)
        kv_lane = jax.lax.broadcasted_iota(jnp.int32, (R, n_kv), 1)
        runs = (blocks + step - 1) // step
        slot0 = slot_ref[0]

        def run_body(j, _):
            slot = (slot0 + j) % 2

            @pl.when(j + 1 < runs)
            def _():
                for c in kv_copies(tbl_ref, blocks, j + 1, 1 - slot):
                    c.start()

            @pl.when((j + 1 == runs) & (g < last))
            def _():
                for c in kv_copies(nxt_ref, nblk[g + 1], 0, 1 - slot):
                    c.start()

            for c in kv_copies(tbl_ref, blocks, j, slot):
                c.wait()
            s = (scores(q_lat_ref[...].reshape(R, latent),
                        kv_buf[slot, :, :latent])
                 + scores(q_pe_ref[...].reshape(R, W - latent),
                          kv_buf[slot, :, latent:])) * scale
            see = j * n_kv + kv_lane <= sees_to
            s = jnp.where(see, s, NEG)
            m = m_ref[...]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.where(see, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(dtype), kv_buf[slot, :, :latent],
                preferred_element_type=f32)
            m_ref[...] = m_new
            return 0

        jax.lax.fori_loop(0, runs, run_body, 0)
        slot_ref[0] = (slot0 + runs) % 2

        # the group before this one has had a whole walk to land its rows
        @pl.when(g > 0)
        def _():
            for live, c in out_copies(g - 1):
                pl.when(live)(c.wait)

        stage[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(stage.dtype)
        for live, c in out_copies(g):
            pl.when(live)(c.start)

        @pl.when(g == last)
        def _():
            for live, c in out_copies(g):
                pl.when(live)(c.wait)

    def rows_of_group(width):
        # rows tok0 .. tok0 + GROUP, wherever tok0 falls
        return pl.BlockSpec(
            (pl.Element(group), pl.Element(H), pl.Element(width)),
            lambda g, tok0, *_: (tok0[g], 0, 0))

    # Mosaic where the program is lowered for the TPU (a chip attached or
    # described), the Pallas interpreter elsewhere
    call = lambda interpret: lambda *args: pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(groups["n_groups"],),
            in_specs=[
                rows_of_group(latent), rows_of_group(W - latent),
                pl.BlockSpec((None, 1, MB), lambda g, *_: (g, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((None, 1, MB),
                             lambda g, *_: (jnp.minimum(g + 1, G - 1), 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((R, latent), dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.VMEM((R, 1), f32), pltpu.VMEM((R, 1), f32),
                pltpu.VMEM((R, latent), f32),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((T1, H, latent), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(*args)
    o_lat = jax.lax.platform_dependent(
        groups["tok0"], groups["ntok"], groups["nblk"],
        q_pos[groups["tok0"]], q_lat, q_pe, table, table, pool,
        tpu=call(False), default=call(True))
    # rows no group wrote hold whatever the buffer held: masked after
    # the value half (a row's product reads that row alone), where the
    # rows are a quarter as wide
    o = jnp.einsum("thc,chv->thv", o_lat, w_v, preferred_element_type=f32)
    return jnp.where(q_live[:, None, None], o, 0).astype(dtype)


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
