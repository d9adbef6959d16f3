"""Grouped-query append-attention over a block-paged K/V cache: one Pallas
TPU kernel over a host-built work list.

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

The packed tokens are cut into WINDOWS of ``Q_TILE`` rows (row ``Q_TILE *
w`` on: what a block of the arrays can address).  The work list
(:func:`fill_items`) holds an ITEM for every (chunk, window it has tokens
in), in token order, with the physical pool rows of the cached blocks the
item's queries can see: from the session's first block (under a
``window``: the block of the item's first query's oldest visible key) to
the block of its last query.  The kernel's grid is the items, their count
data.  A window's first item lays its queries out a key-value head at a
time, the ``R = heads / kv`` query heads of the group down the rows (``R *
Q_TILE`` rows: a key head is read once and scored against all of them in
one product) and clears the online softmax state ``(m, l, acc)``, which
stays in VMEM until the window's last item writes the window's result
once, in the activations' dtype.  In between each item walks its blocks
in RUNS of ``KV_BLOCKS``, each fetched from the pool where it lies while
the one before is worked on (the next item's first among them): a whole
run is scored at once (the reductions along its keys cost a quarter of
what they cost a block at a time), a shorter one a block at a time.
Rows of a window that are another chunk's pass an item unchanged (no key
of its blocks is theirs to see); rows of no chunk come back zero.
"""

from __future__ import annotations

import numpy as np

Q_TILE = 16        # packed tokens of a window: a bfloat16 sublane tile
KV_BLOCKS = 4      # cached blocks of a run
NEG = -1e30        # the running maximum's floor (finite: no NaN from -inf)
#: an item's scalars, ahead of its block rows in its row of the table
#: (``EDGE``: bit 0 the item is its window's first, bit 1 its last; the
#: NEXT item's ``NBLK`` and first run's rows: what is fetched ahead)
LO, HI, POS0, BLK0, NBLK, EDGE, NEXT_NBLK, NEXT = 0, 1, 2, 3, 4, 5, 6, 8
HEAD = NEXT + KV_BLOCKS
#: the names of the calls that hold the kernel: full layers, and layers
#: under a sliding window
SCOPE = "anomod_seq_gqa"
SWA_SCOPE = "anomod_seq_swa"


def items_needed(segments: int, tokens: int) -> int:
    """Rows of a step's work list that always suffice for at most
    ``segments`` chunks in ``tokens`` packed tokens: a chunk has an item
    in every window it has tokens in, and a window's edge cuts one chunk
    at most."""
    return segments + -(-tokens // Q_TILE)


def blocks_needed(session_blocks: int, block: int, window: int = None) -> int:
    """Block rows an item may walk (whole runs of them): a session's, or
    under ``window`` those of the ``window + Q_TILE - 1`` keys its queries
    see between them, whatever their place in a block."""
    if window is not None:
        session_blocks = min(session_blocks,
                             (window + Q_TILE - 2) // block + 2)
    return -(-session_blocks // KV_BLOCKS) * KV_BLOCKS


def empty_items(rows: int, blocks: int) -> dict:
    """A work list of one item of no token (a grid is never empty): it
    is all of window 0 and reads the never-allocated block 0."""
    table = np.zeros((rows, 1, HEAD + blocks), np.int32)
    table[0, 0, NBLK], table[0, 0, EDGE] = 1, 3
    return {"win": np.zeros((rows,), np.int32), "table": table,
            "n_items": np.int32(1), "n_tokens": np.int32(0)}


def fill_items(items: dict, start, n, off, seg_blocks, block: int,
               window: int = None) -> int:
    """Fill :func:`empty_items` for chunks ``(start position, n tokens,
    first packed token off)`` (int64 arrays ``[S]``, packed back to back
    in order) whose sessions' blocks are the rows of ``seg_blocks`` ``[>=
    S, session blocks]``: an item a (chunk, window) in token order with
    its rows ``lo .. hi`` of the window, the position ``pos0`` its window's
    row 0 would have in the chunk's session, and the pool rows of blocks
    ``blk0 .. blk0 + nblk``: the first one of its queries sees (under
    ``window``: the block of position ``first query - window + 1``) to the
    block of its last query.  Returns the number of items."""
    if not len(n):
        return 0
    w0, w1 = off // Q_TILE, (off + n - 1) // Q_TILE
    per = w1 - w0 + 1
    of = np.repeat(np.arange(len(n)), per)           # the item's chunk
    I = len(of)
    win = w0[of] + np.arange(I) - np.repeat(np.cumsum(per) - per, per)
    lo = np.maximum(off[of], win * Q_TILE)
    hi = np.minimum(off[of] + n[of], (win + 1) * Q_TILE)
    pos0 = start[of] - off[of] + win * Q_TILE
    first, last = pos0 + lo - win * Q_TILE, pos0 + hi - win * Q_TILE - 1
    blk0 = np.zeros(I, np.int64) if window is None \
        else np.maximum(first - window + 1, 0) // block
    nblk = last // block - blk0 + 1
    table = items["table"]
    width = table.shape[2] - HEAD
    if nblk.max() > width:
        raise ValueError(f"an item walks {nblk.max()} blocks, the table "
                         f"holds {width}")
    head = table[:I, 0, :HEAD]
    head[:, LO], head[:, HI] = lo - win * Q_TILE, hi - win * Q_TILE
    head[:, POS0], head[:, BLK0], head[:, NBLK] = pos0, blk0, nblk
    edge = np.concatenate([[True], win[1:] != win[:-1], [True]])
    head[:, EDGE] = edge[:-1] + 2 * edge[1:]
    cols = blk0[:, None] + np.arange(width)
    table[:I, 0, HEAD:] = np.where(
        cols < (blk0 + nblk)[:, None],
        seg_blocks[of[:, None], np.minimum(cols, seg_blocks.shape[1] - 1)],
        0)
    head[:-1, NEXT_NBLK], head[-1, NEXT_NBLK] = nblk[1:], 0
    head[:-1, NEXT:] = table[1:I, 0, HEAD:HEAD + KV_BLOCKS]
    items["win"][:I] = win
    items["n_items"] = np.int32(I)
    items["n_tokens"] = np.int32(off[-1] + n[-1])
    return I


def append_attention(q, pool, items: dict, row0, kv: int, scale: float,
                     block: int, window: int = None):
    """``q`` ``[T, H, d]`` (``T`` a multiple of ``Q_TILE``), ``pool``
    ``[rows, block, 2 * kv * d]`` (this layer's rows from ``row0`` on, an
    int32 scalar), ``items``: :func:`fill_items`; ``window``: a query sees
    the ``window`` newest keys up to its own.  Returns ``[T, H, d]`` in
    ``q``'s dtype (rows of no chunk are zero)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, H, d = q.shape
    R, Q = H // kv, Q_TILE
    M, W = R * Q, pool.shape[-1]
    f32, dtype = jnp.float32, q.dtype
    if T % Q or H % kv or W != 2 * kv * d:
        raise ValueError(f"{T} tokens in windows of {Q}, {H} heads over "
                         f"{kv} key-value heads, rows of {W}: not whole")
    table = items["table"]
    width, step = table.shape[2], KV_BLOCKS

    def kernel(win, row0_ref, q_ref, tbl_ref, pool_ref, out_ref, kv_buf,
               kv_sem, qs, m_ref, l_ref, acc_ref, slot_ref):
        lo, hi, pos0 = tbl_ref[0, LO], tbl_ref[0, HI], tbl_ref[0, POS0]
        blk0, nblk, edge = (tbl_ref[0, BLK0], tbl_ref[0, NBLK],
                            tbl_ref[0, EDGE])

        def run_copies(at, held, slot):
            """The blocks of a run (the table's columns ``at .. at +
            step``, the first ``held`` of them there) on their way into
            ``kv_buf[slot]``, each with whether it is there."""
            return [(b < held, pltpu.make_async_copy(
                pool_ref.at[row0_ref[0] + tbl_ref[0, at + b]],
                kv_buf.at[slot, pl.ds(b * block, block)], kv_sem.at[slot]))
                for b in range(step)]

        def start(at, held, slot):
            for has, copy in run_copies(at, held, slot):
                pl.when(has)(copy.start)

        @pl.when(pl.program_id(0) == 0)
        def _():
            slot_ref[0] = 0
            start(HEAD, nblk, 0)

        @pl.when((edge & 1) != 0)
        def _():
            # a key-value head's R query heads down the rows: r * Q + t
            for g in range(kv):
                qs[g] = jnp.concatenate(
                    [q_ref[:, (g * R + r) * d:(g * R + r + 1) * d]
                     for r in range(R)], axis=0)
            m_ref[...] = jnp.full(m_ref.shape, NEG, f32)
            l_ref[...] = jnp.zeros(l_ref.shape, f32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        t_row = jnp.concatenate(
            [jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)] * R, axis=0)
        mine = (t_row >= lo) & (t_row < hi)
        q_pos = pos0 + t_row
        # the oldest key a row sees, and none for a row of another chunk
        oldest = jnp.where(mine, 0 if window is None
                           else q_pos - (window - 1), q_pos + 1)

        def attend(slot, key0, at, n_keys):
            """Keys ``at .. at + n_keys`` of the buffer, the first at
            position ``key0``, against every group's rows."""
            k_pos = key0 + jax.lax.broadcasted_iota(jnp.int32, (M, n_keys),
                                                    1)
            see = (k_pos <= q_pos) & (k_pos >= oldest)
            at = pl.ds(at, n_keys)
            for g in range(kv):
                keys = kv_buf[slot, at, g * d:(g + 1) * d]
                values = kv_buf[slot, at, (kv + g) * d:(kv + g + 1) * d]
                s = jax.lax.dot_general(
                    qs[g], keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale
                s = jnp.where(see, s, NEG)
                m = m_ref[g]
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                # a hidden key's exp(NEG - m_new) is 0 once the row has
                # seen a key; until then the row gathers what its first
                # visible key's alpha = 0 wipes, or the window's last item
                # where no key ever comes
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_ref[g] = l_ref[g] * alpha + p.sum(axis=-1, keepdims=True)
                acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                    p.astype(values.dtype), values,
                    preferred_element_type=f32)
                m_ref[g] = m_new

        runs = (nblk + step - 1) // step
        slot0 = slot_ref[0]

        def run_body(r, _):
            slot = (slot0 + r) % 2
            held = nblk - r * step

            @pl.when(r + 1 < runs)
            def _():
                start(HEAD + (r + 1) * step, held - step, 1 - slot)

            @pl.when(r + 1 == runs)
            def _():
                start(NEXT, tbl_ref[0, NEXT_NBLK], 1 - slot)

            for has, copy in run_copies(HEAD + r * step, held, slot):
                pl.when(has)(copy.wait)
            key0 = (blk0 + r * step) * block

            @pl.when(held >= step)
            def _():
                attend(slot, key0, 0, step * block)

            @pl.when(held < step)
            def _():
                def block_body(b, _):
                    attend(slot, key0 + b * block,
                           pl.multiple_of(b * block, block), block)
                    return 0

                jax.lax.fori_loop(0, held, block_body, 0)

            return 0

        jax.lax.fori_loop(0, runs, run_body, 0)
        slot_ref[0] = (slot0 + runs) % 2

        @pl.when((edge & 2) != 0)
        def _():
            for g in range(kv):
                o = jnp.where(m_ref[g] > 0.5 * NEG, acc_ref[g] / l_ref[g],
                              0.0).astype(dtype)
                for r in range(R):
                    out_ref[:, (g * R + r) * d:(g * R + r + 1) * d] = \
                        o[r * Q:(r + 1) * Q]

    rows = pl.BlockSpec((Q, H * d), lambda i, win, *_: (win[i], 0))
    # Mosaic where the program is lowered for the TPU (a chip attached or
    # described), the Pallas interpreter elsewhere
    call = lambda interpret: lambda *args: pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(items["n_items"],),
            in_specs=[rows,
                      pl.BlockSpec((None, 1, width), lambda i, *_: (i, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((2, step * block, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((kv, M, d), dtype),
                pltpu.VMEM((kv, M, 1), f32), pltpu.VMEM((kv, M, 1), f32),
                pltpu.VMEM((kv, M, d), f32),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((T, H * d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(*args)
    # (where the TPU is the backend no other platform is lowered for, and
    # the interpreter's trace of the kernel is saved)
    elsewhere = {} if jax.default_backend() == "tpu" \
        else {"default": call(True)}
    o = jax.lax.platform_dependent(
        items["win"], jnp.asarray(row0, jnp.int32).reshape(1),
        q.reshape(T, H * d), table, pool, tpu=call(False), **elsewhere)
    # a window no item opened holds whatever the buffer held
    return jnp.where(jnp.arange(T)[:, None] < items["n_tokens"], o,
                     0).reshape(T, H, d)
