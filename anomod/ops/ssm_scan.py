"""The Mamba-2 recurrence over a PACKED step of ragged per-session chunks,
each continuing from its own carried state: one Pallas TPU kernel in which
a chunk's state lives in VMEM from its first token to its last.

A session's state lives in a slot of a pool ``[layers, slots, state,
heads * head_dim]`` between steps (the pool's dtype; float32 inside the
step; the state TRANSPOSED, ``state`` on sublanes and ``(head, head_dim)``
on lanes, so that every product below is a plain matmul and every per-head
factor a lane pattern).  A step appends a packed batch of chunks of many
sessions; chunk ``c`` is tokens ``tok0[c] .. tok0[c] + n[c]`` of the packed
arrays.  Per token and head (``A < 0`` a head, ``dt > 0`` after the
softplus, head ``j`` reads group ``j // (heads / groups)`` of ``B`` and
``C``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t

- **recurrent** (short chunks): exactly that, a token after another on the
  VPU in float32, ``y`` a sum over the state's sublanes.
- **chunked** (long chunks): a block of up to ``chunk`` tokens at once from
  the carried state; inside the block the decays are a lower-triangular
  matrix ``L[t, s] = exp(sum_{s < r <= t} dt_r A)`` built and consumed in
  VMEM a head, and everything is matmuls: ``y = (L * C B^T * dt) x +
  exp(cum) C S``, ``S' = exp(cum_end) S + B^T (exp(cum_end - cum) dt x)``.

Both are equal in exact arithmetic.  The packed tokens are cut into
WINDOWS of ``chunk`` rows (row ``chunk * w`` on: what a block of the
arrays can address); the kernel's work list (:func:`work_lists`) holds an
ITEM for every (chunk, window it has tokens in), in token order.  The grid
is ``(groups, items)``, the items' count data: for a ``B`` / ``C`` group
the kernel walks the items; at a chunk's first item the group's share of
the session's state comes from its slot into VMEM (zeros for a fresh
session, whose slot is never read; the next ``DEPTH - 1`` chunks' shares
are on their way meanwhile), every item runs its tokens against it in its
form, the window's ``y`` is written once, and at the chunk's last item the
share goes back to the same slot, IN PLACE where the caller donates the
pool.  Rows of a window that are not the item's pass the state unchanged
(their ``dt`` is 0) and keep their own ``y``.  Which form a chunk takes is
decided on the host from sizes alone (:func:`recurrent_is_cheaper`).
"""

from __future__ import annotations

import numpy as np

#: what a token of the recurrent form and a head of a chunked block cost,
#: in passes of the vector unit over their tiles: the recurrent token's
#: five over the group's state (decay, outer product, add, times ``C``,
#: sum); the block's six over a head's ``[chunk, chunk]`` tile (difference,
#: mask, exp, times ``C B^T``, times ``dt``, to the matmul's dtype) and its
#: matmuls, read together on the chip as twelve (PERF.md section 6, PR 35:
#: 0.23 us a token and group, 1.03 us a block and group on a v5e)
TOKEN_PASSES, BLOCK_PASSES = 5, 12
#: shares of states on their way into VMEM, and out of it, at a time
DEPTH = 4
#: an item's flags
FIRST, LAST, FRESH, RECURRENT, OPENS, CLOSES = 1, 2, 4, 8, 16, 32
#: the name of the call that holds the kernel: every device op of the
#: recurrence carries it in the trace's op metadata
SCOPE = "anomod_seq_ssm"


def recurrent_is_cheaper(n, heads: int, head_dim: int, state: int,
                         groups: int, chunk: int):
    """Whether the recurrent form costs less than the chunked one on a
    chunk of ``n`` tokens (a whole number or an array).  In the kernel both
    forms move the state once a chunk and differ in compute alone, which
    is counted in the vector registers (8 sublanes of 128 lanes) each
    passes over: a recurrent token passes :data:`TOKEN_PASSES` times over
    a group's ``[state, heads / groups * head_dim]`` float32 state; a
    chunked block, whatever it holds, :data:`BLOCK_PASSES` times over a
    ``[chunk, chunk]`` tile a head.  (On the window's edges a chunk may
    take a block more than ``n / chunk``: not known from sizes.)"""
    vregs = lambda rows, lanes: -(-rows // 8) * -(-lanes // 128)
    k = heads // groups
    token = TOKEN_PASSES * vregs(state, k * head_dim)
    block = BLOCK_PASSES * k * vregs(chunk, chunk)
    return n * token <= -(-n // chunk) * block


def work_caps(tokens: int, segments: int, chunk: int) -> dict:
    """Static rows of the work list for at most ``segments`` chunks in
    ``tokens`` packed tokens: a chunk has an item in every window it has
    tokens in, and a window's edge cuts one chunk at most."""
    return {"scan_items": segments + -(-tokens // chunk)}


def empty_work(caps: dict) -> dict:
    """A work list of one item of no token (a grid is never empty)."""
    z = lambda: np.zeros((caps["scan_items"],), np.int32)
    work = {"window": z(), "lo": z(), "hi": z(), "slot": z(), "flags": z(),
            "next": z(), "n_items": np.int32(1), "n_tokens": np.int32(0)}
    work["flags"][0], work["next"][0] = RECURRENT, 1
    return work


def work_lists(work: dict, tok0: np.ndarray, n: np.ndarray,
               slot: np.ndarray, fresh: np.ndarray, recurrent: np.ndarray,
               chunk: int) -> dict:
    """Fill :func:`empty_work` for chunks of ``n`` tokens each packed back
    to back from ``tok0`` (ascending), those of ``recurrent`` through the
    recurrent form: an item a (chunk, window) in token order with its
    rows ``lo .. hi`` of the window.  Returns the step's share of the work
    counters."""
    if len(n):
        w0, w1 = tok0 // chunk, (tok0 + n - 1) // chunk
        per = w1 - w0 + 1
        of = np.repeat(np.arange(len(n)), per)       # the item's chunk
        first = np.cumsum(per) - per
        window = w0[of] + np.arange(per.sum()) - first[of]
        lo = np.maximum(tok0[of], window * chunk)
        hi = np.minimum(tok0[of] + n[of], (window + 1) * chunk)
        I = len(of)
        edge = np.ones(I + 1, bool)
        edge[1:-1] = window[1:] != window[:-1]
        work["window"][:I] = window
        work["lo"][:I], work["hi"][:I] = lo - window * chunk, \
            hi - window * chunk
        work["slot"][:I] = slot[of]
        work["next"][:I] = (first + per)[of]     # the next chunk's first
        work["flags"][:I] = (
            FIRST * (lo == tok0[of]) + LAST * (hi == tok0[of] + n[of])
            + FRESH * (fresh[of] > 0) + RECURRENT * recurrent[of]
            + OPENS * edge[:-1] + CLOSES * edge[1:])
        work["n_items"] = np.int32(I)
        work["n_tokens"] = np.int32(tok0[-1] + n[-1])
    long = n[~recurrent]
    full, rest = long // chunk, long % chunk
    return {"ssm_recurrent_tokens": int(n[recurrent].sum()),
            "ssm_scan_tokens": int(long.sum()),
            "ssm_scan_blocks": int((-(-long // chunk)).sum()),
            # (token, earlier-or-same token) pairs inside the blocks
            "ssm_scan_pairs": int((full * (chunk * (chunk + 1) // 2)
                                   + rest * (rest + 1) // 2).sum())}


def ssm_scan(x, B, C, dt, A, pool, layer, work: dict, chunk: int):
    """``x`` ``[T, H * P]``, ``B`` / ``C`` ``[T, G * N]``, ``dt`` ``[T,
    H]`` float32, ``A`` ``[H]`` float32, ``pool`` ``[layers, slots, N, H *
    P]`` (``layer`` this layer's row), ``work``: :func:`work_lists`; ``T``
    a multiple of ``chunk``.  Returns ``(y [T, H * P]`` in ``x``'s dtype,
    rows of no chunk zero, ``pool)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, H = dt.shape
    N, P = pool.shape[2], x.shape[1] // H
    G = B.shape[1] // N
    K, Q = H // G, chunk
    W = K * P                       # a group's lanes of the state
    m = max(1, min(K, 128 // P))    # heads side by side in a lane tile
    LW, tiles = m * P, K // m
    Kp = max(K, Q)                  # a block's per-head sums transpose square
    f32, dtype = jnp.float32, x.dtype
    if T % Q or K % m:
        raise ValueError(f"{T} tokens in windows of {Q}, {K} heads a group "
                         f"in lane tiles of {m}: not whole")
    highest = jax.lax.Precision.HIGHEST
    dot = lambda a, b, **kw: jnp.dot(a, b, preferred_element_type=f32, **kw)

    def kernel(window, lo_ref, hi_ref, slot, flags, nxt, layer_ref,
               x_ref, b_ref, c_ref, dt_ref, a_row_ref, a_sm, dt_sm, pool_ref,
               y_ref, pool_out, S, sbuf, ssem, stage, osem, xf, yf, bt, ct,
               ctr):
        g, i = pl.program_id(0), pl.program_id(1)
        items = pl.num_programs(1)
        at_end = (g == pl.num_programs(0) - 1) & (i == items - 1)
        flag = flags[i]
        has = lambda bit: (flag & bit) != 0
        lo, hi = lo_ref[i], hi_ref[i]

        def share(ref, of, gg):
            return ref.at[layer_ref[0], slot[of], :, pl.ds(gg * W, W)]

        def fetch(of, gg, p):
            return pltpu.make_async_copy(share(pool_ref, of, gg), sbuf.at[p],
                                         ssem.at[p])

        def fetched(of):             # a chunk's first item, not fresh
            return (flags[of] & (FIRST | FRESH)) == FIRST

        def put(of, gg, p):
            return pltpu.make_async_copy(stage.at[p], share(pool_out, of, gg),
                                         osem.at[p])

        def ask():
            """Start the next share's way into the ring, the one after the
            last asked for (its group, item and buffer: ``ctr[2:5]``)."""
            gq, iq, pq = ctr[2], ctr[3], ctr[4]

            @pl.when(gq < pl.num_programs(0))
            def _():
                pl.when(fetched(iq))(fetch(iq, gq, pq).start)
                then = nxt[iq]
                over = then >= items
                ctr[2] = gq + over.astype(jnp.int32)
                ctr[3] = jnp.where(over, 0, then)
                ctr[4] = (pq + 1) % DEPTH

        @pl.when((g == 0) & (i == 0))
        def _():
            ctr[0] = 0               # the buffer this chunk's share comes to
            ctr[1] = 0               # shares sent back so far
            ctr[2] = ctr[3] = ctr[4] = 0
            for _ in range(DEPTH - 1):
                ask()

        p = ctr[0]

        @pl.when(has(FIRST))
        def _():
            @pl.when(has(FRESH))
            def _():
                S[...] = jnp.zeros(S.shape, f32)

            @pl.when(jnp.logical_not(has(FRESH)))
            def _():
                fetch(i, g, p).wait()
                S[...] = sbuf[p].astype(f32)

            ask()                    # into the buffer before this one

        @pl.when(has(OPENS))
        def _():
            yf[...] = jnp.zeros(yf.shape, f32)
            xf[...] = x_ref[...].astype(f32)
            bt[...] = b_ref[...].astype(f32).T.astype(bt.dtype)
            ct[...] = c_ref[...].astype(f32).T.astype(ct.dtype)

        lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, LW), 1) // P

        def by_head(j, of_head):
            """A lane tile's ``[.., LW]`` pattern: ``of_head(k)`` on the
            lanes of head ``k`` of tile ``j``."""
            out = of_head(j * m)
            for q in range(1, m):
                out = jnp.where(lane_head >= q, of_head(j * m + q), out)
            return out

        @pl.when(has(RECURRENT))
        def _():
            rows = jax.lax.broadcasted_iota(jnp.int32, (Q, LW), 0)
            exact = {"precision": highest} if dtype == f32 else {}

            def token(r, _):
                at = lo + r
                # B_t and C_t down the sublanes, alike on every lane
                hot = (rows == at).astype(dtype)
                Bb = dot(bt[...], hot, **exact)
                Cb = dot(ct[...], hot, **exact)
                xrow = xf[pl.ds(at, 1), :]
                # a row is stored through its aligned slab of sublanes
                slab = pl.ds(pl.multiple_of(at // 8 * 8, 8), 8)
                in_slab = jax.lax.broadcasted_iota(
                    jnp.int32, (8, LW), 0) == at % 8
                for j in range(tiles):
                    sl = slice(j * LW, (j + 1) * LW)
                    a = by_head(j, lambda k: a_sm[0, at * K + k])
                    d = by_head(j, lambda k: dt_sm[0, at * K + k])
                    Sj = S[:, sl] * a + Bb * (d * xrow[:, sl])
                    S[:, sl] = Sj
                    yf[slab, sl] = jnp.where(
                        in_slab, (Sj * Cb).sum(axis=0, keepdims=True),
                        yf[slab, sl])
                return 0

            jax.lax.fori_loop(0, hi - lo, token, 0)

        @pl.when(jnp.logical_not(has(RECURRENT)))
        def _():
            row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
            mine = (row >= lo) & (row < hi)
            t_ge_s = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) \
                >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
            dtm = jnp.where(mine, dt_ref[...], 0.0)            # [Q, Kp]
            cum = dot(t_ge_s.astype(f32), dtm * a_row_ref[...],
                      precision=highest)                       # through t
            cum_t, dt_t = cum.T, dtm.T                         # [Kp, Q]
            Bq, Cq = b_ref[...], c_ref[...]
            cb = jax.lax.dot_general(Cq, Bq, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
            lane_k = jax.lax.broadcasted_iota(jnp.int32, (Q, LW), 1) // P
            # onto the lanes of the tile's head ``q`` and after
            spread = lambda q, new, old: new if old is None \
                else jnp.where(lane_k >= q, new, old)
            for j in range(tiles):
                sl = slice(j * LW, (j + 1) * LW)
                xq = x_ref[:, sl]
                y = cum_j = dt_j = None
                for q in range(m):
                    k = j * m + q
                    col = cum[:, k:k + 1]
                    decay = jnp.exp(jnp.where(
                        t_ge_s, col - cum_t[k:k + 1, :], -jnp.inf))
                    scores = (decay * cb * dt_t[k:k + 1, :]).astype(dtype)
                    y = spread(q, dot(scores, xq), y)
                    cum_j = spread(q, col, cum_j)
                    dt_j = spread(q, dtm[:, k:k + 1], dt_j)
                Sj = S[:, sl]
                y = y + jnp.exp(cum_j) * dot(Cq, Sj.astype(dtype))
                yf[:, sl] = jnp.where(mine, y, yf[:, sl])
                end = cum_j[Q - 1:Q, :]
                xs = (xq.astype(f32) * (jnp.exp(end - cum_j) * dt_j)
                      ).astype(dtype)
                S[:, sl] = jnp.exp(end) * Sj + dot(bt[...], xs)

        @pl.when(has(CLOSES))
        def _():
            y_ref[...] = yf[...].astype(y_ref.dtype)

        @pl.when(has(LAST))
        def _():
            sent = ctr[1]
            q = sent % DEPTH
            pl.when(sent >= DEPTH)(put(i, g, q).wait)
            stage[q] = S[...].astype(stage.dtype)
            put(i, g, q).start()
            ctr[1] = sent + 1
            ctr[0] = (p + 1) % DEPTH

        @pl.when(at_end)
        def _():
            sent = ctr[1]
            for back in range(DEPTH):
                pl.when(sent > back)(
                    put(i, g, (sent - 1 - back) % DEPTH).wait)

    rows = lambda width: pl.BlockSpec(
        (Q, width), lambda g, i, window, *_: (window[i], g))
    scalars = pl.BlockSpec((None, None, 1, Q * K),
                           lambda g, i, window, *_: (g, window[i], 0, 0),
                           memory_space=pltpu.SMEM)
    # Mosaic where the program is lowered for the TPU (a chip attached or
    # described), the Pallas interpreter elsewhere
    call = lambda interpret: lambda *args: pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(G, work["n_items"]),
            in_specs=[
                rows(W), rows(N), rows(N),
                pl.BlockSpec((None, Q, Kp),
                             lambda g, i, window, *_: (g, window[i], 0)),
                pl.BlockSpec((None, 1, Kp), lambda g, i, *_: (g, 0, 0)),
                scalars, scalars,
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[rows(W), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((N, W), f32),
                pltpu.VMEM((DEPTH, N, W), pool.dtype),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.VMEM((DEPTH, N, W), pool.dtype),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.VMEM((Q, W), f32), pltpu.VMEM((Q, W), f32),
                pltpu.VMEM((N, Q), dtype), pltpu.VMEM((N, Q), dtype),
                pltpu.SMEM((5,), jnp.int32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((T, H * P), dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={14: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret)(*args)

    # the recurrent form's factors a token and head are scalars: a
    # window's, group-major
    by_window = lambda a: a.reshape(T // Q, Q, G, K).transpose(
        2, 0, 1, 3).reshape(G, T // Q, 1, Q * K)
    by_group = lambda a: jnp.pad(
        a.reshape(-1, G, K).transpose(1, 0, 2),
        ((0, 0), (0, 0), (0, Kp - K)))
    y, pool = jax.lax.platform_dependent(
        work["window"], work["lo"], work["hi"], work["slot"], work["flags"],
        work["next"],
        jnp.asarray(layer, jnp.int32).reshape(1),
        x, B, C, by_group(dt), by_group(A[None]), by_window(jnp.exp(dt * A)),
        by_window(dt), pool,
        tpu=call(False), default=call(True))
    # a window no item opened holds whatever the buffer held
    return jnp.where(jnp.arange(T)[:, None] < work["n_tokens"], y, 0), pool
