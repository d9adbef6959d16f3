"""The Mamba-2 recurrence over a PACKED step of ragged per-session chunks,
each continuing from its own carried state, in two forms.

A session's state ``S`` ``[heads, head_dim, state]`` lives in a slot of a
pool ``[layers, slots, heads, head_dim, state]`` between steps (the
pool's dtype; float32 inside the step).  A step appends a packed batch of
chunks of many sessions; chunk ``g`` is tokens ``tok0[g] .. tok0[g] +
n[g]`` of the packed arrays.  Per token and head (``A < 0`` a head, ``dt >
0`` after the softplus, head ``j`` reads group ``j // (heads / groups)`` of
``B`` and ``C``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t

- **recurrent** (short chunks): exactly that, a token a trip, elementwise
  in float32.  A trip reads and writes the batch's states, so a token
  costs a state's bytes twice: the decode step's form.
- **chunked** (long chunks): a block of ``chunk`` tokens a trip from the
  carried state; inside the block the decays are a lower-triangular
  matrix ``L[t, s] = exp(sum_{s < r <= t} dt_r A)`` and everything is
  matmuls: ``y = (L * C B^T * dt) x + exp(cum) C S``, ``S' = exp(cum_end)
  S + (exp(cum_end - cum) dt x)^T B``.  A chunk's blocks follow each other
  inside one batch, the state carried in float32.

Both are equal in exact arithmetic.  Work lists (:func:`work_lists`):
batches of ``RECURRENT_BATCH`` / ``SCAN_BATCH`` chunks, the longest first,
a batch taking as many trips as its longest chunk; trip counts are data
(``lax.fori_loop`` with traced bounds), every shape is static.  A batch
gathers its states from the pool, a fresh session's replaced by zeros (a
fresh slot is never zeroed), and writes them back IN PLACE where the
caller donates the pool.  Which form a chunk takes is decided on the host
from sizes alone (:func:`recurrent_is_cheaper`).
"""

from __future__ import annotations

import numpy as np

RECURRENT_BATCH = 32   # chunks of a recurrent batch
SCAN_BATCH = 8         # chunks of a chunked batch
#: FLOPs the chip does in the time it moves a byte (TPU v5e: 197e12 /
#: 819e9), the rate at which the size rule trades matmuls for traffic
FLOPS_PER_BYTE = 240.0
#: the name of the call that holds both forms: every device op of theirs
#: carries it in the trace's op metadata
SCOPE = "anomod_seq_ssm"


def recurrent_is_cheaper(n, heads: int, head_dim: int, state: int,
                         groups: int, chunk: int):
    """Whether the recurrent form costs less than the chunked one on a
    chunk of ``n`` tokens (a whole number or an array), as the two forms
    spend it, in bytes of traffic with matmul FLOPs at
    :data:`FLOPS_PER_BYTE`: a recurrent trip moves the float32 state in
    and out for one token; a chunked trip moves it once for a block of
    ``chunk`` tokens (rows padded to the block), writes and reads the
    block's ``[heads, chunk, chunk]`` float32 decay and score matrices
    and pays the block's four matmuls."""
    state_io = 2 * 4 * heads * head_dim * state
    flops = 2 * chunk * (groups * chunk * state + heads * chunk * head_dim
                         + 2 * heads * head_dim * state)
    block = state_io + 3 * 4 * heads * chunk * chunk + flops / FLOPS_PER_BYTE
    return n * state_io <= -(-n // chunk) * block


def work_caps(segments: int) -> dict:
    """Static rows of the two work lists for at most ``segments`` chunks."""
    return {"rec_batches": -(-segments // RECURRENT_BATCH),
            "scan_batches": -(-segments // SCAN_BATCH)}


def empty_work(caps: dict, pad_segment: int) -> dict:
    z = lambda b, w: {"seg": np.full((b, w), pad_segment, np.int32),
                      "trips": np.zeros((b,), np.int32),
                      "n_batches": np.int32(0)}
    return {"rec": z(caps["rec_batches"], RECURRENT_BATCH),
            "scan": z(caps["scan_batches"], SCAN_BATCH)}


def work_lists(work: dict, n: np.ndarray, recurrent: np.ndarray,
               chunk: int) -> dict:
    """Fill :func:`empty_work` for chunks of ``n`` tokens each, those of
    ``recurrent`` through the recurrent form: each form's chunks the
    longest first, a batch's trips its longest chunk's.  Returns the
    step's share of the work counters."""
    for form, width, unit, pick in (
            ("rec", RECURRENT_BATCH, 1, recurrent),
            ("scan", SCAN_BATCH, chunk, ~recurrent)):
        segs = np.nonzero(pick)[0]
        segs = segs[np.argsort(-n[segs], kind="stable")]
        w = work[form]
        batches = -(-len(segs) // width)
        flat = w["seg"].reshape(-1)
        flat[:len(segs)] = segs
        w["trips"][:batches] = -(-n[segs[::width]] // unit)
        w["n_batches"] = np.int32(batches)
    long = n[~recurrent]
    full, rest = long // chunk, long % chunk
    return {"ssm_recurrent_tokens": int(n[recurrent].sum()),
            "ssm_scan_tokens": int(long.sum()),
            "ssm_scan_blocks": int((-(-long // chunk)).sum()),
            # (token, earlier-or-same token) pairs inside the blocks
            "ssm_scan_pairs": int((full * (chunk * (chunk + 1) // 2)
                                   + rest * (rest + 1) // 2).sum())}


def _token_trip(S, xb, Bb, Cb, dtb, A):
    """One token of every chunk of a batch: ``S`` ``[W, G, K, P, N]``
    float32, ``xb`` ``[W, 1, G, K, P]``, ``Bb`` / ``Cb`` ``[W, 1, G, N]``,
    ``dtb`` ``[W, 1, G, K]`` float32 (0 where the chunk has no such
    token: the state passes unchanged)."""
    import jax.numpy as jnp
    f32 = jnp.float32
    d = dtb[:, 0]
    S = jnp.exp(d * A)[..., None, None] * S \
        + (d[..., None] * xb[:, 0].astype(f32))[..., None] \
        * Bb[:, 0].astype(f32)[:, :, None, None, :]
    y = (S * Cb[:, 0].astype(f32)[:, :, None, None, :]).sum(axis=-1)
    return S, y[:, None]


def _block_trip(S, xb, Bb, Cb, dtb, A):
    """One block of ``Q`` tokens of every chunk of a batch: ``xb`` ``[W,
    Q, G, K, P]``, ``Bb`` / ``Cb`` ``[W, Q, G, N]``, ``dtb`` ``[W, Q, G,
    K]`` float32 (0 past a chunk's end)."""
    import jax.numpy as jnp
    f32 = jnp.float32
    Q, dtype = xb.shape[1], xb.dtype
    dot = lambda spec, a, b: jnp.einsum(spec, a, b,
                                        preferred_element_type=f32)
    dt_h = jnp.moveaxis(dtb, 1, -1)                       # [W, G, K, Q]
    cum = jnp.cumsum(dt_h * A[..., None], axis=-1)        # through token t
    lane = jnp.arange(Q)
    decay = jnp.exp(jnp.where(lane[:, None] >= lane[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                  # [W, G, K, t, s]
    cb = dot("wtgn,wsgn->wgts", Cb, Bb)
    scores = decay * cb[:, :, None] * dt_h[..., None, :]
    y = dot("wgkts,wsgkp->wtgkp", scores.astype(dtype), xb) \
        + dot("wtgn,wgkpn->wtgkp", Cb, S.astype(dtype)) \
        * jnp.moveaxis(jnp.exp(cum), -1, 1)[..., None]
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum) * dt_h, -1, 1)
    S = jnp.exp(cum[..., -1])[..., None, None] * S + dot(
        "wsgkp,wsgn->wgkpn", (xb.astype(f32) * to_end[..., None]
                              ).astype(dtype), Bb)
    return S, y


def ssm_scan(x, B, C, dt, A, pool, layer: int, seg: dict, work: dict,
             chunk: int):
    """``x`` ``[T, H, P]``, ``B`` / ``C`` ``[T, G, N]``, ``dt`` ``[T, H]``
    float32, ``A`` ``[H]`` float32, ``pool`` ``[layers, slots, H, P, N]``
    (``layer`` this layer's row); ``seg``: ``tok0``, ``n``, ``slot``,
    ``fresh`` ``[S + 1]`` (the last row the pad chunk: no token, the
    never-allocated slot 0); ``work``: :func:`work_lists`.  Returns ``(y
    [T, H, P]`` in ``x``'s dtype, rows of no chunk zero, ``pool)``."""
    import jax
    import jax.numpy as jnp
    T, H, P = x.shape
    G, N = B.shape[1:]
    K = H // G
    f32 = jnp.float32
    xg = x.reshape(T, G, K, P)
    dtg = dt.reshape(T, G, K)
    Ag = A.reshape(G, K)

    def run(form, unit, trip_fn, carry):
        w = work[form]
        lane = jnp.arange(unit, dtype=jnp.int32)

        def batch(b, carry):
            y, pool = carry
            sg = w["seg"][b]
            tok0, n, slot = seg["tok0"][sg], seg["n"][sg], seg["slot"][sg]
            S0 = jnp.where(seg["fresh"][sg][:, None, None, None, None] > 0,
                           0.0, pool[layer, slot].astype(f32).reshape(
                               -1, G, K, P, N))

            def trip(i, carry):
                S, y = carry
                at = i * unit + lane
                idx = tok0[:, None] + at
                live = at < n[:, None]
                src = jnp.minimum(idx, T - 1)
                S, yb = trip_fn(S, xg[src], B[src], C[src], jnp.where(
                    live[..., None, None], dtg[src], 0.0), Ag)
                y = y.at[jnp.where(live, idx, T).reshape(-1)].set(
                    yb.reshape(-1, G, K, P).astype(y.dtype), mode="drop")
                return S, y

            S, y = jax.lax.fori_loop(0, w["trips"][b], trip, (S0, y))
            pool = pool.at[layer, slot].set(
                S.reshape(-1, H, P, N).astype(pool.dtype))
            return y, pool

        return jax.lax.fori_loop(0, w["n_batches"], batch, carry)

    carry = (jnp.zeros((T, G, K, P), x.dtype), pool)
    carry = run("rec", 1, _token_trip, carry)
    y, pool = run("scan", chunk, _block_trip, carry)
    return y.reshape(T, H, P), pool
