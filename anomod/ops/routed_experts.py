"""Routed experts for a layer that is TOLD which experts it holds.

The router scores every token over all ``n_routed`` experts and picks its
top-k; the layer computes the part of the result that its own experts
``[lo, lo + held)`` give and leaves the absent experts' part out (they
live on other chips; nothing here stands in for them).

Dispatch drops no token whatever the skew: the token-expert pairs are
sorted by expert, the held ones first, and taken through a grouped matmul
(``jax.lax.ragged_dot`` over the held experts) in rounds of ``capacity``
rows; the number of rounds is data (one where routing is even, up to
``top_k * T / capacity`` where every token lands here).  Where
``capacity`` holds every pair of the step (:func:`one_round`) there is
one round, run once, and each token gathers its ``top_k`` weighted rows
back from the expert-sorted result through the sort's inverse
permutation; where rounds are many, each round scatter-adds its rows into
the result.

Two call names reach the device ops' metadata (how a trace reduction
prices the parts; the grouped matmuls themselves keep only the name their
expansion gives them, ``ragged-dot-none``): :data:`ROUTE_SCOPE` over the
router and the sort, counts, offsets and inverse permutation before the
rounds, :data:`ROUNDS_SCOPE` over everything else of the rounds.
"""

from __future__ import annotations

ROUTE_SCOPE = "anomod_seq_route"
ROUNDS_SCOPE = "anomod_seq_rounds"


def route(x, w_router, bias, top_k: int, scaling: float, norm_topk: bool,
          score: str = "sigmoid"):
    """Scores in float32 (``score``: ``"sigmoid"`` of each logit, or
    ``"softmax"`` over all of them), top-k of ``score + bias`` (the bias
    only chooses; ``None``: the router has none), weights from the
    unbiased scores, normalised and scaled.  Returns ``(experts [T, k]
    int32, weights [T, k] float32)``."""
    import jax
    import jax.numpy as jnp

    def pick(x, w_router, bias):
        logits = jnp.dot(x.astype(jnp.float32), w_router,
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits) if score == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        _, experts = jax.lax.top_k(s if bias is None else s + bias, top_k)
        w = jnp.take_along_axis(s, experts, axis=1)
        if norm_topk:
            w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), w * scaling

    return jax.named_call(pick, name=ROUTE_SCOPE)(x, w_router, bias)


def gated_silu(xs, dot, w_gate, w_up, w_down):
    """An expert's body with three weights: ``(silu(x W_gate) * (x W_up))
    W_down`` (``w_gate`` / ``w_up`` ``[E, D, F]``, ``w_down`` ``[E, F,
    D]``); ``dot`` is the grouped matmul over the round's rows."""
    import jax
    mid = (jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)).astype(xs.dtype)
    return dot(mid, w_down)


def relu2(xs, dot, w_1, w_2):
    """An expert's body with two weights, not gated: ``relu(x W_1)^2 W_2``
    (``w_1`` ``[E, D, F]``, ``w_2`` ``[E, F, D]``)."""
    import jax.numpy as jnp
    mid = jnp.square(jnp.maximum(dot(xs, w_1), 0.0)).astype(xs.dtype)
    return dot(mid, w_2)


def one_round(tokens: int, top_k: int, capacity: int) -> bool:
    """Whether :func:`held_expert_sum` takes every pair of ``tokens``
    tokens in ONE round of ``capacity`` rows (and combines by gather)."""
    return capacity >= tokens * top_k


def held_expert_sum(x, experts, weights, valid, body, expert_weights,
                    lo: int, capacity: int):
    """``sum_e w_e * expert_e(x)`` over the held experts ``[lo, lo + E)``
    for the ``valid`` rows of ``x`` ``[T, D]``; the expert is ``body(rows,
    dot, *expert_weights)`` (:func:`gated_silu`, :func:`relu2`), every
    weight stacked ``[E, ...]``.  Returns ``(out [T, D] float32, tokens
    per held expert [E] int32)``.

    One round (:func:`one_round`): the ``T * k`` pairs once through the
    experts, and each token sums its ``k`` weighted rows, gathered from
    the expert-sorted result.  More than one: a loop of rounds of
    ``capacity`` rows, each scatter-adding its rows into ``out``."""
    import jax
    import jax.numpy as jnp
    T, k = experts.shape
    E = expert_weights[0].shape[0]
    single = one_round(T, k, capacity)

    def dispatch(experts, valid):
        local = experts - lo
        held = (local >= 0) & (local < E) & valid[:, None]
        local = jnp.where(held, local, E).reshape(-1)     # E sorts last
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        counts = jnp.zeros((E + 1,), jnp.int32).at[local].add(1)[:E]
        ends = jnp.cumsum(counts)
        if single:
            # where each pair sits in the expert-sorted order
            pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
                jnp.arange(T * k, dtype=jnp.int32), unique_indices=True)
            return order, pos, counts, ends
        order = jnp.concatenate([order, jnp.zeros((capacity,), jnp.int32)])
        return order, counts, ends - counts, ends

    def one(x, weights, order, pos, counts, ends, *expert_weights):
        dot = lambda a, w: jax.lax.ragged_dot(
            a, w, counts, preferred_element_type=jnp.float32)
        y = body(x[order // k], dot, *expert_weights)
        # rows past the held pairs are the kernel's to leave undefined
        y = jnp.where((pos < ends[-1])[:, None],
                      y[pos] * weights.reshape(-1)[:, None], 0.0)
        return y.reshape(T, k, -1).sum(axis=1)

    def rounds(x, weights, order, starts, ends, *expert_weights):
        n_held = ends[-1]
        tok_of = jnp.arange(T * k, dtype=jnp.int32) // k
        w_flat = weights.reshape(-1)
        lane = jnp.arange(capacity, dtype=jnp.int32)

        def round_body(r, out):
            base = r * capacity
            pair = jax.lax.dynamic_slice_in_dim(order, base, capacity)
            live = base + lane < n_held
            tok = tok_of[pair]
            sizes = (jnp.clip(ends - base, 0, capacity)
                     - jnp.clip(starts - base, 0, capacity))
            xs = x[tok]
            dot = lambda a, w: jax.lax.ragged_dot(
                a, w, sizes, preferred_element_type=jnp.float32)
            # rows past the held pairs are the kernel's to leave undefined
            y = jnp.where(live[:, None],
                          body(xs, dot, *expert_weights)
                          * w_flat[pair][:, None], 0.0)
            return out.at[jnp.where(live, tok, T)].add(y, mode="drop")

        return jax.lax.fori_loop(
            0, (n_held + capacity - 1) // capacity, round_body,
            jnp.zeros((T, x.shape[1]), jnp.float32))

    if single:
        order, pos, counts, ends = jax.named_call(
            dispatch, name=ROUTE_SCOPE)(experts, valid)
        out = jax.named_call(one, name=ROUNDS_SCOPE)(
            x, weights, order, pos, counts, ends, *expert_weights)
        return out, counts
    order, counts, starts, ends = jax.named_call(
        dispatch, name=ROUTE_SCOPE)(experts, valid)
    out = jax.named_call(rounds, name=ROUNDS_SCOPE)(
        x, weights, order, starts, ends, *expert_weights)
    return out, counts
