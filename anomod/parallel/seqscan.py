"""Sequence-parallel temporal scan: the long-context plane for time series.

GRUs are non-associative, so they cannot shard over time.  For long window
streams (days of 15 s samples — far beyond one device's comfortable scan
length) the temporal recurrence is expressed as a **linear recurrence**

    h_t = a ⊙ h_{t-1} + x_t,   a ∈ (0,1)^C  (per-channel decay)

whose composition law ``(a1,b1)∘(a2,b2) = (a1·a2, a2·b1 + b2)`` is
associative.  Within a device it runs as ``lax.associative_scan`` (log-depth,
VPU-friendly); across devices the window axis is sharded and the classic
block-scan applies: local scan → all_gather of the [D] block aggregates over
ICI → exclusive prefix (computed redundantly per device, D is tiny) → local
correction.  Exact to floating-point reassociation, verified against the
single-device scan on the CPU mesh.
"""

from __future__ import annotations


def linear_recurrence(xs, decay):
    """Single-device reference: h_t = decay ⊙ h_{t-1} + xs_t over axis 0.

    xs: [T, ...]; decay: broadcastable to xs[0].  Returns all states [T, ...].
    """
    import jax
    import jax.numpy as jnp

    a = jnp.broadcast_to(decay, xs.shape[1:])
    a_seq = jnp.broadcast_to(a, xs.shape)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a_seq, xs), axis=0)
    return h


def make_seqpar_recurrence(mesh, axis: str = "data"):
    """Sequence-parallel linear recurrence: window axis sharded over ``axis``.

    Returns fn(xs [T, ...], decay) -> [T, ...] with T % mesh_size == 0;
    xs arrives sharded on axis 0, output leaves sharded the same way.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]

    def body(xs_local, decay):
        # decay is replicated (P()) hence device-invariant; mark it varying so
        # every derived carry/aggregate has consistent vma annotations
        decay = jax.lax.pcast(decay, (axis,), to="varying")
        # local block scan
        h_local = linear_recurrence(xs_local, decay)             # [T/D, ...]
        t_local = xs_local.shape[0]
        a = jnp.broadcast_to(decay, xs_local.shape[1:])
        block_a = a ** t_local                                   # decay^T/D
        block_b = h_local[-1]
        # gather all block aggregates: [D, ...]
        all_a = jax.lax.all_gather(block_a, axis)
        all_b = jax.lax.all_gather(block_b, axis)
        # exclusive prefix over blocks (serial over D — D is the mesh size)
        idx = jax.lax.axis_index(axis)

        def step(carry, ab):
            a_i, b_i = ab
            new = (carry[0] * a_i, a_i * carry[1] + b_i)
            return new, carry[1]          # emit EXCLUSIVE prefix state

        init = (jnp.ones_like(block_a), jnp.zeros_like(block_b))
        _, prefix_states = jax.lax.scan(step, init, (all_a, all_b))
        carry_in = prefix_states[idx]                            # [...]
        # correction: h_t += a^(t+1) * carry_in within the local block
        t_idx = jnp.arange(1, t_local + 1).reshape(
            (t_local,) + (1,) * (xs_local.ndim - 1))
        corr = (a[None] ** t_idx) * carry_in[None]
        return h_local + corr

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(axis))
    return jax.jit(fn)
