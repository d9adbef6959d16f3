"""The routed experts' two combines (``ops/routed_experts.held_expert_sum``):
a step whose pairs fit one round gathers each token's rows back from the
expert-sorted result, a step of more rounds scatter-adds each round's
rows.  Both compute the same float32 products; only the order of a
token's sum differs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from anomod.ops import routed_experts as rx
from anomod.serve import seqplane as sp
from test_block_diffusion_moe import TINY as SDAR
from test_hybrid_ssm_moe import TINY as N3S
from test_latent_moe import TINY as K2
from test_seq_scopes import instructions, scopes_of
from test_swa_moe import TINY as LXS2
from test_swa_moe import _served

T, D, F, E, K, LO = 40, 32, 24, 4, 3, 4


def inputs(case: str, dtype, seed: int = 0):
    """``(x, experts, weights, valid)`` of a case; rows that no held pair
    reads are NaN where the case has them, so that a combine that lets an
    undefined row through reads NaN."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    experts = jax.random.randint(ks[1], (T, K), LO, LO + E)
    weights = jax.random.uniform(ks[2], (T, K), jnp.float32)
    valid = jnp.ones((T,), bool)
    if case == "invalid_rows":
        valid = jnp.arange(T) % 5 != 2
        x = jnp.where(valid[:, None], x, jnp.nan)
    elif case == "outside_held":
        experts = jax.random.randint(ks[1], (T, K), 0, 3 * E)
    elif case == "one_expert":
        experts = jnp.full((T, K), LO + 1)
    elif case == "none_held":
        experts = jax.random.randint(ks[1], (T, K), LO + E, LO + 3 * E)
        x = jnp.full((T, D), jnp.nan)
    return x.astype(dtype), experts.astype(jnp.int32), weights, valid


def expert_weights(dtype, seed: int = 1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, s, jnp.float32).astype(dtype) * 0.2
                 for k, s in zip(ks, [(E, D, F), (E, D, F), (E, F, D)]))


def leaky(xs, dot, *w):
    """:func:`rx.gated_silu` with each row's NaN carried through, as a
    grouped matmul may leave the rows past the held pairs undefined."""
    return rx.gated_silu(xs, dot, *w) + 0.0 * xs[:, :1].astype(jnp.float32)


def summed(capacity: int):
    return jax.jit(lambda x, e, wt, v, *w: rx.held_expert_sum(
        x, e, wt, v, leaky, w, LO, capacity))


CASES = ["even", "invalid_rows", "outside_held", "one_expert", "none_held",
         "capacity_over"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_one_round_combines_as_the_loop_of_rounds(case, dtype):
    x, experts, weights, valid = inputs(case, dtype)
    w = expert_weights(dtype)
    capacity = T * K + (37 if case == "capacity_over" else 0)
    assert rx.one_round(T, K, capacity)
    assert not rx.one_round(T, K, T * K - 1)
    got, counts = summed(capacity)(x, experts, weights, valid, *w)
    want, want_counts = summed(T * K - 1)(x, experts, weights, valid, *w)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    if case == "none_held":
        assert not np.asarray(counts).any()
        np.testing.assert_array_equal(got, np.zeros((T, D), np.float32))
        return
    # the same float32 products, summed in another order
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-7 * scale)
    if case == "invalid_rows":
        assert not got[~np.asarray(valid)].any()


def scatters_under_rounds(capacity: int) -> list:
    from jax._src.lib import xla_client
    x, experts, weights, valid = inputs("even", jnp.float32)
    lowered = summed(capacity).lower(x, experts, weights, valid,
                                     *expert_weights(jnp.float32))
    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    ops = instructions(lowered.compiler_ir(dialect="hlo").as_hlo_module()
                       .to_string(options))
    assert {rx.ROUTE_SCOPE, rx.ROUNDS_SCOPE} <= set().union(
        *(scopes_of(n) for _, n in ops))
    return [(o, n) for o, n in ops
            if o == "scatter" and scopes_of(n) == {rx.ROUNDS_SCOPE}]


@pytest.mark.parametrize("capacity, scatters", [(T * K, 0), (T * K + 8, 0),
                                                (T * K - 1, 1), (16, 1)])
def test_only_the_loop_of_rounds_scatters_its_rows(capacity, scatters):
    """One round has no scatter under the rounds' name (its float32 rows
    are gathered; the inverse permutation is the route's int32 work); the
    loop keeps its float32 scatter-add."""
    assert len(scatters_under_rounds(capacity)) == scatters


@pytest.mark.parametrize("name, spec, layers", [
    ("sdar", dict(SDAR), 2), ("lxs2", dict(LXS2, vocab_size=1024), 4),
    ("k2", dict(K2, vocab_size=1024, vocab_held=1024), 0),
    ("n3s", dict(N3S, vocab_size=1024, vocab_held=1024), 0)])
def test_the_plane_counts_the_layers_that_took_one_round(name, spec, layers):
    """``moe_one_round_layers``: every sparse layer of every step of the
    models whose capacity is a step's pairs, none of those whose rounds
    loop."""
    spec["assumed"] = dict(spec["assumed"], token_grid=[32])
    plane = sp.SeqPlane(spec, range(4), 5, 16, 5_000_000)
    rng = np.random.default_rng(3)
    for tick in range(2):
        plane.step([_served(t, rng.integers(0, 5 * 16 * 12, 5 + 3 * t),
                            tick * 10 ** 6) for t in range(4)])
    c = plane.counters
    assert c["seq_steps"] >= 2
    assert c["moe_one_round_layers"] == layers * c["seq_steps"]
