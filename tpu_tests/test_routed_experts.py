"""The routed experts' two combines compiled on the chip at
``sdar-fleet-overload``'s widths (a step of 8,192 rows, top-8 of 128
softmax-routed experts, hidden 2,048, expert width 768, bfloat16 rows and
weights): the one round that gathers each row's 8 weighted results back,
against the loop whose one round scatter-adds them (reached with
``capacity`` one row under the pairs and one row of no pairs), with the
time a call of each and of the two ways to build the inverse permutation
printed.  ``tests/test_routed_experts.py`` holds the two forms to each
other in tier-1 at a tiny size."""

import json
import os
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, K, D, F, E = 8192, 8, 2048, 768, 128


def timed(fn, args, n: int = 10):
    """``(result, ms a call)``: one call to compile and warm, then ``n``."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        again = fn(*args)
    jax.block_until_ready(again)
    return out, (time.perf_counter() - t0) * 1e3 / n


def record(line: dict) -> None:
    print("ROUTED", json.dumps(line))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "routed_experts.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def test_one_round_by_gather_against_the_scatter_add_round():
    import jax
    import jax.numpy as jnp

    from anomod.ops import routed_experts as rx

    chip = jax.devices()[0]
    assert chip.platform == "tpu"
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(43), 5)
    x = jax.random.normal(ks[0], (T, D), bf)
    router = jax.random.normal(ks[1], (D, E), jnp.float32) * D ** -0.5
    w = tuple((jax.random.normal(k, s, bf) * s[1] ** -0.5).astype(bf)
              for k, s in zip(ks[2:], [(E, D, F), (E, D, F), (E, F, D)]))
    experts, weights = jax.jit(lambda x, r: rx.route(
        x, r, None, K, 1.0, True, score="softmax"))(x, router)
    # the last row holds no pair: a loop of capacity T K - 1 takes ONE
    # round, the parent's shape with its scatter-add
    valid = jnp.arange(T) < T - 1
    args = jax.device_put((x, experts, weights, valid) + w, chip)
    lines = {}
    for form, capacity in (("gather", T * K), ("scatter_add", T * K - 1)):
        assert rx.one_round(T, K, capacity) == (form == "gather")
        fn = jax.jit(lambda x, e, wt, v, *w, capacity=capacity:
                     rx.held_expert_sum(x, e, wt, v, rx.gated_silu, w, 0,
                                        capacity))
        (out, counts), ms = timed(fn, args)
        lines[form] = (np.asarray(out), ms)
        assert int(counts.sum()) == (T - 1) * K
    (got, ms_gather), (want, ms_scatter) = lines["gather"], \
        lines["scatter_add"]
    top = float(np.abs(want).max())
    gap = float(np.abs(got - want).max())
    record({"T": T, "k": K, "D": D, "F": F, "E": E,
            "ms_gather": round(ms_gather, 3),
            "ms_scatter_add": round(ms_scatter, 3),
            "ms_saved": round(ms_scatter - ms_gather, 3),
            "gap_over_top": gap / top})
    assert np.isfinite(got).all() and not got[T - 1].any()
    # the same float32 products, summed in another order
    assert gap < 1e-5 * top


def test_the_inverse_permutation_by_scatter_and_by_sort():
    """The two ways to invert the dispatch's order at the cell's ``T K``
    pairs, timed, beside the dispatch's own sort."""
    import jax
    import jax.numpy as jnp

    n = T * K
    local = jax.random.randint(jax.random.PRNGKey(5), (n,), 0, E + 1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)
    by_scatter = jax.jit(lambda o: jnp.zeros((n,), jnp.int32).at[o].set(
        iota, unique_indices=True))
    by_sort = jax.jit(lambda o: jnp.argsort(o).astype(jnp.int32))
    sort = jax.jit(lambda l: jnp.argsort(l, stable=True).astype(jnp.int32))
    a, ms_scatter = timed(by_scatter, (order,), 50)
    b, ms_sort = timed(by_sort, (order,), 50)
    _, ms_dispatch_sort = timed(sort, (local,), 50)
    record({"pairs": n, "ms_inverse_scatter": round(ms_scatter, 4),
            "ms_inverse_argsort": round(ms_sort, 4),
            "ms_dispatch_argsort": round(ms_dispatch_sort, 4)})
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(order)[np.asarray(a)] == np.arange(n)).all()
