"""The Mamba-2 scan kernel Mosaic-compiled on the chip at the published
widths of ``nemotron3-super-ep8-share``, on the chunk lengths of its cell
(a power law over the tenants: most chunks one to four tokens, the busiest
several blocks), against the same kernel under the Pallas interpreter (the
host's CPU device: with the forms chosen by size, a minute and a half
of interpreter) and against the recurrence a token after another in
float32 (also with every chunk forced through each form).  ``tests/test_hybrid_ssm_moe.py`` holds the interpreter to
the plain recurrence in tier-1 at a tiny size;
``tests/test_pool_layout_compile.py`` compiles the whole step for a
described chip.  Only this one runs what Mosaic made."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests"))
#: tokens of the step, its windows' rows and the pool's slots
TOKENS, GRID, SLOTS = 1200, 2048, 256
#: limits on the widest gap to the float32 recurrence over the widest
#: value.  The XLA loops this kernel replaced read on this step ``y``
#: 0.0025 / 0.0041 and a state 0.0037 / 0.0056 (recurrent / chunked, all
#: chunks through one form; PERF.md section 6, PR 35)
Y_GAP, STATE_GAP = 0.005, 0.008


@pytest.fixture(scope="module")
def step():
    import jax
    import jax.numpy as jnp

    from anomod.models import hybrid_ssm_moe as hm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3-super-ep8-share.json")) as f:
        spec = json.load(f)
    cfg = hm.HybridConfig.from_dict(spec)
    H, N, G = cfg.mamba_num_heads, cfg.ssm_state_size, cfg.n_groups
    rng = np.random.default_rng(35)
    share = np.arange(1, 257) ** -float(1.2)
    n = rng.multinomial(TOKENS, share / share.sum())
    n = n[n > 0].astype(np.int64)
    assert (n <= 4).sum() > len(n) // 2 and n.max() > 2 * cfg.chunk_size
    tok0 = np.cumsum(n) - n
    slot = 1 + rng.permutation(SLOTS - 1)[:len(n)]
    fresh = (rng.random(len(n)) < 0.2).astype(np.int64)
    ks = jax.random.split(jax.random.PRNGKey(35), 6)
    bf = jnp.bfloat16
    args = (jax.random.normal(ks[0], (GRID, cfg.d_inner), bf),
            0.3 * jax.random.normal(ks[1], (GRID, G * N), bf),
            0.3 * jax.random.normal(ks[2], (GRID, G * N), bf),
            jax.random.uniform(ks[3], (GRID, H), jnp.float32, 0.001, 0.1),
            -jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0),
            (0.1 * jax.random.normal(ks[5], (1, SLOTS, N, cfg.d_inner),
                                     jnp.float32)).astype(bf))
    from test_hybrid_ssm_moe import scan_by_tokens

    host = [np.asarray(a.astype(jnp.float32)) for a in args]
    x, B, C = host[0].reshape(GRID, H, -1), host[1].reshape(GRID, G, N), \
        host[2].reshape(GRID, G, N)
    want_y, want_S = scan_by_tokens(x, B, C, *host[3:], 0,
                                    zip(tok0, n, slot, fresh))
    want = want_y.reshape(GRID, -1), want_S
    return cfg, args, host, (tok0, n, slot, fresh), want


@pytest.mark.parametrize("form", ["by_size", "recurrent", "chunked"])
def test_the_compiled_scan_equals_the_interpreter_and_the_recurrence(
        step, form):
    import jax
    import jax.numpy as jnp

    from anomod.ops import ssm_scan as ss

    cfg, args, host, (tok0, n, slot, fresh), (want_y, want_S) = step
    Q = cfg.chunk_size
    by_size = ss.recurrent_is_cheaper(
        n, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
        cfg.n_groups, Q)
    assert by_size.any() and not by_size.all()
    # forced through the recurrent form, the longest chunks aside: a
    # 400-token chunk a token after another proves nothing more
    rec = {"by_size": by_size, "recurrent": n <= 40,
           "chunked": np.zeros(len(n), bool)}[form]
    work = ss.empty_work(ss.work_caps(GRID, len(n), Q))
    ss.work_lists(work, tok0, n, slot, fresh, rec, Q)
    fn = jax.jit(lambda *a: ss.ssm_scan(*a, 0, work, Q))

    chip = jax.devices()[0]
    assert chip.platform == "tpu"
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    got_y, got_pool = map(f32, fn(*jax.device_put(args, chip)))
    assert np.isfinite(got_y).all() and np.isfinite(got_pool).all()
    top = np.abs(want_y).max()
    assert np.abs(got_y - want_y).max() < Y_GAP * top
    assert not got_y[n.sum():].any()
    scale = {s: np.abs(v).max() for s, v in want_S.items()}
    for s in range(SLOTS):
        if s in want_S:
            assert np.abs(got_pool[0, s] - want_S[s]).max() \
                < STATE_GAP * scale[s]
        else:           # a slot the step did not name: bit for bit
            np.testing.assert_array_equal(got_pool[0, s], host[5][0, s])
    if form != "by_size":
        return
    # the two compilers' roundings of one kernel lie no further apart
    # than either lies from float32
    int_y, int_pool = map(f32, fn(*jax.device_put(
        args, jax.devices("cpu")[0])))
    assert not int_y[n.sum():].any()
    np.testing.assert_allclose(got_y, int_y, atol=Y_GAP * top)
    for s in want_S:
        np.testing.assert_allclose(got_pool[0, s], int_pool[0, s],
                                   atol=STATE_GAP * scale[s])
