"""The block-diffusion decoder at a tiny preset on the CPU, through the
serve plane (``SeqPlane``, picked by ``model_type``), against the plain
reference: a token's surprisal is a function of its session's tokens
however they fall into ticks (whole blocks, a token a tick, 1 + 3, 3 + 1),
the first block reads no prefix, a session rolls at ``context_tokens``, a
session is ended with its block open, a tick is cut into steps at the
grid's largest size; the kept logits rows and the pool's rows against the
reference's; the block-causal kernel against dense attention; the
arithmetic of the published widths.  The four faults the cell's
``correct`` is there to catch are ``tests/benchmark/
test_benchmark_sdar_cell.py``'s."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from anomod.models import block_diffusion_moe as bm
from anomod.ops import gqa_attention as ga
from anomod.serve import seqplane as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import block_diffusion_moe_decoder as ref  # noqa
from test_swa_moe import _served  # noqa: E402

#: hidden 64; 4 query heads over 2 key-value heads of 32; 8 experts top-2,
#: all held; diffusion blocks of 4 in cache blocks of 8; sessions of 32
#: tokens; 63 usable cache blocks; vocabulary 1,024, the mask token 1,000
TINY = dict(
    model_type="sdar_moe", hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    rope_theta=1000000, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6,
    vocab_size=1024, attention_bias=False, decoder_sparse_step=1,
    mlp_only_layers=[], rope_scaling=None, use_sliding_window=False,
    tie_word_embeddings=False,
    assumed=dict(context_tokens=32, block_tokens=8, pool_tokens=512,
                 block_length=4, denoising_steps=4, mask_token_id=1000,
                 token_grid=[32, 64]))
L = 4


def flat(spec):
    out = dict(spec, **{k: v for k, v in spec["assumed"].items()
                        if not isinstance(v, (dict, list, str))})
    out.setdefault("vocab_held", out["vocab_size"])
    out.setdefault("experts_held", out["num_experts"])
    return out


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def plane_of(spec, n_tenants, exact=True):
    """The plane; ``exact``: its weights (the serving draw) and pool in
    float32, so that it and the reference differ by rounding alone."""
    plane = sp.SeqPlane(dict(spec, weights_seed=7,
                             audit_tenants=list(range(n_tenants))),
                        range(n_tenants), 5, 16, 5_000_000)
    if exact:
        plane.params = f32(plane.params)
        plane.state = bm.init_state(plane.cfg, n_tenants, jnp.float32)
    return plane


def sessions_of(plane):
    out = {}
    for t, number, start, tok, s in plane.audit_segments:
        e = out.setdefault((t, number), ([], []))
        assert sum(map(len, e[0])) == start
        e[0].append(tok)
        e[1].append(s)
    return {k: (np.concatenate(a), np.concatenate(b))
            for k, (a, b) in out.items()}


def kept_rows(plane):
    rows = {}
    for t, number, p, row in plane.audit_logits:
        rows.setdefault((t, number), {})[p] = row
    return rows


#: one tenant's 40 tokens: its first session rolls at 32, the second is
#: left with an open block of 0 and then of 3 tokens
SEQ = np.random.default_rng(0).integers(0, 5 * 16 * 12, 43)
SPLITS = {"whole": [40, 3],                      # a step cut by the grid
          "one_a_tick": [1] * 43,
          "1+3": [1, 3] * 10 + [3],
          "3+1": [3, 1] * 10 + [3]}


@pytest.fixture(scope="module")
def exact():
    """The reference's surprisals and logits of the two sessions, and the
    serving draw upcast (what the exact plane serves with)."""
    spec = flat(dict(TINY, vocab_held=1024))
    params = f32(ref.draw_params(spec, 7))
    runner = ref.SessionRunner(spec, params, lengths=(32,))
    want = {(0, 0): runner.run(SEQ[:32], range(32)),
            (0, 1): runner.run(SEQ[32:], range(11))}
    return spec, params, want


def _ticks(plane, split, seq=SEQ, tenant=0):
    at = 0
    for k, n in enumerate(split):
        plane.step([_served(tenant, seq[at:at + n], k * 10 ** 6)])
        at += n


@pytest.mark.parametrize("split", sorted(SPLITS))
def test_a_score_is_a_function_of_the_sequence(exact, split):
    spec, params, want = exact
    plane = plane_of(TINY, 1)
    assert isinstance(plane.model, sp.BlockDiffusionMoE)
    assert sp.MODELS["sdar_moe"] is sp.BlockDiffusionMoE
    _ticks(plane, SPLITS[split])
    got, rows = sessions_of(plane), kept_rows(plane)
    assert sorted(got) == [(0, 0), (0, 1)] and plane.table.rolled == 1
    for key, (s, logits) in want.items():
        np.testing.assert_array_equal(got[key][0], SEQ[:32] if key == (0, 0)
                                      else SEQ[32:])
        # the same surprisals to float32 rounding whatever the split: the
        # reference runs one whole session, no tick in it
        np.testing.assert_allclose(got[key][1], s, atol=2e-5)
        assert rows[key]
        for p, row in rows[key].items():
            np.testing.assert_allclose(row, logits[p], atol=2e-4)
    # the first token of a session reads no prefix: its score is its
    # round's alone, no lower than a peaked guess
    assert got[(0, 1)][1][0] > 0
    # the live session's open block: three ids after its two whole blocks
    live = plane.table.sessions[0]
    assert (live.number, live.length) == (1, 11)
    np.testing.assert_array_equal(live.pending, SEQ[40:])
    assert plane.counters["bd_pending_tokens"] == 3
    # the pool holds the whole blocks' keys and values as the reference's
    # commit pass makes them; the open block's rows are not written
    x = params["embed"][jnp.asarray(SEQ[32:43])]
    pool = np.asarray(plane.state["pool"])
    pos = jnp.arange(11)
    for i in range(2):
        x, k, v = ref.commit_layer(spec, params[f"layer{i:02d}"], x, pos)
        kv = np.concatenate([np.asarray(k).reshape(11, -1),
                             np.asarray(v).reshape(11, -1)], axis=1)
        slots = [live.blocks[p // 8] * 8 + p % 8 for p in range(11)]
        rows_held = pool[i].reshape(-1, 128)[slots]
        np.testing.assert_allclose(rows_held[:8], kv[:8], atol=2e-5)
        assert not rows_held[8:].any()
    c = plane.counters
    assert c["seq_tokens"] == 43
    assert c["bd_round_rows"] == 43 * L and c["bd_commit_rows"] == 40
    assert c["bd_blocks_committed"] == 10
    assert c["bd_inblock_pairs"] == (43 * L + 40) * L
    if split == "whole":
        # 40 tokens take 200 rows: the tick was cut into steps of 64
        assert c["seq_steps"] == 4 + 1
    plane.close()


def _served_log(n_ticks, n_tenants, seed):
    rng = np.random.default_rng(seed)
    log = []
    for tick in range(n_ticks):
        tenants = rng.choice(n_tenants, rng.integers(1, n_tenants + 1),
                             False)
        log.append([_served(int(t), rng.integers(0, 5 * 16 * 12,
                                                 int(rng.integers(1, 14))),
                            tick * 10 ** 6) for t in sorted(tenants)])
    return log


def test_the_plane_replays_its_served_log_at_the_serving_dtype():
    """Seeded ticks of six tenants over a pool of 15 usable blocks through
    the plane at bfloat16: the segments are the reference policy's, the
    open blocks its replay's, sessions roll and are ended with their
    block open, and the surprisals stand near the reference's."""
    spec = dict(TINY, assumed=dict(TINY["assumed"], pool_tokens=128))
    plane = plane_of(spec, 6, exact=False)
    log = _served_log(16, 6, 3)
    for served in log:
        plane.step(served)
    assert set(plane.state) == {"pool"}
    assert plane.state["pool"].shape == (2, 16, 8, 128)
    policy = ref.SessionPolicy(15, 32, 8)
    segs = []
    for served in log:
        segs += policy.step([(qb.tenant_id, qb.n_spans) for qb in served])
    assert [(t, n, s, len(tok)) for t, n, s, tok, _ in
            plane.audit_segments] == segs
    assert (policy.rolled, policy.evicted) == (plane.table.rolled,
                                               plane.table.evicted)
    assert policy.rolled > 0 and policy.evicted > 0
    got = sessions_of(plane)
    # a session ended with its block open, and every live one's open
    # block as the replay has it
    ended = [k for k in got if k[0] not in policy.live
             or k[1] < policy.live[k[0]][2]]
    assert any(len(got[k][0]) % L for k in ended)
    for t, (length, _, number) in policy.live.items():
        np.testing.assert_array_equal(
            plane.table.sessions[t].pending,
            ref.open_block(got[(t, number)][0], length, L))
    assert plane.counters["bd_pending_tokens"] == sum(
        length % L for length, _, _ in policy.live.values())
    runner = ref.SessionRunner(flat(dict(spec, vocab_held=1024)),
                               ref.draw_params(flat(dict(
                                   spec, vocab_held=1024)), 7),
                               lengths=(32,))
    assert ref.digests(runner.params) == ref.digests(plane.params)
    rows = kept_rows(plane)
    program = {k: (s, rows.get(k, {})) for k, (_, s) in got.items()}
    reference = {}
    for k, (tok, _) in got.items():
        at = sorted(rows.get(k, {}))
        s, logits = runner.run(tok, at)
        reference[k] = (s, dict(zip(at, logits)))
    n = ref.compare(program, reference, 16)
    assert n["spans_compared"] == plane.counters["seq_tokens"] == sum(
        qb.n_spans for served in log for qb in served)
    assert n["surprisal_gap_mean"] < 0.05 and n["surprisal_gap_p50"] < 0.03
    assert n["logit_gap"] < 0.05
    plane.close()


# -- the kernel's block-causal form -------------------------------------------

def test_the_block_causal_kernel_is_dense_attention_over_each_rows_prefix():
    rng = np.random.default_rng(0)
    B, kv, d, H, T = 8, 2, 32, 6, 64
    n = np.asarray([20, 7, 13, 4, 12])
    off = np.cumsum(n) - n
    lim = np.full(T, -1, np.int64)
    for s, k in enumerate(n):
        lim[off[s]:off[s] + k] = np.sort(rng.integers(-1, 40, k))
    lim[off[4]:off[4] + 12] = -1       # a chunk of no cached key at all
    table = (1 + rng.permutation(40)).reshape(5, 8).astype(np.int32)
    pool = rng.standard_normal((41, B, 2 * kv * d)).astype(np.float32)
    q = rng.standard_normal((T, H, d)).astype(np.float32)
    items = ga.empty_items(ga.items_needed(5, T), ga.blocks_needed(8, B))
    count = ga.fill_items(items, np.zeros(5, np.int64), n, off, table, B,
                          limits=lim)
    assert (items["table"][:count, 0, ga.NBLK] == 0).any()
    o, lse = jax.jit(lambda q, pool, items, lim: ga.append_attention(
        q, pool, items, 0, kv, d ** -0.5, B, limits=lim))(
        q, pool, items, jnp.asarray(lim, jnp.int32))
    o, lse = np.asarray(o), np.asarray(lse)
    for s in range(5):
        keys = pool[table[s]].reshape(-1, 2, kv, d)
        for t in range(off[s], off[s] + n[s]):
            seen = keys[:lim[t] + 1]
            for h in range(H):
                if not len(seen):
                    assert not o[t, h].any() and lse[t, h] == ga.NEG
                    continue
                sc = seen[:, 0, h // 3] @ q[t, h] * d ** -0.5
                p = np.exp(sc - sc.max())
                np.testing.assert_allclose(
                    o[t, h], p @ seen[:, 1, h // 3] / p.sum(), atol=1e-5)
                np.testing.assert_allclose(
                    lse[t, h], sc.max() + np.log(p.sum()), atol=1e-5)
    assert not o[n.sum():].any()


def test_a_step_is_cut_only_at_a_diffusion_blocks_edge():
    cfg = bm.BlockDiffusionConfig.from_dict(TINY)
    # 2 + 4 + 4 + 1 tokens from position 2: open, whole, whole, open
    new, rows = bm.units(cfg, 2, 11)
    assert new.tolist() == [2, 4, 4, 1]
    assert rows.tolist() == [12, 20, 20, 4]
    assert bm.fit(cfg, 2, 11, 55) == (10, 52)
    assert bm.fit(cfg, 2, 11, 11) == (0, 0)
    assert bm.fit(cfg, 2, 11, 64) == (11, 56)
    caps = bm.plan_caps(cfg, 64, 12)
    assert caps["scored"] == 16


# -- the published widths -----------------------------------------------------

def test_param_count_at_published_widths_is_the_stage_arithmetic():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-pp8-stage.json")) as f:
        spec = json.load(f)
    cfg = bm.BlockDiffusionConfig.from_dict(spec)
    shapes = bm.param_shapes(cfg)
    layer = sum(int(np.prod(s)) for s, _ in shapes["layer00"].values())
    experts = sum(int(np.prod(shapes["layer00"][k][0]))
                  for k in ("e_gate", "e_up", "e_down"))
    assert (layer - experts, experts) == (19_140_864, 603_979_776)
    assert bm.param_count(cfg) == 4_361_055_744
    assert bm.param_count(bm.BlockDiffusionConfig.from_dict(
        dict(spec, num_hidden_layers=48))) == 30_532_122_624
    assert (cfg.experts_held, cfg.vocab_held, cfg.kv_row_width,
            cfg.block_length, cfg.mask_token_id) == (128, 151936, 1024, 4,
                                                     151669)


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("rope_scaling", {"rope_type": "yarn"}),
    ("use_sliding_window", True), ("tie_word_embeddings", True),
])
def test_what_is_not_written_here_is_refused(key, value):
    with pytest.raises(ValueError):
        bm.BlockDiffusionConfig.from_dict(dict(TINY, **{key: value}))
    with pytest.raises(ValueError):
        bm.BlockDiffusionConfig.from_dict(dict(TINY, assumed=dict(
            TINY["assumed"], denoising_steps=2)))


def test_the_references_own_draw_is_the_programs_bit_for_bit():
    cfg = bm.BlockDiffusionConfig.from_dict(TINY)
    ours = bm.init_params(cfg, 2147486001)
    theirs = ref.draw_params(flat(TINY), 2147486001)
    leaves = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert [p for p, _ in leaves(ours)] == [p for p, _ in leaves(theirs)]
    for (path, a), (_, b) in zip(leaves(ours), leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), str(path))
    assert ref.digests(ours) == ref.digests(theirs)
    assert ours["layer01"]["router"].dtype == jnp.float32
    assert ours["layer01"]["q_norm"].shape == (32,)


@pytest.mark.parametrize("rows, experts_lo, held", [(3, 0, 8), (40, 0, 8),
                                                    (700, 2, 5)])
def test_the_references_grouped_experts_are_its_dense_sum(rows, experts_lo,
                                                          held):
    """Grouped by expert (tiles of 8, 16 and 128 pairs here; a share of the
    experts held), the reference's experts are every held expert over
    every row by a mask, to float32 rounding."""
    c = dict(flat(TINY), num_experts_per_tok=3, experts_lo=experts_lo,
             experts_held=held)
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(rows), 5)
    lp = {"router": jax.random.normal(ks[0], (D, c["num_experts"])),
          "e_gate": jax.random.normal(ks[1], (held, D, F)) / 8,
          "e_up": jax.random.normal(ks[2], (held, D, F)) / 8,
          "e_down": jax.random.normal(ks[3], (held, F, D)) / 6}
    b = jax.random.normal(ks[4], (rows, D))
    w = ref._leaf(lp)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda b: ref._experts(c, w, b))(b)
        p = jax.nn.softmax(b @ lp["router"], axis=-1)
        top, choice = jax.lax.top_k(p, 3)
        wt = top / top.sum(axis=1, keepdims=True)
        want = jnp.zeros_like(b)
        for e in range(held):
            w_e = jnp.where(choice == experts_lo + e, wt, 0.0).sum(axis=1)
            want += w_e[:, None] * ((jax.nn.silu(b @ lp["e_gate"][e])
                                     * (b @ lp["e_up"][e])) @ lp["e_down"][e])
    assert ref.tile_rows(rows, c) == {3: 8, 40: 16, 700: 128}[rows]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


#: sha1 of the lowered step of each older model at its tests' tiny preset,
#: taken from the tree before the kernel's block-causal form: that form is
#: a static switch, and the programs of the kernel's other callers (and of
#: the latent-attention model, which shares the plane) are the same text.
#: The window-and-full model's was re-taken when a step's one round of
#: experts began to combine by gather; the other two take the loop of
#: rounds, whose program is the same text
LOWERED = {
    ("test_latent_moe", 128): "861fd5f0bc9e135f60508d5e0cc22fc8198ed667",
    ("test_hybrid_ssm_moe", 128): "64af329240dc01280b26ec38e0c2da3836eb61b4",
    ("test_swa_moe", 128): "56bec4466c88356b2c240822518d40b1e26ff50f",
}


@pytest.mark.parametrize("module, rows", sorted(LOWERED))
def test_the_older_models_steps_lower_as_before(module, rows):
    import hashlib
    import importlib
    spec = importlib.import_module(module).TINY
    m = sp.MODELS.get(spec.get("model_type"), sp.LatentMoE)(spec)
    text = jax.jit(m.step).lower(
        jax.eval_shape(lambda: m.init_params(0)),
        jax.eval_shape(lambda: m.init_state(6)),
        m.empty_plan(m.caps(rows, 12), 6)).as_text()
    assert hashlib.sha1(text.encode()).hexdigest() == LOWERED[module, rows]
