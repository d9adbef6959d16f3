"""The window-and-full attention decoder at a tiny preset on the CPU:
prefill and appends through both K/V pools against the plain reference's
whole-session logits; the window's edge, the ring of window blocks and the
two-pool policy; rotary tables against numbers written out by hand; the
softmax router beside the parent's sigmoid one, bit for bit; the expert
shares against the uncut layer."""

import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from anomod.models import swa_moe as wm
from anomod.ops import gqa_attention as ga
from anomod.ops import routed_experts as rx
from anomod.serve import seqplane as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import swa_moe_decoder as ref  # noqa: E402

YARN = dict(rope_theta=500000, rope_type="yarn", factor=64,
            original_max_position_embeddings=4096, beta_slow=1, beta_fast=64,
            attention_factor=1.4158883083359672, partial_rotary_factor=0.5)
#: hidden 64; 6 query heads on full layers and 8 on sliding ones over 2
#: key-value heads of 32; a window of 20 keys over blocks of 8 (the edge
#: falls inside a block); YaRN from 16 positions on half a head, default
#: rotary on all of it; 16 experts top-2, all held; F S S S F: a dense
#: layer first; vocabulary 256
TINY = dict(
    model_type="laguna", hidden_size=64, num_hidden_layers=5,
    layer_types=[wm.FULL, wm.SWA, wm.SWA, wm.SWA] * 2,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 2,
    mlp_layer_types=["dense"] + ["sparse"] * 7,
    num_key_value_heads=2, head_dim=32, sliding_window=20,
    rope_parameters={
        wm.FULL: dict(YARN, original_max_position_embeddings=16,
                      beta_fast=4),
        wm.SWA: dict(rope_type="default", rope_theta=10000,
                     partial_rotary_factor=1)},
    intermediate_size=128, num_experts=16, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6, gating=True,
    attention_bias=False, moe_apply_router_weight_on_input=False,
    tie_word_embeddings=False, vocab_size=256,
    assumed=dict(context_tokens=64, block_tokens=8, pool_tokens=512,
                 window_blocks=24))


def tiny(**over):
    spec = dict(TINY, **over)
    return spec, wm.SwaMoeConfig.from_dict(spec)


def flat(spec):
    out = dict(spec, **spec["assumed"])
    out.setdefault("vocab_held", out["vocab_size"])
    out.setdefault("experts_held", out["num_experts"])
    return out


@pytest.fixture(scope="module")
def model():
    spec, cfg = tiny()
    return spec, cfg, wm.init_params(cfg, 3, dtype=jnp.float32)


class Stepper:
    """Drives ``append_step`` from a tick's ``(tenant, n)`` chunks as the
    plane does, keeping each session's tokens, surprisals and the logits
    rows of every chunk's last token."""

    def __init__(self, cfg, params, n_tenants=8, grid=128, step=None):
        self.cfg, self.params, self.grid = cfg, params, grid
        self.table = sp.SessionTable(
            cfg.pool_blocks, cfg.context_tokens, cfg.block_tokens, None,
            cfg.window_blocks, cfg.sliding_window)
        self.state = wm.init_state(cfg, n_tenants, dtype=jnp.float32)
        self.ids = np.arange(n_tenants)
        self.step = jax.jit(step or (lambda p, state, plan: wm.append_step(
            cfg, p, state, plan)))
        self.sessions, self.rows, self.stats = {}, {}, {}

    def tick(self, chunks, rng):
        for segs in self.table.place(sorted(chunks)):
            n_tok = sum(s[3] for s in segs)
            tok = rng.integers(0, self.cfg.vocab_held, n_tok).astype(
                np.int32)
            caps = wm.plan_caps(self.cfg, self.grid, 2 * len(self.ids))
            plan, stats, audit_rows = wm.build_plan(
                self.cfg, caps, segs, tok, self.ids,
                frozenset(self.ids.tolist()))
            self.state, s, audit, counts = self.step(self.params,
                                                     self.state, plan)
            s, at = np.asarray(s), 0
            for t, number, start, n_seg, *_ in segs:
                got = self.sessions.setdefault((t, number), [[], []])
                assert sum(map(len, got[0])) == start
                got[0].append(tok[at:at + n_seg])
                got[1].append(s[at:at + n_seg])
                at += n_seg
            for i, (t, number, p) in enumerate(audit_rows):
                self.rows.setdefault((t, number), {})[p] = np.asarray(
                    audit[i])
            for k, v in stats.items():
                self.stats[k] = self.stats.get(k, 0) + v
        return np.asarray(counts)

    def worst_gaps(self, spec, params=None):
        """``(widest surprisal gap, widest logits-row gap)`` against the
        reference's whole-session forward."""
        runner = ref.SessionRunner(flat(spec), params or self.params,
                                   lengths=(64,))
        worst, worst_row = 0.0, 0.0
        for key, (tok, s) in self.sessions.items():
            rows_at = sorted(self.rows.get(key, {}))
            want, logits = runner.run(np.concatenate(tok), rows_at)
            worst = max(worst, float(np.abs(want - np.concatenate(s)).max()))
            for p, row in zip(rows_at, logits):
                worst_row = max(worst_row, float(
                    np.abs(row - self.rows[key][p]).max()))
        return worst, worst_row


CASES = {
    # prefill, then appends: one token, across a block, across the window
    "prefill_then_appends": [[(1, 13)], [(1, 1)], [(1, 9)], [(1, 1)],
                             [(1, 22)], [(1, 3)]],
    # a session longer than the window and two blocks, one token a step
    # around the places where a block and the window's edge fall
    "one_token_chunks": [[(0, 19)]] + [[(0, 1)]] * 6 + [[(0, 11)]]
    + [[(0, 1)]] * 3,
    # a chunk longer than the window with its own keys beyond reach
    "chunk_longer_than_window": [[(2, 3)], [(2, 45)], [(2, 2)]],
    "session_roll": [[(0, 40)], [(0, 30), (1, 3)], [(0, 10)]],
    "many_sessions": [[(t, 1 + (5 * t) % 11) for t in range(6)],
                      [(t, 1 + (3 * t) % 7) for t in range(6)],
                      [(t, 2 + t) for t in range(6)]],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunks_through_both_pools_equal_one_full_forward(model, case):
    spec, cfg, params = model
    run = Stepper(cfg, params)
    rng = np.random.default_rng(1)
    for chunks in CASES[case]:
        run.tick(chunks, rng)
        # between steps a session holds the ring its next token can see
        for s in run.table.sessions.values():
            assert len(s.ring) == ref.ring_blocks(s.length, 20, 8) <= 4
            assert len(s.blocks) == -(-s.length // 8)
    gap, row_gap = run.worst_gaps(spec)
    assert gap < 2e-5 and row_gap < 2e-4
    assert sum(len(r) for r in run.rows.values()) >= 3
    assert run.stats["swa_pairs"] <= run.stats["full_pairs"]
    if case == "session_roll":
        assert run.table.rolled == 1 and (0, 1) in run.sessions
    if case == "chunk_longer_than_window":
        assert run.stats["swa_keys"] == 3 + 48 + 21
        assert run.stats["full_keys"] == 3 + 48 + 50
        assert run.stats["swa_pairs"] == sum(min(p + 1, 20)
                                             for p in range(50))


def test_an_evicted_session_restarts_and_a_freed_block_is_reused(model):
    """Eleven usable window blocks: a step that needs more than are free
    ends the least recently appended session, which then starts anew; the
    window blocks a long chunk passed are handed to other sessions and
    rewritten, and no logit of the first session moves."""
    spec, cfg = tiny(assumed=dict(TINY["assumed"], window_blocks=12))
    run = Stepper(cfg, model_params(cfg))
    rng = np.random.default_rng(2)
    run.tick([(0, 50)], rng)
    passed = set(range(1, 12)) - set(run.table.sessions[0].ring)
    assert len(run.table.sessions[0].ring) == 4 and len(passed) == 7
    run.tick([(1, 30), (2, 20)], rng)
    reused = set(run.table.sessions[1].ring) | set(
        run.table.sessions[2].ring)
    assert reused & passed and run.table.evicted == 0
    run.tick([(0, 3), (3, 33)], rng)
    assert run.table.evicted >= 1 and run.table.evicted_by_window >= 1
    run.tick([(1, 4), (2, 4), (0, 2)], rng)
    assert any(number == 1 for _, number in run.sessions)
    assert run.table.win_blocks_held <= 11
    assert run.table.win_freed > 0
    gap, row_gap = run.worst_gaps(spec)
    assert gap < 2e-5 and row_gap < 2e-4


def model_params(cfg):
    return wm.init_params(cfg, 3, dtype=jnp.float32)


TICKS = [[(1, 13), (2, 30)], [(1, 9)], [(1, 22), (2, 5)], [(1, 3)]]


def _gap_with(spec, cfg, step=None, ref_spec=None, ref_params=None):
    run = Stepper(cfg, model_params(cfg), step=step)
    rng = np.random.default_rng(4)
    for chunks in TICKS:
        run.tick(chunks, rng)
    return run.worst_gaps(ref_spec or spec, ref_params)[0]


def test_the_sound_program_is_near_the_reference(model):
    spec, cfg, _ = model
    assert _gap_with(spec, cfg) < 2e-5


@pytest.mark.parametrize("off", [-1, 1])
def test_a_window_off_by_one_is_far_from_the_reference(model, monkeypatch,
                                                       off):
    # the window is sliding_window keys WITH the token itself
    spec, cfg, _ = model
    real = ga.append_attention
    monkeypatch.setattr(
        ga, "append_attention", lambda *a: real(
            *a[:-1], None if a[-1] is None else a[-1] + off))
    assert _gap_with(spec, cfg) > 1e-3


def test_the_references_window_off_by_one_is_far_too(model):
    spec, cfg, _ = model
    assert _gap_with(spec, cfg, ref_spec=dict(spec, sliding_window=21)) \
        > 1e-3


@pytest.mark.parametrize("pool", ["pool", "wpool"])
def test_a_stale_block_is_far_from_the_reference(model, pool):
    # the step leaves one pool unwritten: later chunks read stale keys
    spec, cfg, _ = model

    def stale(p, state, plan):
        out = wm.append_step(cfg, p, state, plan)
        return (dict(out[0], **{pool: state[pool]}),) + tuple(out[1:])

    assert _gap_with(spec, cfg, step=stale) > 1e-3


@pytest.mark.parametrize("heads", [[8, 8, 8, 8], [6, 6, 6, 6]],
                         ids=["full_as_sliding", "sliding_as_full"])
def test_the_wrong_head_count_is_far_from_the_reference(model, heads):
    # a reference that gives one kind the other's head count draws its
    # own weights by its own shapes
    spec, cfg, _ = model
    wrong = dict(spec, num_attention_heads_per_layer=heads * 2)
    theirs = ref.draw_params(flat(wrong), 3)
    as_f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), theirs)
    assert _gap_with(spec, cfg, ref_spec=wrong, ref_params=as_f32) > 1e-3


def test_rotary_on_the_wrong_half_is_far_from_the_reference(model,
                                                            monkeypatch):
    spec, cfg, _ = model
    real = wm.rotate
    monkeypatch.setattr(wm, "rotate", lambda x, cos, sin: real(
        x[..., ::-1], cos, sin)[..., ::-1])
    assert _gap_with(spec, cfg) > 1e-3


def test_a_sigmoid_router_is_far_from_the_reference(model, monkeypatch):
    spec, cfg, _ = model
    real = rx.route
    monkeypatch.setattr(rx, "route", lambda *a, score: real(*a))
    assert _gap_with(spec, cfg) > 1e-3


# -- the attention op and its work list ---------------------------------------

def _dense_attention(q, k, v, pos, seg, window):
    """Plain numpy: ``q`` ``[T, H, d]``, ``k`` / ``v`` ``[T, kv, d]`` of
    packed tokens that are all of a step's keys."""
    T, H, d = q.shape
    per = H // k.shape[1]
    out = np.zeros_like(q)
    for h in range(H):
        s = q[:, h] @ k[:, h // per].T * d ** -0.5
        see = (seg[:, None] == seg[None, :]) & (pos[None, :] <= pos[:, None])
        if window is not None:
            see &= pos[None, :] > pos[:, None] - window
        s = np.where(see, s, -np.inf)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        out[:, h] = (p / p.sum(axis=1, keepdims=True)) @ v[:, h // per]
    return out


#: a step's chunks ``(start position, tokens)``, blocks of 8 and windows
#: of ``ga.Q_TILE`` packed tokens: a fresh session longer than a tile; a
#: chunk that starts inside a block; ONE token deep in a session, inside
#: a block; a chunk that spans tile edges from a block's edge; one token
#: of a fresh session; one token on a tile's first row
STEP_START = np.asarray([0, 5, 130, 64, 0, 11])
STEP_N = np.asarray([70, 3, 1, 90, 1, 27])


def _items_of(window, B=8, n_blocks=32):
    """The step's work list over a pool in which session ``s`` holds the
    blocks ``1 + s * n_blocks ..`` in order (block 0 is no session's)."""
    start, n = STEP_START, STEP_N
    off = np.cumsum(n) - n
    T = -(-int(n.sum()) // ga.Q_TILE) * ga.Q_TILE + ga.Q_TILE
    table = np.zeros((len(n), n_blocks), np.int32)
    for s in range(len(n)):
        held = -(-(start[s] + n[s]) // B)
        table[s, :held] = 1 + s * n_blocks + np.arange(held)
    items = ga.empty_items(ga.items_needed(len(n), T),
                           ga.blocks_needed(n_blocks, B, window))
    n_items = ga.fill_items(items, start, n, off, table, B, window)
    return items, n_items, off, table, T


@pytest.mark.parametrize("window", [None, 1, 7, 8, 9, 200])
def test_the_work_list_covers_every_visible_pair_once(window):
    """Every packed token is a row of exactly one item, whose blocks hold
    every key the token sees (so every visible (query, key) pair is one
    item's); under a window no item fetches a block that none of its
    queries reaches; the static sizes suffice."""
    B = 8
    items, n_items, off, table, T = _items_of(window, B)
    start, n = STEP_START, STEP_N
    assert n_items == int(items["n_items"]) <= ga.items_needed(len(n), T)
    assert int(items["n_tokens"]) == n.sum()
    head = items["table"][:n_items, 0, :ga.HEAD].astype(np.int64)
    rows = items["table"][:n_items, 0, ga.HEAD:]
    win = items["win"][:n_items].astype(np.int64)
    lo, hi, pos0, blk0, nblk, edge = (head[:, k] for k in (
        ga.LO, ga.HI, ga.POS0, ga.BLK0, ga.NBLK, ga.EDGE))
    assert (nblk >= 1).all() and nblk.max() <= rows.shape[1] \
        == ga.blocks_needed(32, B, window)
    assert rows.shape[1] % ga.KV_BLOCKS == 0 and (
        rows.shape[1] < 32 or window in (None, 200))
    assert (0 <= lo).all() and (lo < hi).all() and (hi <= ga.Q_TILE).all()
    # in token order, a window opened by its first item alone and closed
    # by its last
    assert (np.diff(win) >= 0).all()
    new = (np.diff(win) > 0).tolist()
    assert (edge & 1).tolist() == [1] + new
    assert (edge >> 1).tolist() == new + [1]
    # what the kernel fetches ahead: the next item's first run
    assert head[:, ga.NEXT_NBLK].tolist() == nblk[1:].tolist() + [0]
    np.testing.assert_array_equal(head[:-1, ga.NEXT:],
                                  rows[1:, :ga.KV_BLOCKS])
    seg = np.repeat(np.arange(len(n)), n)
    taken = np.zeros(n.sum(), int)
    for i in range(n_items):
        tok = win[i] * ga.Q_TILE + np.arange(lo[i], hi[i])
        taken[tok] += 1
        s = seg[tok[0]]
        assert (seg[tok] == s).all()                 # one chunk's rows
        pos = start[s] + tok - off[s]
        np.testing.assert_array_equal(pos, pos0[i] + np.arange(lo[i], hi[i]))
        oldest = 0 if window is None else max(pos[0] - window + 1, 0)
        # from the block of the oldest key any row sees to the block of
        # the newest: all of them and, under a window, no other
        assert blk0[i] == (oldest // B if window is not None else 0)
        assert blk0[i] + nblk[i] - 1 == pos[-1] // B
        walked = blk0[i] + np.arange(nblk[i])
        np.testing.assert_array_equal(rows[i, :nblk[i]], table[s, walked])
        assert (rows[i, :nblk[i]] > 0).all() and not rows[i, nblk[i]:].any()
    assert (taken == 1).all()


@pytest.mark.parametrize("heads, kv, d", [(12, 2, 16), (8, 1, 16),
                                          (16, 1, 8)],
                         ids=["R6", "R8", "R16"])
@pytest.mark.parametrize("window", [None, 1, 7, 8, 9, 200])
def test_the_window_is_exactly_its_keys_with_the_token_itself(window, heads,
                                                              kv, d):
    """The kernel (under the Pallas interpreter) over chunks that start
    anywhere in a block and a tile, walked by ``fill_items``' list, a
    group's heads down the rows: ``window`` keys, the query's own among
    them, whatever the place of the edge in a block or a tile; rows of no
    chunk come back zero; a layer's rows are found from ``row0``."""
    B, H = 8, heads
    rng = np.random.default_rng(0)
    start, n = STEP_START, STEP_N
    items, n_items, off, table, T = _items_of(window, B)
    n_tok, n_blocks = int(n.sum()), table.shape[1]
    layer = 1 + len(n) * n_blocks                    # rows of a layer
    pool = rng.standard_normal((2 * layer, B, 2 * kv * d)).astype(
        np.float32)
    q = np.zeros((T, H, d), np.float32)
    q[:] = rng.standard_normal((T, H, d))            # pads: not zero
    want = np.zeros_like(q)
    for s in range(len(n)):
        total = start[s] + n[s]
        k, v = (rng.standard_normal((total, kv, d)).astype(np.float32)
                for _ in range(2))
        rows = np.concatenate([k.reshape(total, -1), v.reshape(total, -1)],
                              axis=1)
        flat_pool = pool[layer + 1 + s * n_blocks:
                         layer + 1 + (s + 1) * n_blocks].reshape(
            -1, 2 * kv * d)
        flat_pool[:total] = rows
        sl = slice(off[s], off[s] + n[s])
        qs = np.zeros((total, H, d), np.float32)
        qs[start[s]:] = q[sl]
        want[sl] = _dense_attention(
            qs, k, v, np.arange(total), np.zeros(total, int),
            window)[start[s]:]
    got = np.asarray(jax.jit(lambda q, pool, items, row0: ga.append_attention(
        q, pool, items, row0, kv, d ** -0.5, B, window))(
            jnp.asarray(q), jnp.asarray(pool), items, jnp.int32(layer)))
    assert got.shape == q.shape
    np.testing.assert_allclose(got[:n_tok], want[:n_tok], atol=2e-5)
    assert np.abs(want[:n_tok]).max() > 0.1 and not got[n_tok:].any()


def test_a_step_of_no_token_comes_back_zero():
    items = ga.empty_items(ga.items_needed(4, 32), 4)
    q = jnp.ones((32, 4, 16), jnp.float32)
    pool = jnp.ones((9, 8, 64), jnp.float32)
    got = ga.append_attention(q, pool, items, 0, 2, 0.25, 8)
    assert got.shape == (32, 4, 16) and not np.asarray(got).any()
    with pytest.raises(ValueError, match="not whole"):
        ga.append_attention(q[:30], pool, items, 0, 2, 0.25, 8)
    with pytest.raises(ValueError, match="the table holds 4"):
        ga.fill_items(items, np.asarray([0]), np.asarray([40]),
                      np.asarray([0]), np.zeros((1, 8), np.int32), 8)


def test_without_a_window_the_kernel_is_one_program():
    """``window=None`` (n3s's call) is the default: the same program with
    the argument or without, another under a window."""
    B, kv, H, d, T = 8, 2, 4, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (T, H, d), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (9, B, 2 * kv * d), jnp.bfloat16)
    items = ga.empty_items(ga.items_needed(2, T), 8)
    ga.fill_items(items, np.asarray([0]), np.asarray([T]), np.asarray([0]),
                  np.arange(1, 9)[None, :], B)
    text = lambda *a: jax.jit(
        lambda q, pool, items: ga.append_attention(
            q, pool, items, 0, kv, d ** -0.5, B, *a)
    ).lower(q, pool, items).as_text()
    assert text() == text(None)
    assert text() != text(9)


# -- rotary tables ------------------------------------------------------------

@pytest.mark.parametrize("table", [wm.rope_table, ref.inv_freq],
                         ids=["program", "reference"])
def test_yarn_inverse_frequencies_are_the_numbers_written_out(table):
    """The published full-attention parameters: 64 rotated dims of 128,
    theta 500,000, factor 64 from 4,096 positions, beta 64 and 1: the ramp
    runs from pair 5 to pair 16."""
    freq, amp = table(YARN, 128)
    assert freq.shape == (32,) and amp == 1.4158883083359672
    c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) \
        / (2 * math.log(500000))
    assert (math.floor(c(64)), math.ceil(c(1))) == (5, 16)
    extrap = [500000 ** (-2 * i / 64) for i in range(32)]
    np.testing.assert_allclose(freq[:6], extrap[:6], rtol=1e-6)
    np.testing.assert_allclose(freq[16:], np.asarray(extrap[16:]) / 64,
                               rtol=1e-6)
    # pair 10: five elevenths of the way from extrapolation to
    # interpolation
    np.testing.assert_allclose(
        freq[10], extrap[10] * (6 / 11) + extrap[10] / 64 * (5 / 11),
        rtol=1e-6)
    assert freq[0] == 1.0
    np.testing.assert_allclose(freq[1], 0.6636012, rtol=1e-6)
    np.testing.assert_allclose(freq[31], 4.7091534e-8, rtol=1e-6)
    # no attention_factor keyed: the family's 0.1 ln(factor) + 1
    no_amp = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert table(no_amp, 128)[1] == pytest.approx(1.4158883083359672)
    plain, one = table(dict(rope_type="default", rope_theta=10000,
                            partial_rotary_factor=1), 128)
    assert plain.shape == (64,) and one == 1.0
    np.testing.assert_allclose(plain[[0, 1, 63]],
                               [1.0, 10000 ** (-1 / 64),
                                10000 ** (-63 / 64)], rtol=1e-6)


def test_the_partial_rotation_turns_the_first_half_only():
    # one head of 8 dims, 4 of them rotated as the pairs (0, 2) and (1, 3)
    x = jnp.asarray([[[1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]]])
    ang = np.asarray([[math.pi / 2, math.pi]])
    got = np.asarray(wm.rotate(x, jnp.cos(ang) * 2.0, jnp.sin(ang) * 2.0))
    # pair (0, 2) by a quarter turn, pair (1, 3) by a half, amplitude 2
    np.testing.assert_allclose(
        got[0, 0], [-6.0, -4.0, 2.0, -8.0, 5.0, 6.0, 7.0, 8.0], atol=1e-5)
    theirs = np.asarray(ref._rope(
        x.transpose(1, 0, 2), jnp.asarray([3]),
        dict(rope_type="default", rope_theta=10000,
             partial_rotary_factor=0.5), 8))
    f = [1.0, 10000 ** -0.5]
    want = [1 * math.cos(3 * f[0]) - 3 * math.sin(3 * f[0]),
            2 * math.cos(3 * f[1]) - 4 * math.sin(3 * f[1]),
            3 * math.cos(3 * f[0]) + 1 * math.sin(3 * f[0]),
            4 * math.cos(3 * f[1]) + 2 * math.sin(3 * f[1]), 5, 6, 7, 8]
    np.testing.assert_allclose(theirs[0, 0], want, rtol=1e-5)
    freq, _ = wm.rope_table(dict(rope_type="default", rope_theta=10000,
                                 partial_rotary_factor=0.5), 8)
    a = 3.0 * freq[None, :]
    np.testing.assert_allclose(
        np.asarray(wm.rotate(x, jnp.cos(a), jnp.sin(a)))[0, 0], want,
        rtol=1e-5)


# -- the router ---------------------------------------------------------------

def _parents_route(x, w_router, bias, top_k, scaling, norm_topk):
    """``route`` as the parent commit had it, kept for the comparison."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_router,
                               precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), w * scaling


@pytest.mark.parametrize("seed, dtype, norm", [
    (0, jnp.float32, True), (1, jnp.bfloat16, True),
    (2, jnp.bfloat16, False)])
def test_the_sigmoid_router_is_the_parents_bit_for_bit(seed, dtype, norm):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (40, 32), dtype)
    w = jax.random.normal(ks[1], (32, 24), jnp.float32)
    bias = 0.1 * jax.random.normal(ks[2], (24,), jnp.float32)
    want = jax.jit(lambda *a: _parents_route(*a, 3, 2.5, norm))(x, w, bias)
    got = jax.jit(lambda *a: rx.route(*a, 3, 2.5, norm))(x, w, bias)
    named = jax.jit(lambda *a: rx.route(*a, 3, 2.5, norm,
                                        score="sigmoid"))(x, w, bias)
    for a, b, c in zip(got, want, named):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_the_softmax_router_renormalises_its_chosen_scores():
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (30, 16), jnp.float32)
    w = jax.random.normal(ks[1], (16, 12), jnp.float32)
    experts, weights = rx.route(x, w, None, 4, 2.5, True, score="softmax")
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    order = np.argsort(-p, axis=1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), axis=1),
                                  np.sort(order, axis=1))
    top = np.take_along_axis(p, np.asarray(experts), axis=1)
    np.testing.assert_allclose(np.asarray(weights),
                               2.5 * top / top.sum(axis=1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 2.5,
                               rtol=1e-5)
    # another choice than the sigmoid's on the same logits is possible
    # only through the weights: the order of the scores is the same
    sig, _ = rx.route(x, w, jnp.zeros(12), 4, 2.5, True)
    np.testing.assert_array_equal(np.sort(np.asarray(sig), axis=1),
                                  np.sort(np.asarray(experts), axis=1))


# -- the shares ---------------------------------------------------------------

@pytest.mark.parametrize("path", ["reference", "program"])
def test_four_shares_add_up_to_the_uncut_layer(path):
    """Four shares of 4 of the 16 experts, the shared expert counted once,
    give the whole layer's result."""
    spec, whole = tiny()
    lp = model_params(whole)["layer01"]
    h = jax.random.normal(jax.random.PRNGKey(0), (40, 64), jnp.float32)
    leaf = lambda p: (lambda name, e=None: p[name] if e is None
                      else p[name][e])
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.moe_parts(flat(spec), leaf(lp), h)
        total = jnp.zeros_like(routed)
        for i in range(4):
            share_spec, cfg = tiny(experts_held=4, experts_lo=4 * i)
            cut = dict(lp, **{k: lp[k][4 * i:4 * i + 4]
                              for k in ("e_gate", "e_up", "e_down")})
            if path == "reference":
                part, again = ref.moe_parts(flat(share_spec), leaf(cut), h)
            else:
                part, again, counts = wm.moe_parts(
                    cfg, cut, h, jnp.ones((40,), bool), 80)
                assert counts.shape == (4,)
            np.testing.assert_allclose(again, shared, atol=1e-5)
            total = total + part
    np.testing.assert_allclose(total + shared, routed + shared, atol=5e-5)
    assert float(jnp.abs(routed).max()) > 0.01


def test_every_pair_of_a_step_is_taken_in_one_round(model):
    spec, cfg, params = model
    run = Stepper(cfg, params)
    counts = run.tick([(0, 30), (1, 7)], np.random.default_rng(0))
    # 4 sparse layers, every expert held: top-2 of 37 tokens a layer
    assert counts.shape == (4, 16) and (counts.sum(axis=1) == 74).all()


def test_the_references_bfloat16_and_forced_readings(model):
    """What ``--control 1`` reads beside the control: the reference with
    the program's bfloat16 roundings moves a little, a run that is given
    its own experts is itself to the bit, and forced experts are taken
    (a run forced to another session's choice is far)."""
    spec, cfg, params = model
    runner = ref.SessionRunner(flat(spec), params, lengths=(64,))
    tok = np.random.default_rng(5).integers(0, 256, 50).astype(np.int32)
    plain, rows, experts = runner.run(tok, [49], chosen=True)
    assert experts.shape == (5, 50, 2) and experts.dtype == np.int32
    assert not experts[0].any() and experts[1:].max() == 15   # layer 0 dense
    again, rows_again = runner.run(tok, [49], forced=experts)
    np.testing.assert_array_equal(again, plain)
    np.testing.assert_array_equal(rows_again, rows)
    rounded, _, own = runner.run(tok, [49], acts=True, chosen=True)
    gap = np.abs(rounded - plain)
    assert 1e-5 < gap.mean() < 0.05
    held, _ = runner.run(tok, [49], acts=True, forced=experts)
    assert np.abs(held - plain).mean() <= gap.mean() + 1e-6
    other = np.roll(experts, 1, axis=1)
    assert np.abs(runner.run(tok, forced=other)[0] - plain).max() > 0.01
    x = jnp.asarray([1.0 + 2.0 ** -8, 1.0 + 2.0 ** -7, 3.0e38], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ref._as_bf16(x, True)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(ref._as_bf16(x, False)), x)


# -- the two-pool policy ------------------------------------------------------

@pytest.mark.parametrize("seed, blocks, win_blocks, window", [
    (0, 40, 20, 20), (1, 24, 30, 8), (2, 60, 14, 33), (3, 30, 30, 1)])
def test_two_pool_policy_equals_the_references_replay(seed, blocks,
                                                      win_blocks, window):
    rng = np.random.default_rng(seed)
    table = sp.SessionTable(blocks, 64, 8, None, win_blocks, window)
    policy = ref.SessionPolicy(blocks - 1, win_blocks - 1, 64, 8, window)
    for _ in range(80):
        chunks = sorted((int(t), int(rng.integers(1, 40)))
                        for t in rng.choice(8, rng.integers(1, 8), False))
        if sum(-(-n // 8) + 1 for _, n in chunks) > min(blocks,
                                                        win_blocks) - 1:
            continue
        segs = table.append(chunks)
        assert [s[:4] for s in segs] == policy.step(chunks)
        assert table.blocks_held == policy.blocks_held
        assert table.win_blocks_held == policy.win_blocks_held
        rings = [b for s in table.sessions.values() for b in s.ring]
        assert len(rings) == len(set(rings)) == table.win_blocks_held
        assert 0 not in rings and max(rings, default=0) < win_blocks
        for s in table.sessions.values():
            assert len(s.ring) == ref.ring_blocks(s.length, window, 8)
            assert s.ring_lo == max(s.length - window + 1, 0) // 8
        # a segment carries every window block its chunk can reach
        for _, _, start, n, full, (lo, ring) in segs:
            assert lo <= max(start - window + 1, 0) // 8
            assert lo + len(ring) == len(full) >= -(-(start + n) // 8)
    assert (table.rolled, table.evicted, table.evicted_by_window,
            table.win_freed) == (policy.rolled, policy.evicted,
                                 policy.evicted_by_window, policy.win_freed)
    assert table.rolled > 0 and table.evicted > 0 and table.win_freed > 0
    assert (table.evicted_by_window > 0) == (win_blocks < 30)


@pytest.mark.parametrize("seed, blocks, win_blocks, window", [
    (0, 40, 8, 20), (1, 12, 30, 8), (2, 9, 9, 33), (3, 30, 5, 1)])
def test_a_tick_wider_than_a_pool_is_cut_into_steps(seed, blocks,
                                                    win_blocks, window):
    """More one-span tenants in a tick than window blocks, chunks that
    alone outnumber a pool's blocks, chunks that roll: ``place`` cuts the
    tick into steps that each fit (nothing raises), every token is placed
    once and in order, and the steps are the reference policy's own."""
    rng = np.random.default_rng(seed)
    table = sp.SessionTable(blocks, 64, 8, None, win_blocks, window)
    policy = ref.SessionPolicy(blocks - 1, win_blocks - 1, 64, 8, window)
    room = min(blocks, win_blocks) - 1
    ticks = [[(t, 1) for t in range(3 * room)],          # by tenants alone
             [(0, 8 * room + 5)], [(1, 200), (2, 1)]]    # by one chunk
    ticks += [sorted((int(t), int(rng.integers(1, 90)))
                     for t in rng.choice(40, rng.integers(1, 40), False))
              for _ in range(40)]
    cut = 0
    for chunks in ticks:
        before = table.steps_split_by_window
        steps = table.place(chunks)
        cut += len(steps) > 1
        assert len(steps) == 1 + table.steps_split_by_window - before
        got = [s[:4] for step in steps for s in step]
        assert got == policy.tick(chunks)
        placed = {}
        for t, _, _, n in got:
            placed[t] = placed.get(t, 0) + n
        assert placed == dict(chunks)
        assert [t for t, *_ in got] == sorted(t for t, *_ in got)
        for step in steps:
            begun = sum(-(-(start + n) // 8) - -(-start // 8)
                        for _, _, start, n, *_ in step)
            assert begun <= room
        assert (table.blocks_held, table.win_blocks_held, table.evicted,
                table.evicted_by_window, table.win_freed,
                table.steps_split_by_window) == (
            policy.blocks_held, policy.win_blocks_held, policy.evicted,
            policy.evicted_by_window, policy.win_freed, policy.steps_split)
    assert cut >= 10


def test_a_session_of_a_window_table_is_whole_blocks():
    with pytest.raises(ValueError, match="whole number of blocks"):
        sp.SessionTable(16, 60, 8, None, 8, 20)


def test_without_a_window_kind_the_table_is_the_parents_to_the_byte():
    """No ``window_blocks``: segments of five fields, no ring, and the
    same blocks, evictions and order as a table whose window pool never
    binds (its first five fields are the table's own without one)."""
    rng = np.random.default_rng(3)
    plain = sp.SessionTable(24, 64, 8)
    none = sp.SessionTable(24, 64, 8, None, None, None)
    wide = sp.SessionTable(24, 64, 8, None, 1000, 16)
    assert plain.free_win is None and plain.win_blocks_held == 0
    for _ in range(40):
        chunks = sorted((int(t), int(rng.integers(1, 30)))
                        for t in rng.choice(6, rng.integers(1, 4), False))
        segs = plain.append(chunks)
        assert all(len(s) == 5 for s in segs)
        assert none.place(chunks) == [segs]
        assert [s[:5] for s in wide.append(chunks)] == segs
        assert list(plain.free) == list(wide.free)
        assert list(plain.sessions) == list(wide.sessions)
    assert plain.evicted == wide.evicted > 0 and plain.rolled > 0
    assert plain.evicted_by_window == wide.evicted_by_window == 0
    assert plain.win_freed == 0 < wide.win_freed


# -- the published widths -----------------------------------------------------

def test_param_count_at_published_widths_is_the_issues_arithmetic():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs2-pp8-stage.json")) as f:
        spec = json.load(f)
    cfg = wm.SwaMoeConfig.from_dict(spec)
    assert cfg.layer_types == (wm.FULL, wm.SWA, wm.SWA, wm.SWA, wm.FULL)
    assert cfg.num_attention_heads_per_layer == (48, 64, 64, 64, 48)
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    shapes = wm.param_shapes(cfg)
    of = lambda name: sum(int(np.prod(s)) for s, _ in shapes[name].values())
    assert of("layer00") == 79_794_176
    assert of("layer01") == of("layer02") == of("layer03") == 846_860_288
    assert of("layer04") == 838_438_912
    assert wm.param_count(cfg) == 3_869_857_792
    assert (cfg.experts_held, cfg.experts_lo, cfg.vocab_held,
            cfg.kv_row_width) == (256, 0, 100352, 2048)
    # the whole model's 40 layers by the same shapes, nothing allocated
    whole = wm.SwaMoeConfig.from_dict(dict(spec, num_hidden_layers=40))
    assert (whole.count(wm.FULL), whole.count(wm.SWA)) == (10, 30)
    assert wm.param_count(whole) == 33_442_596_864


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("moe_apply_router_weight_on_input", True),
    ("gating", False), ("tie_word_embeddings", True),
    ("rope_parameters", {wm.FULL: dict(YARN, rope_type="llama3"),
                         wm.SWA: TINY["rope_parameters"][wm.SWA]}),
    ("layer_types", ["chunked_attention"] * 8),
    ("num_attention_heads_per_layer", [6, 7, 8, 8, 6]),
])
def test_what_is_not_written_here_is_refused(key, value):
    with pytest.raises(ValueError):
        tiny(**{key: value})


@pytest.mark.parametrize("seed", [3, 2147486001, 5000000011])
def test_the_references_own_draw_is_the_programs_bit_for_bit(seed):
    spec, cfg = tiny()
    ours, theirs = wm.init_params(cfg, seed), ref.draw_params(flat(spec),
                                                              seed)
    leaves = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert [p for p, _ in leaves(ours)] == [p for p, _ in leaves(theirs)]
    for (path, a), (_, b) in zip(leaves(ours), leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), str(path))
    assert ref.digests(ours) == ref.digests(theirs)
    assert ours["layer00"]["w_q"].shape == (64, 6, 32)
    assert ours["layer01"]["w_q"].shape == (64, 8, 32)
    assert ours["layer01"]["router"].dtype == jnp.float32


# -- through the plane --------------------------------------------------------

def _served(tenant, tokens, t_us):
    n = len(tokens)
    # the tokeniser's inverse at n_hist 16: service, bucket, class, kind
    kind, rest = tokens % 3, tokens // 3
    cls, rest = rest % 4, rest // 4
    bucket, service = rest % 16, rest // 16
    spans = types.SimpleNamespace(
        service=service.astype(np.int32),
        duration_us=np.expm1(bucket + 0.5).astype(np.int64),
        status=np.asarray([200, 404, 500, 0])[cls].astype(np.int16),
        kind=kind.astype(np.int8),
        start_us=np.full(n, t_us, np.int64))
    return types.SimpleNamespace(tenant_id=tenant, n_spans=n, spans=spans)


@pytest.mark.parametrize("win_blocks", [36, 12])
def test_the_plane_steps_the_model_and_replays_from_its_served_log(
        win_blocks):
    """Seeded random chunkings through ``SeqPlane.step`` at the serving
    dtype, the model picked by ``model_type``: every span scored once, the
    plane's segments are the reference policy's replayed from the served
    log, surprisals near the reference's whole-session ones; rolls,
    evictions by the window pool and a step cut by the grid among them,
    and with 11 window blocks to 8 tenants ticks cut into policy steps."""
    spec = dict(TINY, vocab_size=1024, weights_seed=7,
                audit_tenants=list(range(8)),
                assumed=dict(TINY["assumed"], window_blocks=win_blocks,
                             token_grid=[32, 64]))
    plane = sp.SeqPlane(spec, range(8), 5, 16, 5_000_000)
    assert isinstance(plane.model, sp.SwaMoE)
    assert sp.MODELS["laguna"] is sp.SwaMoE
    assert plane._step.__wrapped__.__name__ == "anomod_seq_step"
    assert set(plane.state) == {"pool", "wpool", "h_last"}
    assert plane.state["wpool"].shape == (3, win_blocks, 8, 128)
    assert plane.state["pool"].shape == (2, 64, 8, 128)
    rng = np.random.default_rng(11)
    log = []
    for tick in range(14):
        tenants = rng.choice(8, rng.integers(1, 9), False)
        chunks = [(int(t), int(rng.integers(1, 24))) for t in tenants]
        if tick == 5:
            chunks = [(t, 12) for t in range(8)]     # 96 tokens: two steps
        served = [_served(t, rng.integers(0, 5 * 16 * 12, n), tick * 10 ** 6)
                  for t, n in chunks]
        plane.step(served)
        log.append(served)
        assert plane.tick_doc["win_blocks_held"] \
            == plane.table.win_blocks_held
    c = plane.counters
    assert set(c) == set(sp.COUNTERS)
    n_spans = sum(qb.n_spans for served in log for qb in served)
    assert c["seq_tokens"] == n_spans
    assert c["seq_steps"] > len(log)
    assert 0 < c["swa_pairs"] < c["full_pairs"]
    assert 0 < c["swa_keys"] < c["full_keys"]
    # the kernel's work items, once a step whatever the layers: a (chunk,
    # window of packed tokens) each, alike under either kind
    assert c["full_items"] == c["swa_items"] >= c["seq_steps"]
    assert c["gqa_items"] == 0
    # a window pool this short ends a session before it is full
    assert (c["sessions_rolled"] > 0) == (win_blocks == 36)
    assert c["sessions_evicted_by_window"] > 0 and c["win_blocks_freed"] > 0
    assert c["win_blocks_held"] == plane.table.win_blocks_held \
        <= win_blocks - 1
    assert (c["steps_split_by_window"] > 0) == (win_blocks == 12)
    assert c["gqa_pairs"] == c["ssm_scan_tokens"] == c["seq_pairs"] == 0
    policy = ref.SessionPolicy(63, win_blocks - 1, 64, 8, 20)
    want = []
    for served in log:
        counts = {}
        for qb in served:
            counts[qb.tenant_id] = counts.get(qb.tenant_id, 0) + qb.n_spans
        want += policy.tick(list(counts.items()))
    got = [(t, number, start, len(tok))
           for t, number, start, tok, _ in plane.audit_segments]
    # a segment the grid cut is audited whole: the cut is the step's
    assert got == want
    assert (policy.rolled, policy.evicted, policy.evicted_by_window,
            policy.steps_split) == (
        plane.table.rolled, plane.table.evicted,
        plane.table.evicted_by_window, plane.table.steps_split_by_window)
    sessions = {}
    for t, number, start, tok, s in plane.audit_segments:
        entry = sessions.setdefault((t, number), ([], []))
        entry[0].append(tok)
        entry[1].append(s)
    theirs = ref.draw_params(flat(dict(spec, vocab_held=1024)), 7)
    assert ref.digests(theirs) == ref.digests(plane.params)
    runner = ref.SessionRunner(flat(dict(spec, vocab_held=1024)), theirs,
                               lengths=(64,))
    gaps = []
    for (t, number), (tok, s) in sessions.items():
        want_s, _ = runner.run(np.concatenate(tok))
        gaps.append(np.abs(want_s - np.concatenate(s)))
    gaps = np.concatenate(gaps)
    assert len(gaps) == n_spans
    # bfloat16 activations at hidden 64 with 16 experts top-2: a near-tie
    # of two scores moves a token's row by half its routed part
    assert gaps.mean() < 0.06 and np.median(gaps) < 0.03
    plane.close()
    assert plane.state == {} and plane.params is None


def test_sketch_outputs_byte_identical_with_this_model_on_and_off():
    """The engine's own path (``ServeEngine(seq_model=)``, the model
    picked by ``model_type``): states, alerts and shed decisions of the
    sketch planes are the same bytes with the plane on or off, and the
    plane scored every served span."""
    from anomod.serve.engine import run_power_law
    spec = dict(TINY, vocab_size=2048, weights_seed=3,
                assumed=dict(TINY["assumed"], pool_tokens=1024,
                             window_blocks=96, token_grid=[64, 512]))
    run = dict(n_tenants=12, n_services=8, duration_s=40.0,
               capacity_spans_per_s=400.0, seed=4)
    on, rep_on = run_power_law(seq_model=spec, flight=True, **run)
    off, rep_off = run_power_law(flight=True, **run)
    assert sorted(on._tenant_replay) == sorted(off._tenant_replay)
    for t in on._tenant_replay:
        a, b = (e._tenant_replay[t].get_state() for e in (on, off))
        assert np.asarray(a.agg).tobytes() == np.asarray(b.agg).tobytes()
        assert np.asarray(a.hist).tobytes() == np.asarray(b.hist).tobytes()
        assert on.alerts_for(t) == off.alerts_for(t)
    for key in ("offered_spans", "served_spans", "shed_spans",
                "shed_batches"):
        assert getattr(on.admission.totals(), key) \
            == getattr(off.admission.totals(), key)
    assert rep_on.n_alerts == rep_off.n_alerts
    c = on.seq_counters
    assert c["seq_tokens"] == on.admission.totals().served_spans > 0
    assert c["swa_pairs"] > 0 and off.seq_counters is None
