"""The latent-attention, routed-expert decoder at the tiny preset on the
CPU: both attention forms and the paged cache against the plain
reference, the router against a hand-worked case, the expert shares
against the uncut layer, the dispatch under a routing skew."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from anomod.models import latent_moe as lm
from anomod.ops import latent_attention as la
from anomod.ops import routed_experts as rx
from anomod.serve import seqplane as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

YARN = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
#: hidden 64, 4 heads, 16 routed experts top-2 of which 4 held, 3 layers,
#: vocabulary 256
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=64,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=2.827,
    norm_topk_prob=True, first_k_dense_replace=1, num_hidden_layers=3,
    rms_norm_eps=1e-6, rope_theta=50000, rope_scaling=YARN, vocab_size=256,
    vocab_held=256, experts_held=4, experts_lo=4,
    assumed=dict(context_tokens=64, block_tokens=8, pool_tokens=512))
K2_DIMS = dict(heads=64, nope=128, rope=64, v_dim=128, latent=512, block=128)


def tiny(**over):
    spec = dict(TINY, **over)
    return spec, lm.DecoderConfig.from_dict(spec)


@pytest.fixture(scope="module")
def model():
    spec, cfg = tiny()
    return spec, cfg, lm.init_params(cfg, 3, dtype=jnp.float32)


class Stepper:
    """Drives ``append_step`` from ``(tenant, n)`` chunks as the plane
    does, keeping each session's tokens and surprisals."""

    def __init__(self, cfg, params, n_tenants=6, blocks=None, grid=128):
        self.cfg, self.params, self.grid = cfg, params, grid
        self.table = sp.SessionTable(blocks or cfg.pool_blocks,
                                     cfg.context_tokens, cfg.block_tokens)
        self.pool = jnp.zeros((cfg.num_hidden_layers, cfg.pool_blocks,
                               cfg.block_tokens, cfg.pool_row_width),
                              jnp.float32)
        self.h_last = jnp.zeros((n_tenants + 1, cfg.hidden_size),
                                jnp.float32)
        self.ids = np.arange(n_tenants)
        self.step = jax.jit(lambda p, pool, h, plan: lm.append_step(
            cfg, p, pool, h, plan))
        self.sessions = {}
        self.expanded_tokens = self.tokens = 0

    def append(self, chunks, rng):
        segs = self.table.append(sorted(chunks))
        n_tok = sum(s[3] for s in segs)
        tok = rng.integers(0, self.cfg.vocab_held, n_tok).astype(np.int32)
        caps = lm.plan_caps(self.cfg, self.grid, 2 * len(self.ids))
        plan, stats, _ = sp.build_plan(self.cfg, caps, segs, tok, self.ids,
                                       frozenset())
        self.pool, self.h_last, s, _, counts = self.step(
            self.params, self.pool, self.h_last, plan)
        s, at = np.asarray(s), 0
        for t, number, start, n, _ in segs:
            got = self.sessions.setdefault((t, number), [[], []])
            assert sum(map(len, got[0])) == start
            got[0].append(tok[at:at + n])
            got[1].append(s[at:at + n])
            at += n
        self.tokens += stats["seq_tokens"]
        self.expanded_tokens += stats["seq_tokens"] \
            - stats["seq_absorbed_tokens"]
        return stats, np.asarray(counts)

    def worst_gap(self):
        worst = 0.0
        for tok, s in self.sessions.values():
            tok, s = np.concatenate(tok), np.concatenate(s)
            ref = lm.reference_surprisal(
                lm.reference_logits(self.cfg, self.params, tok), tok,
                self.cfg.vocab_held)
            worst = max(worst, float(np.abs(np.asarray(ref) - s).max()))
        return worst


@pytest.fixture
def force_form(monkeypatch):
    def force(form):
        if form != "by_size":
            monkeypatch.setattr(
                la, "absorbed_is_cheaper",
                lambda n, total, *a, **k: np.full(
                    np.shape(n), form == "absorbed"))
    return force


@pytest.mark.parametrize("form, steps", [
    ("absorbed", (5, 1, 20, 14)),
    ("expanded", (5, 1, 20, 14)),
    # the kernel's groups: one live row of GROUP (9 = 8 + 1), a last block
    # partly filled (block 8: 9 + 17 + 3 = 29), one token against it
    ("absorbed", (9, 17, 3, 1)),
])
def test_each_attention_form_equals_the_reference(model, force_form, form,
                                                  steps):
    _, cfg, params = model
    force_form(form)
    run = Stepper(cfg, params)
    rng = np.random.default_rng(0)
    for n in steps:
        run.append([(1, n)], rng)
    assert (run.expanded_tokens == run.tokens) == (form == "expanded")
    assert run.worst_gap() < 2e-5


@pytest.mark.parametrize("case, chunks, blocks", [
    ("block_edge", [[(0, 7)], [(0, 2)], [(0, 15)], [(0, 1)]], None),
    ("session_roll", [[(0, 40)], [(0, 30), (1, 3)], [(0, 10)]], None),
    ("eviction", [[(0, 60)], [(1, 60)], [(2, 60), (3, 9)], [(0, 5)],
                  [(1, 4), (3, 2)]], 24),
    # the absorbed kernel's walks, all in the last call: 64 blocks (a
    # session of 512 tokens, full), 1 block, and 13 with the last block
    # holding one token; a group of one live row (11 = 8 + 3, 3 = 3)
    ("ragged_walks", [[(0, 120)], [(0, 120)], [(0, 120)], [(0, 120)],
                      [(0, 21), (2, 96)],
                      [(0, 11), (1, 1), (2, 1), (3, 3)]], None),
])
def test_appended_chunks_through_the_paged_cache_equal_one_full_forward(
        model, force_form, case, chunks, blocks):
    _, cfg, params = model
    if case == "ragged_walks":
        _, cfg = tiny(assumed=dict(context_tokens=512, block_tokens=8,
                                   pool_tokens=1024))
        force_form("absorbed")
    run = Stepper(cfg, params, blocks=blocks)
    rng = np.random.default_rng(1)
    for step in chunks:
        stats, _ = run.append(step, rng)
    assert run.worst_gap() < 2e-5
    if case == "session_roll":
        assert run.table.rolled == 1 and (0, 1) in run.sessions
    if case == "eviction":
        # 23 usable blocks of 8 tokens: the third step ends tenant 0's
        # session (least recently appended), which then starts anew
        assert run.table.evicted >= 1 and (0, 1) in run.sessions
    if case == "ragged_walks":
        assert run.table.rolled == 1
        assert stats["seq_absorbed_group_blocks"] == 64 + 64 + 13 + 1 + 1


def _dense_absorbed(q_cat, q_pos, pool, seg_blocks, tok_seg, w_v, scale,
                    latent):
    """Every absorbed token against its session's whole block table, a
    plain softmax over the keys at or before its position."""
    out = np.zeros(q_cat.shape[:2] + (w_v.shape[-1],), np.float32)
    for t in np.nonzero(tok_seg >= 0)[0]:
        keys = pool[seg_blocks[tok_seg[t]]].reshape(-1, pool.shape[-1])
        s = (q_cat[t] @ keys.T) * scale
        s[:, np.arange(len(keys)) > q_pos[t]] = -np.inf
        p = np.exp(s - s.max(axis=1, keepdims=True))
        o_lat = (p / p.sum(axis=1, keepdims=True)) @ keys[:, :latent]
        out[t] = np.einsum("hc,chv->hv", o_lat, w_v)
    return out


@pytest.mark.parametrize("case, held, new", [
    ("no_groups", [], []),                  # the warm-up's empty plan
    ("one_live_row", [30, 0], [9, 1]),
    ("one_and_64_blocks", [505, 0, 100], [7, 2, 3]),
    ("last_block_partly_filled", [11, 61, 7], [8, 16, 1]),
])
def test_the_absorbed_kernel_on_ragged_work(case, held, new):
    """``absorbed_attention`` alone under the interpreter, the pool and
    the queries random: rows of no group and the pad rows come back
    zero, every other row is the dense softmax's."""
    H, latent, W, block = 4, 64, 128, 8
    _, cfg = tiny(assumed=dict(context_tokens=512, block_tokens=block,
                               pool_tokens=2048))
    caps = lm.plan_caps(cfg, 32, 8)
    plan = lm.empty_plan(cfg, caps, 0)
    n_tok = sum(new)
    if new:
        table = sp.SessionTable(cfg.pool_blocks, cfg.context_tokens, block)
        table.append([(t, n) for t, n in enumerate(held) if n])
        plan, stats, _ = sp.build_plan(
            cfg, caps, table.append(list(enumerate(new))),
            np.zeros(n_tok, np.int32), np.arange(len(new)), frozenset())
        assert stats["seq_absorbed_tokens"] == n_tok
    rng = np.random.default_rng(4)
    T1 = caps["tokens"] + la.GROUP
    q_cat = rng.standard_normal((T1, H, W)).astype(np.float32)
    pool = rng.standard_normal((cfg.pool_blocks, block, W)).astype(
        np.float32)
    w_v = rng.standard_normal((latent, H, 16)).astype(np.float32)
    pad = lambda a: np.concatenate([a, np.zeros(la.GROUP, a.dtype)])
    tok_seg = np.concatenate([plan["tok_seg"], np.full(la.GROUP, -1)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(la.absorbed_attention(
            jnp.asarray(q_cat[..., :latent]), jnp.asarray(q_cat[..., latent:]),
            pad(plan["tok_pos"]), tok_seg >= 0, jnp.asarray(pool),
            plan["seg_blocks"], plan["groups"], jnp.asarray(w_v), 0.25,
            block))
    want = _dense_absorbed(q_cat, pad(plan["tok_pos"]), pool,
                           plan["seg_blocks"], tok_seg, w_v, 0.25, latent)
    assert not got[n_tok:].any()
    np.testing.assert_allclose(got, want, atol=2e-4)
    if case == "one_and_64_blocks":
        g = plan["groups"]
        assert sorted(g["nblk"][:int(g["n_groups"])]) == [1, 13, 64]
        assert stats["seq_absorbed_group_blocks"] == 78


def test_both_forms_in_one_step_chosen_by_size(model, monkeypatch):
    _, cfg, params = model
    for name, value in (("Q_TILE", 8), ("KV_BLOCKS", 1), ("GROUP", 2)):
        monkeypatch.setattr(la, name, value)
    run = Stepper(cfg, params)
    rng = np.random.default_rng(2)
    run.append([(0, 30), (1, 2)], rng)
    stats, _ = run.append([(0, 30), (1, 1), (2, 3)], rng)
    assert 0 < stats["seq_absorbed_tokens"] < stats["seq_tokens"]
    assert run.worst_gap() < 2e-5


def test_the_forms_cross_near_170_appended_tokens_at_published_widths():
    n = np.arange(1, 1024)
    cheaper = la.absorbed_is_cheaper(n, n + 8000, **K2_DIMS)
    first = int(n[~cheaper][0])
    # 170 by the unpadded FLOPs; the query tile of 256 moves it to 209 and
    # hands 257..288 back to the absorbed form
    assert 150 <= first <= 260 and not cheaper[n > 300].any()
    # against no cache at all a short chunk stays absorbed too
    assert la.absorbed_is_cheaper(3, 3, **K2_DIMS)


def test_router_choice_weights_and_scaling_hand_worked():
    # two tokens, four experts, top-2: the bias chooses, the unbiased
    # scores weigh
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], jnp.float32)
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.5, -0.5, 1.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 2.0])
    experts, weights = rx.route(x, w, bias, 2, 2.827, True)
    sig = lambda v: 1 / (1 + math.exp(-v))
    # token 0: scores .881 .731 .5 .269, biased 2.269 is first, then .881
    # token 1: scores .731 .269 .881 .5, biased 2.5 first, then .881
    assert experts.tolist() == [[3, 0], [3, 2]]
    for row, (a, b) in zip(np.asarray(weights),
                           [(sig(-1), sig(2)), (sig(0), sig(2))]):
        np.testing.assert_allclose(
            row, [2.827 * a / (a + b), 2.827 * b / (a + b)], rtol=1e-6)
    _, plain = rx.route(x, w, bias, 2, 1.0, False)
    np.testing.assert_allclose(np.asarray(plain)[0], [sig(-1), sig(2)],
                               rtol=1e-6)


def _whole_and_shares(n_shares=4):
    spec, whole = tiny(experts_held=16, experts_lo=0)
    params = lm.init_params(whole, 5, dtype=jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["moe"])
    shares = []
    for i in range(n_shares):
        _, cfg = tiny(experts_held=4, experts_lo=4 * i)
        cut = dict(lp, **{k: lp[k][4 * i:4 * i + 4]
                          for k in ("e_gate", "e_up", "e_down")})
        shares.append((cfg, cut))
    return whole, lp, shares


@pytest.mark.parametrize("path", ["reference", "program"])
def test_the_shares_add_up_to_the_uncut_layer(path):
    whole, lp, shares = _whole_and_shares()
    h = jax.random.normal(jax.random.PRNGKey(0), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, shared = lm.reference_moe_parts(whole, lp, h)
        total = jnp.zeros_like(routed)
        for cfg, cut in shares:
            if path == "reference":
                part, again = lm.reference_moe_parts(cfg, cut, h)
            else:
                part, again, _ = lm.moe_parts(cfg, cut, h,
                                              jnp.ones((40,), bool), 16)
            np.testing.assert_allclose(again, shared, atol=1e-5)
            total = total + part
    # every routed expert once, the shared expert counted once
    np.testing.assert_allclose(total + shared, routed + shared, atol=2e-5)
    assert float(jnp.abs(routed).max()) > 0.01


def test_no_token_is_dropped_when_every_token_goes_to_one_expert():
    _, cfg = tiny()
    params = lm.init_params(cfg, 6, dtype=jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["moe"])
    # the bias sends every token to expert 5 (held: the share is [4, 8))
    lp = dict(lp, router_bias=lp["router_bias"].at[5].set(100.0))
    h = jax.random.normal(jax.random.PRNGKey(1), (64, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = lm.reference_moe_parts(cfg, lp, h)
        # capacity 16 rows against at least 64 held pairs: four rounds
        got, _, counts = lm.moe_parts(cfg, lp, h, jnp.ones((64,), bool), 16)
    assert int(counts[1]) == 64 and int(counts.sum()) >= 64
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(want).min(axis=1).max()) > 0   # every row routed


def test_the_program_and_benchmark_references_are_equal(model):
    from benchmark.reference import latent_moe_decoder as ref
    spec, cfg, params = model
    tokens = np.random.default_rng(3).integers(0, 256, 50).astype(np.int32)
    want = np.asarray(lm.reference_logits(cfg, params, tokens))
    c = dict(spec, **spec["assumed"])
    runner = ref.SessionRunner(c, params, lengths=(64,))
    rows = [0, 7, 49]
    s, logits = runner.run(tokens, rows)
    np.testing.assert_allclose(logits, want[rows], atol=2e-5)
    np.testing.assert_allclose(
        s, lm.reference_surprisal(want, tokens, 256), atol=2e-5)
    # the control is another answer
    coarse = runner.run(tokens, control=True)[0]
    assert np.abs(coarse - s).max() > 1e-3
    # and a switch of the compiled layers, which the reference's own
    # answer does not feel
    np.testing.assert_array_equal(runner.run(tokens)[0], s)


@pytest.mark.parametrize("seed", [3, 2147486001, 5000000011])
def test_the_references_own_draw_is_the_programs_bit_for_bit(seed):
    from benchmark.reference import latent_moe_decoder as ref
    cfg = lm.DecoderConfig.from_dict(TINY)
    ours, theirs = lm.init_params(cfg, seed), ref.draw_params(TINY, seed)
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert [p for p, _ in flat(ours)] == [p for p, _ in flat(theirs)]
    for (path, a), (_, b) in zip(flat(ours), flat(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), str(path))
    assert ref.digests(ours) == ref.digests(theirs)
    # the digest tells two experts changed over, which a plain sum does not
    swapped = dict(ours, moe=dict(
        ours["moe"], e_up=ours["moe"]["e_up"][:, ::-1]))
    differing = set(ref.digests(swapped).items()) \
        ^ set(ref.digests(theirs).items())
    assert {k for k, _ in differing} == {"['moe']['e_up']"}


def test_yarn_frequencies_and_scale_of_the_published_rope():
    _, cfg = tiny(qk_rope_head_dim=64, qk_nope_head_dim=128)
    freq = lm.rope_inv_freq(cfg)
    plain = 1.0 / 50000 ** (np.arange(0, 64, 2) / 64)
    # dimensions that turn more than once over 4,096 positions keep their
    # frequency, the slow ones are interpolated by the factor
    np.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(freq[21:], plain[21:] / 32, rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert lm.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
