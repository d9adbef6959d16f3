"""The hybrid state-space, attention and latent-expert decoder at a tiny
preset on the CPU: chunked appends through both scan forms, the paged K/V
cache and the slot pool against the plain reference's whole-session
logits; the expert shares against the uncut layer; the slot policy
against the reference's replay; the expert body argument of
``held_expert_sum`` against the parent's gated sum, bit for bit."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from anomod.models import hybrid_ssm_moe as hm
from anomod.ops import routed_experts as rx
from anomod.ops import ssm_scan as ss
from anomod.serve import seqplane as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import hybrid_ssm_moe_decoder as ref  # noqa: E402

#: hidden 64, 4 Mamba heads of 16, state 16, 2 groups, chunk 8; 4 query
#: and 2 key-value heads of 32; 16 experts top-2 of which 4 held, latent
#: 32; all three kinds of layer; vocabulary 256
TINY = dict(
    model_type="nemotron_h", hidden_size=64,
    hybrid_override_pattern="MEM*EMEM*EMEMEM", num_hidden_layers=7,
    mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, n_routed_experts=16,
    num_experts_per_tok=2, moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_shared_experts=1,
    routed_scaling_factor=5.0, norm_topk_prob=True,
    layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=0.0001, mlp_hidden_act="relu2", vocab_size=256,
    vocab_held=256, experts_held=4, experts_lo=4,
    assumed=dict(context_tokens=64, block_tokens=8, pool_tokens=512,
                 state_slots=6))


def tiny(**over):
    spec = dict(TINY, **over)
    return spec, hm.HybridConfig.from_dict(spec)


def flat(spec):
    return dict(spec, **spec["assumed"])


@pytest.fixture(scope="module")
def model():
    spec, cfg = tiny()
    return spec, cfg, hm.init_params(cfg, 3, dtype=jnp.float32)


class Stepper:
    """Drives ``append_step`` from a tick's ``(tenant, n)`` chunks as the
    plane does (``SessionTable.place``, one device step a policy step),
    keeping each session's tokens, surprisals and segment starts."""

    def __init__(self, cfg, params, n_tenants=8, grid=128):
        self.cfg, self.params, self.grid = cfg, params, grid
        self.table = sp.SessionTable(cfg.pool_blocks, cfg.context_tokens,
                                     cfg.block_tokens, cfg.state_slots)
        self.state = hm.init_state(cfg, n_tenants, dtype=jnp.float32)
        self.ids = np.arange(n_tenants)
        self.step = jax.jit(lambda p, state, plan: hm.append_step(
            cfg, p, state, plan))
        self.sessions = {}
        self.stats = {}

    def tick(self, chunks, rng):
        counts = []
        for segs in self.table.place(sorted(chunks)):
            n_tok = sum(s[3] for s in segs)
            tok = rng.integers(0, self.cfg.vocab_held, n_tok).astype(
                np.int32)
            caps = hm.plan_caps(self.cfg, self.grid, 2 * len(self.ids))
            plan, stats, _ = hm.build_plan(self.cfg, caps, segs, tok,
                                           self.ids, frozenset())
            self.state, s, _, n = self.step(self.params, self.state, plan)
            s, at = np.asarray(s), 0
            for t, number, start, n_seg, _, _ in segs:
                got = self.sessions.setdefault((t, number), [[], [], []])
                assert sum(map(len, got[0])) == start
                got[0].append(tok[at:at + n_seg])
                got[1].append(s[at:at + n_seg])
                got[2].append(start)
                at += n_seg
            for k, v in stats.items():
                self.stats[k] = self.stats.get(k, 0) + v
            counts.append(np.asarray(n))
        return counts

    def worst_gap(self, spec):
        runner = ref.SessionRunner(flat(spec), self.params, lengths=(64,))
        worst = 0.0
        for tok, s, _ in self.sessions.values():
            tok, s = np.concatenate(tok), np.concatenate(s)
            want, _ = runner.run(tok)
            worst = max(worst, float(np.abs(want - s).max()))
        return worst


@pytest.fixture
def force_form(monkeypatch):
    def force(form):
        if form != "by_size":
            monkeypatch.setattr(
                ss, "recurrent_is_cheaper",
                lambda n, *a, **k: np.full(np.shape(n), form == "recurrent"))
    return force


@pytest.mark.parametrize("form, steps", [
    ("recurrent", (5, 1, 20, 3)),
    ("chunked", (5, 1, 20, 3)),
    # a block exactly full (8), one token past it (9), two blocks and one
    ("chunked", (8, 9, 17, 1)),
    # four blocks at once, then windows cut the chunks where they fall
    ("chunked", (32, 3, 8, 16)),
    ("recurrent", (8, 9, 17, 1)),
    ("by_size", (1, 7, 1, 1, 12)),
])
def test_each_scan_form_equals_the_token_by_token_reference(
        model, force_form, form, steps):
    spec, cfg, params = model
    force_form(form)
    run = Stepper(cfg, params)
    rng = np.random.default_rng(0)
    for n in steps:
        run.tick([(1, n)], rng)
    rec, scan = run.stats["ssm_recurrent_tokens"], \
        run.stats["ssm_scan_tokens"]
    assert rec + scan == run.stats["seq_tokens"] == sum(steps)
    assert (scan == 0) == (form == "recurrent")
    assert (rec == 0) == (form == "chunked")
    if form == "by_size":                # one token a trip, else a block
        assert rec == 3 and run.stats["ssm_scan_blocks"] == 1 + 2
        assert run.stats["ssm_scan_pairs"] == 28 + 36 + 10
    assert run.worst_gap(spec) < 2e-5


@pytest.mark.parametrize("case, ticks", [
    ("block_edge", [[(0, 7)], [(0, 2)], [(0, 15)], [(0, 1)]]),
    ("session_roll", [[(0, 40)], [(0, 30), (1, 3)], [(0, 10)]]),
    # five usable slots: the sixth tenant ends tenant 0's session (least
    # recently appended), which then starts anew
    ("slot_eviction", [[(0, 9), (1, 2)], [(2, 1), (3, 5), (4, 1)],
                       [(1, 1), (5, 3)], [(0, 4), (2, 2)]]),
    # seven tenants in one tick against five slots: two steps
    ("split_step", [[(0, 3), (1, 1)], [(t, 1 + t % 3) for t in range(7)],
                    [(6, 2), (0, 1)]]),
    # chunks of both forms side by side in the windows, cut by their edges
    ("many_chunks", [[(t, 1 + (5 * t) % 11) for t in range(5)],
                     [(t, 1 + (3 * t) % 7) for t in range(5)]]),
])
def test_chunks_through_both_caches_equal_one_full_forward(
        model, case, ticks):
    spec, cfg, params = model
    run = Stepper(cfg, params)
    rng = np.random.default_rng(1)
    for chunks in ticks:
        run.tick(chunks, rng)
    assert run.worst_gap(spec) < 2e-5
    assert run.stats["ssm_scan_tokens"] > 0
    assert run.stats["ssm_recurrent_tokens"] > 0 or case == "session_roll"
    if case == "session_roll":
        assert run.table.rolled == 1 and (0, 1) in run.sessions
    if case == "slot_eviction":
        assert run.table.evicted_by_slots >= 1 and (0, 1) in run.sessions
        assert run.table.slots_held <= 5
    if case == "split_step":
        assert run.table.steps_split == 1 and run.table.evicted >= 2


def scan_by_tokens(x, B, C, dt, A, pool, layer, chunks):
    """The recurrence a token after another in float32 (numpy; ``x``
    ``[T, H, P]``, ``B`` / ``C`` ``[T, G, N]``): ``chunks`` ``(tok0, n,
    slot, fresh)``.  Returns ``(y, {slot: state [N, H * P]})``.  The chip's
    twin of the test below, ``tpu_tests/test_ssm_scan.py``, uses it too."""
    x, B, C, dt, A, pool = (np.asarray(a, np.float32)
                            for a in (x, B, C, dt, A, pool))
    (T, H, P), (G, N) = x.shape, B.shape[1:]
    y, states = np.zeros_like(x), {}
    for tok0, n, slot, fresh in chunks:
        S = np.zeros((H, P, N), np.float32) if fresh else \
            pool[layer, slot].reshape(N, H, P).transpose(1, 2, 0).copy()
        for t in range(tok0, tok0 + n):
            Bt, Ct = (np.repeat(a[t], H // G, axis=0) for a in (B, C))
            S = np.exp(dt[t] * A)[:, None, None] * S \
                + (dt[t][:, None] * x[t])[:, :, None] * Bt[:, None, :]
            y[t] = (S * Ct[:, None, :]).sum(-1)
        states[slot] = S.transpose(2, 0, 1).reshape(N, H * P)
    return y, states


#: (tokens, recurrent?) of the chunks packed back to back from row 0 in
#: windows of 8 rows; every third chunk's session is fresh
SCAN_STEPS = {
    # the first chunk ends mid-window: the neighbour's rows are its own
    "neighbours_mid_block": [(5, False), (6, False), (3, True), (9, False)],
    "fresh_and_carried": [(8, False), (1, True), (2, True), (17, False)],
    "one_block_one_more_four": [(8, False), (9, False), (32, False)],
    "no_recurrent_chunk": [(3, False), (13, False), (1, False)],
    "no_chunked_chunk": [(3, True), (13, True), (1, True), (8, True)],
    "a_recurrent_chunk_across_an_edge": [(6, False), (5, True), (7, True)],
    "no_chunk_at_all": [],
}


@pytest.mark.parametrize("case", sorted(SCAN_STEPS))
@pytest.mark.parametrize("layer", [0, 1])
def test_the_scan_kernel_step_by_step(case, layer):
    """``ssm_scan`` itself (the interpreter's run of the kernel) against
    the token-by-token recurrence: ``y`` of every chunk's own rows, zeros
    elsewhere; the named slots' new states; every other slot of the pool,
    slot 0 and the other layer among them, bit for bit as it was."""
    H, P, N, G, Q, T, slots = 4, 16, 16, 2, 8, 64, 9
    steps = SCAN_STEPS[case]
    n = np.asarray([c[0] for c in steps], np.int64)
    rec = np.asarray([c[1] for c in steps], bool)
    tok0 = np.cumsum(n) - n
    slot = np.asarray([7, 2, 5, 8, 3, 1][:len(n)], np.int64)
    fresh = (np.arange(len(n)) % 3 == 1).astype(np.int64)
    work = ss.empty_work(ss.work_caps(T, 8, Q))
    stats = ss.work_lists(work, tok0, n, slot, fresh, rec, Q)
    assert stats["ssm_recurrent_tokens"] == n[rec].sum()
    assert stats["ssm_scan_tokens"] == n[~rec].sum()
    assert stats["ssm_scan_blocks"] == (-(-n[~rec] // Q)).sum()
    live = work["flags"][:int(work["n_items"])]
    assert (live & ss.FIRST > 0).sum() == (live & ss.LAST > 0).sum() \
        == len(n) and work["n_items"] >= 1
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    x = jax.random.normal(ks[0], (T, H, P), jnp.float32)
    B, C = (0.5 * jax.random.normal(k, (T, G, N), jnp.float32)
            for k in ks[1:3])
    dt = jax.random.uniform(ks[3], (T, H), jnp.float32, 0.001, 0.1)
    A = -jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0)
    pool = jax.random.normal(ks[5], (2, slots, N, H * P), jnp.float32)
    y, after = jax.jit(lambda *a: ss.ssm_scan(*a, layer, work, Q))(
        x.reshape(T, -1), B.reshape(T, -1), C.reshape(T, -1), dt, A, pool)
    y = y.reshape(T, H, P)
    want_y, want = scan_by_tokens(x, B, C, dt, A, pool, layer,
                                   zip(tok0, n, slot, fresh))
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    assert not np.asarray(y)[n.sum():].any()
    after, pool = np.asarray(after), np.asarray(pool)
    for s in range(slots):
        if s in want:
            np.testing.assert_allclose(after[layer, s], want[s], atol=2e-5)
        else:
            np.testing.assert_array_equal(after[layer, s], pool[layer, s])
    np.testing.assert_array_equal(after[1 - layer], pool[1 - layer])


def test_param_count_at_published_widths_is_the_issues_arithmetic():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3-super-ep8-share.json")) as f:
        cfg = hm.HybridConfig.from_dict(json.load(f))
    assert cfg.pattern == "MEMEMEM*EME"
    assert (cfg.count("mamba"), cfg.count("moe"), cfg.count("attn")) \
        == (5, 5, 1)
    assert hm.param_count(cfg) == 2_752_338_304
    assert (cfg.d_inner, cfg.conv_dim, cfg.kv_row_width) \
        == (8192, 10240, 512)
    # both forms are reached by size at published widths: a decode-like
    # chunk of a few tokens recurs, anything longer scans in blocks
    n = np.arange(1, 300)
    rec = ss.recurrent_is_cheaper(n, 128, 64, 128, 8, 128)
    assert rec[0] and not rec[8:].any()


def _whole_and_shares(n_shares=4):
    spec, whole = tiny(experts_held=16, experts_lo=0)
    params = hm.init_params(whole, 5, dtype=jnp.float32)
    lp = params["layer01"]
    shares = []
    for i in range(n_shares):
        share_spec, cfg = tiny(experts_held=4, experts_lo=4 * i)
        cut = dict(lp, **{k: lp[k][4 * i:4 * i + 4] for k in ("e_1", "e_2")})
        shares.append((share_spec, cfg, cut))
    return spec, lp, shares


@pytest.mark.parametrize("path", ["reference", "program"])
def test_the_shares_add_up_to_the_uncut_layer(path):
    spec, lp, shares = _whole_and_shares()
    h = jax.random.normal(jax.random.PRNGKey(0), (40, 64), jnp.float32)
    stacked = lambda p: (lambda name, e=None: p[name] if e is None
                         else p[name][e])
    with jax.default_matmul_precision("highest"):
        routed, shared = ref.moe_parts(flat(spec), stacked(lp), h)
        total = jnp.zeros_like(routed)
        for share_spec, cfg, cut in shares:
            if path == "reference":
                part, again = ref.moe_parts(flat(share_spec), stacked(cut),
                                            h)
            else:
                part, again, _ = hm.moe_parts(cfg, cut, h,
                                              jnp.ones((40,), bool), 16)
            np.testing.assert_allclose(again, shared, atol=1e-5)
            total = total + part
    # every routed expert once, the shared expert counted once
    np.testing.assert_allclose(total + shared, routed + shared, atol=5e-5)
    assert float(jnp.abs(routed).max()) > 0.01


def _parents_held_expert_sum(x, experts, weights, valid, w_gate, w_up,
                             w_down, lo, capacity):
    """``held_expert_sum`` as the parent commit had it (gated SiLU with
    three weights written into the round), kept here for the comparison."""
    T, k = experts.shape
    E = w_gate.shape[0]
    local = experts - lo
    held = (local >= 0) & (local < E) & valid[:, None]
    local = jnp.where(held, local, E).reshape(-1)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    counts = jnp.zeros((E + 1,), jnp.int32).at[local].add(1)[:E]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_held = ends[-1]
    order = jnp.concatenate([order, jnp.zeros((capacity,), jnp.int32)])
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
        mid = (jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)).astype(x.dtype)
        y = jnp.where(live[:, None],
                      dot(mid, w_down) * w_flat[pair][:, None], 0.0)
        return out.at[jnp.where(live, tok, T)].add(y, mode="drop")

    rounds = (n_held + capacity - 1) // capacity
    out = jax.lax.fori_loop(0, rounds, round_body,
                            jnp.zeros((T, x.shape[1]), jnp.float32))
    return out, counts


@pytest.mark.parametrize("seed, dtype", [(0, jnp.float32), (1, jnp.bfloat16),
                                         (2, jnp.bfloat16)])
def test_the_gated_body_is_the_parents_sum_bit_for_bit(seed, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    T, D, F, E, k = 48, 32, 24, 4, 3
    x = jax.random.normal(ks[0], (T, D), dtype)
    experts = jax.random.randint(ks[1], (T, k), 0, 12)
    weights = jax.random.uniform(ks[2], (T, k), jnp.float32)
    valid = jnp.arange(T) % 7 != 0
    w = [jax.random.normal(kk, s, dtype) * 0.2 for kk, s in zip(
        ks[3:], [(E, D, F), (E, D, F), (E, F, D)])]
    want = jax.jit(lambda *a: _parents_held_expert_sum(*a, 4, 16))(
        x, experts, weights, valid, *w)
    got = jax.jit(lambda x, e, wt, v, *w: rx.held_expert_sum(
        x, e, wt, v, rx.gated_silu, w, 4, 16))(x, experts, weights, valid,
                                               *w)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(want[0]).max()) > 0


@pytest.mark.parametrize("seed, blocks, slots", [(0, 40, 5), (1, 24, 4),
                                                 (2, 17, 9)])
def test_slot_policy_equals_the_references_replay(seed, blocks, slots):
    rng = np.random.default_rng(seed)
    table = sp.SessionTable(blocks, 64, 8, slots)
    policy = ref.SessionPolicy(blocks - 1, 64, 8, slots - 1)
    for _ in range(80):
        chunks = sorted((int(t), int(rng.integers(1, 40)))
                        for t in rng.choice(8, rng.integers(1, 8), False))
        if sum(-(-n // 8) + 1 for _, n in chunks) > blocks - 1:
            continue
        got = [s[:4] for step in table.place(chunks) for s in step]
        assert got == policy.tick(chunks)
        assert table.blocks_held == policy.blocks_held
        assert table.slots_held == policy.slots_held
        held = [s.slot for s in table.sessions.values()]
        assert len(held) == len(set(held)) == table.slots_held
        assert 0 not in held and max(held, default=0) < slots
    assert (table.rolled, table.evicted, table.evicted_by_slots,
            table.steps_split) == (policy.rolled, policy.evicted,
                                   policy.evicted_by_slots,
                                   policy.steps_split)
    assert table.rolled > 0 and table.evicted > 0
    assert (table.evicted_by_slots > 0) == (table.steps_split > 0) \
        == (slots < 9)


def test_without_state_slots_the_table_knows_blocks_only():
    rng = np.random.default_rng(3)
    plain, none = sp.SessionTable(24, 64, 8), sp.SessionTable(24, 64, 8,
                                                              None)
    assert plain.free_slots is None and plain.slots_held == 0
    for _ in range(30):
        chunks = sorted((int(t), int(rng.integers(1, 30)))
                        for t in rng.choice(6, rng.integers(1, 4), False))
        segs = plain.append(chunks)
        assert all(len(s) == 5 for s in segs)
        assert none.place(chunks) == [segs]
    assert plain.evicted > 0 and plain.evicted_by_slots == 0


@pytest.mark.parametrize("seed", [3, 2147486001, 5000000011])
def test_the_references_own_draw_is_the_programs_bit_for_bit(seed):
    spec, cfg = tiny()
    ours, theirs = hm.init_params(cfg, seed), ref.draw_params(flat(spec),
                                                              seed)
    leaves = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert [p for p, _ in leaves(ours)] == [p for p, _ in leaves(theirs)]
    for (path, a), (_, b) in zip(leaves(ours), leaves(theirs)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), str(path))
    assert ref.digests(ours) == ref.digests(theirs)
    a = -np.exp(np.asarray(ours["layer00"]["a_log"]))
    assert -16 <= a.min() < a.max() <= -1
    dt = np.log1p(np.exp(np.asarray(ours["layer02"]["dt_bias"])))
    assert 0.001 <= dt.min() < dt.max() <= 0.1001


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


def test_the_plane_steps_the_hybrid_and_replays_from_its_served_log():
    """Seeded random chunkings through ``SeqPlane.step`` at the serving
    dtype: every span scored once, the plane's segments are the
    reference policy's replayed from the served log, surprisals near the
    reference's whole-session ones, both forms, a roll, a slot eviction
    and a split step among them."""
    spec = dict(TINY, vocab_size=1024, vocab_held=1024, weights_seed=7,
                audit_tenants=list(range(8)),
                assumed=dict(TINY["assumed"], token_grid=[32, 128]))
    plane = sp.SeqPlane(spec, range(8), 5, 16, 5_000_000)
    assert isinstance(plane.model, sp.HybridSsmMoE)
    assert plane._step.__wrapped__.__name__ == "anomod_seq_step"
    rng = np.random.default_rng(11)
    log = []
    for tick in range(14):
        tenants = rng.choice(8, rng.integers(1, 9), False)
        chunks = [(int(t), int(rng.integers(1, 24))) for t in tenants]
        if tick == 5:
            chunks = [(t, 2) for t in range(8)]
        served = [_served(t, rng.integers(0, 5 * 16 * 12, n), tick * 10 ** 6)
                  for t, n in chunks]
        plane.step(served)
        log.append(chunks)
    c = plane.counters
    assert set(c) == set(sp.COUNTERS)
    assert c["seq_tokens"] == sum(n for ch in log for _, n in ch)
    assert c["ssm_recurrent_tokens"] + c["ssm_scan_tokens"] \
        == c["seq_tokens"]
    assert c["ssm_recurrent_tokens"] > 0 and c["ssm_scan_blocks"] > 0
    assert c["sessions_rolled"] > 0 and c["sessions_evicted_by_slots"] > 0
    assert c["steps_split_by_slots"] > 0 and c["seq_pairs"] == 0
    assert 0 < c["state_slots_held"] <= 5 and c["gqa_pairs"] > 0
    # the attention kernel's work items: at least a chunk's one
    assert c["gqa_items"] >= sum(len(ch) for ch in log)
    assert c["full_items"] == c["swa_items"] == 0
    assert c["ssm_state_rows"] >= 3 * sum(len(ch) for ch in log)
    policy = ref.SessionPolicy(plane.table.usable, 64, 8, 5)
    want = [s for chunks in log for s in policy.tick(chunks)]
    got = [(t, number, start, len(tok))
           for t, number, start, tok, _ in plane.audit_segments]
    assert got == want
    assert (policy.rolled, policy.evicted_by_slots, policy.steps_split) == (
        c["sessions_rolled"], c["sessions_evicted_by_slots"],
        c["steps_split_by_slots"])
    sessions = {}
    for t, number, start, tok, s in plane.audit_segments:
        entry = sessions.setdefault((t, number), ([], []))
        entry[0].append(tok)
        entry[1].append(s)
    runner = ref.SessionRunner(flat(spec), plane.params, lengths=(64,))
    gaps = np.concatenate([
        np.abs(runner.run(np.concatenate(tok))[0] - np.concatenate(s))
        for tok, s in sessions.values()])
    assert len(gaps) == c["seq_tokens"] and gaps.mean() < 0.02
    # the K2 plane is the other model, and counts the same names
    from test_latent_moe import TINY as K2
    k2 = sp.SeqPlane(dict(K2, vocab_size=1024, vocab_held=1024, assumed=dict(
        K2["assumed"], token_grid=[32])), range(4), 5, 16, 5_000_000)
    assert isinstance(k2.model, sp.LatentMoE) \
        and k2.table.free_slots is None
    assert set(k2.counters) == set(sp.COUNTERS) and set(k2.state) == {
        "pool", "h_last"}
    k2.step([_served(0, rng.integers(0, 5 * 16 * 12, 9), 0)])
    assert k2.counters["seq_tokens"] == 9 and not any(
        k2.counters[k] for k in ("gqa_items", "full_items", "swa_items"))
    k2.pool = None
    assert k2.state["pool"] is None and k2.h_last is not None


def test_a_pattern_the_model_does_not_know_is_refused():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        tiny(hybrid_override_pattern="M-M*EME")
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        tiny(mlp_hidden_act="silu")


def test_the_controls_rounding_is_float8_e4m3_written_out():
    """The control's ``_held`` in float32 arithmetic equals the dtype's
    own conversion (saturating), value for value; off, it is the
    identity."""
    rng = np.random.default_rng(0)
    x = np.concatenate(
        [rng.standard_normal(5000).astype(np.float32) * s
         for s in (1e-4, 1e-2, 1, 30, 600)]
        + [np.asarray([0.0, 448.0, 500.0, -1e-9, 2.0 ** -9, 2.0 ** -10,
                       3 * 2.0 ** -10, -0.0175], np.float32)])
    want = jnp.clip(jnp.asarray(x), -448, 448).astype(
        ref.CONTROL_DTYPE).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ref._held(jnp.asarray(x), True)), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(ref._held(jnp.asarray(x), False)), x)


def test_the_control_rounds_what_a_cache_carries_between_segments(model):
    spec, cfg, params = model
    tok = np.random.default_rng(5).integers(0, 256, 60).astype(np.int32)
    runner = ref.SessionRunner(flat(spec), params, lengths=(64,))
    plain, rows = runner.run(tok, rows=[9, 59])
    gaps = []
    for bounds in [(), (10, 20, 30), tuple(range(1, 60))]:
        s, kept = runner.run(tok, rows=[9, 59], control=True, bounds=bounds)
        gaps.append(float(np.abs(s - plain).mean()))
        assert np.abs(kept - rows).max() > 0      # keys and values alone
    # the more often a cache hands the state on, the wider the gap
    assert 0 < gaps[0] < gaps[1] < gaps[2]
    # and a switch of the compiled layers that the reference does not feel
    np.testing.assert_array_equal(runner.run(tok)[0], plain)
