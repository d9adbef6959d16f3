"""The sequence-model plane of the serve tick at the tiny preset: the
tokeniser, the session policy against the reference's replay, the
sketch planes untouched by the plane, its spans, counters and refusals."""

import contextlib
import types

import jax
import numpy as np
import pytest

from anomod.ops import latent_attention as la
from anomod.serve import seqplane as sp
from anomod.serve.engine import run_power_law
from anomod.utils.tracing import Tracer
from benchmark.reference import latent_moe_decoder as ref
from test_latent_moe import TINY

SPEC = dict(TINY, vocab_size=2048, vocab_held=2048, weights_seed=3,
            assumed=dict(context_tokens=64, block_tokens=8, pool_tokens=1024,
                         token_grid=[64, 512]))
RUN = dict(n_tenants=12, n_services=8, duration_s=60.0,
           capacity_spans_per_s=400.0, seed=4)


def test_tokeniser_formula_range_and_reference_copy():
    rng = np.random.default_rng(0)
    n = 500
    service = rng.integers(0, 45, n)
    dur = rng.integers(1, 10_000_000, n)
    status = rng.choice([0, 200, 302, 404, 500, 503], n)
    kind = rng.integers(0, 3, n)
    ids = sp.tokenise(service, dur, status, kind, 16)
    np.testing.assert_array_equal(
        ids, ref.tokenise(service, dur, status, kind, 16))
    assert ids.min() >= 0 and ids.max() < sp.vocab_needed(45, 16) == 8640
    # service 3, log1p(2980) = 8.0 -> bucket 8, a 404, an exit span
    one = sp.tokenise([3], [2980], [404], [1], 16)
    assert one.tolist() == [((3 * 16 + 8) * 4 + 1) * 3 + 1]
    assert sp.tokenise([0], [10 ** 9], [0], [0], 16).tolist() == [15 * 12 + 9]


@pytest.mark.parametrize("seed, blocks", [(0, 40), (1, 24), (2, 17)])
def test_session_policy_equals_the_references_replay(seed, blocks):
    rng = np.random.default_rng(seed)
    table = sp.SessionTable(blocks, 64, 8)
    policy = ref.SessionPolicy(blocks - 1, 64, 8)
    for _ in range(60):
        chunks = sorted((int(t), int(rng.integers(1, 40)))
                        for t in rng.choice(8, rng.integers(1, 5), False))
        if sum(-(-n // 8) + 1 for _, n in chunks) > blocks - 1:
            continue
        got = [s[:4] for s in table.append(chunks)]
        assert got == policy.step(chunks)
        assert table.blocks_held == policy.blocks_held
        held = [b for s in table.sessions.values() for b in s.blocks]
        assert len(held) == len(set(held)) == table.blocks_held
        assert 0 not in held
    assert (table.rolled, table.evicted) == (policy.rolled, policy.evicted)
    assert table.rolled > 0 and (table.evicted > 0 or blocks == 40)


@pytest.fixture(scope="module")
def runs():
    tracer = Tracer("seq-test")
    on, rep_on = run_power_law(seq_model=SPEC, tracer=tracer, flight=True,
                               **RUN)
    off, rep_off = run_power_law(flight=True, **RUN)
    return on, rep_on, off, rep_off, tracer


def test_sketch_outputs_byte_identical_with_the_plane_on_and_off(runs):
    on, rep_on, off, rep_off, _ = runs
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
    assert rep_on.n_alerts == rep_off.n_alerts and rep_on.n_alerts > 0


def test_plane_scores_every_served_span_with_spans_and_counters(runs):
    on, rep_on, off, _, tracer = runs
    c = on.seq_counters
    assert set(sp.COUNTERS) == set(c) and off.seq_counters is None
    assert c["seq_tokens"] == on.admission.totals().served_spans > 0
    assert c["seq_pairs"] >= c["seq_absorbed_pairs"] > 0
    assert c["sessions_rolled"] > 0 and c["pool_blocks_held"] > 0
    assert c["expert_tokens_max"] >= c["expert_tokens_mean"] > 0
    names = {s["name"] for s in tracer.to_chrome()}
    assert {"serve.seq_stage", "serve.seq_model", "serve.seq_score"} <= names
    scores = list(on.seq_scores)
    assert scores and off.seq_scores is None
    tenant, window, n, mean, most = scores[0]
    assert n >= 1 and 0 < mean <= most < 50
    assert on._seq._step.__wrapped__.__name__ == "anomod_seq_step"
    ticks = on.flight_recorder.records()
    assert all("seq" in rec for rec in ticks)
    assert sum(rec["seq"]["tokens"] for rec in ticks) == c["seq_tokens"]
    # the absorbed kernel's working steps: every absorbed token is in a
    # group of at most GROUP that walks at least one block
    assert sum(rec["seq"].get("absorbed_group_blocks", 0) for rec in ticks) \
        == c["seq_absorbed_group_blocks"] \
        >= c["seq_absorbed_tokens"] / la.GROUP > 0
    assert all(rec["seq"] == {"tokens": 0}
               for rec in off.flight_recorder.records())


@pytest.mark.parametrize("kw, what", [
    (dict(tier_hot=4), "state tiering"),
    (dict(worker="process", shards=2), "process workers"),
    (dict(mesh=object()), "a mesh"),
    (dict(async_commit=True), "deferred-commit"),
])
def test_planes_that_refuse_the_sequence_model(kw, what):
    with pytest.raises(ValueError) as err:
        run_power_law(seq_model=SPEC, **dict(RUN, **kw))
    assert "sequence-model plane" in str(err.value) or what in str(err.value)


def test_a_vocabulary_slice_too_small_for_the_tokeniser_is_refused():
    with pytest.raises(ValueError, match="event ids"):
        run_power_law(seq_model=dict(SPEC, vocab_held=256, vocab_size=256),
                      **RUN)


class Recording:
    """A tracer that keeps every span's opening and closing in order."""

    def __init__(self):
        self.events = []

    @contextlib.contextmanager
    def span(self, name, **tags):
        self.events.append(("open", name, tags))
        try:
            yield
        finally:
            self.events.append(("close", name, tags))


def _tick_of(rng, n_tenants, n_spans):
    """One tick's served batches: ``n_spans`` spans over the tenants."""
    served, left = [], n_spans
    for t in range(n_tenants):
        n = left if t == n_tenants - 1 else int(rng.integers(1, left // 2))
        left -= n
        served.append(types.SimpleNamespace(
            tenant_id=t, n_spans=n, spans=types.SimpleNamespace(
                service=rng.integers(0, 8, n),
                duration_us=rng.integers(1, 10 ** 6, n),
                status=rng.choice([200, 404, 500], n),
                kind=rng.integers(0, 3, n),
                start_us=np.sort(rng.integers(0, 10 ** 7, n)))))
    return served


def test_a_step_is_issue_wait_and_fetch_inside_seq_model_and_changes_nothing():
    spec = dict(SPEC, audit_tenants=[0, 3],
                assumed=dict(SPEC["assumed"], token_grid=[64]))
    rec = Recording()
    traced, plain = (sp.SeqPlane(spec, range(6), 8, 16, 5_000_000, tracer=t)
                     for t in (rec, None))
    plans = []
    build = traced.model.build_plan
    traced.model.build_plan = lambda *a: plans.append(build(*a)) or plans[-1]
    rng = np.random.default_rng(7)
    n_steps = fetched = 0
    for _ in range(3):
        served = _tick_of(rng, 6, 150)
        at = len(rec.events)
        traced.step(served)
        plain.step(served)
        assert traced.tick_doc == plain.tick_doc
        assert traced.tick_doc["steps"] == 3        # 150 tokens, 64 a step
        events = rec.events[at:]
        names = [(what, name) for what, name, _ in events]
        leaf = [(w, "serve.seq_" + part)
                for part in ("issue", "wait", "fetch")
                for w in ("open", "close")]
        assert names == (
            [("open", "serve.seq_stage"), ("close", "serve.seq_stage"),
             ("open", "serve.seq_model")] + 3 * leaf
            + [("close", "serve.seq_model"), ("open", "serve.seq_score"),
               ("close", "serve.seq_score")])
        opened = [(name, tags) for what, name, tags in events
                  if what == "open" and name[6:] in ("seq_issue", "seq_wait",
                                                     "seq_fetch")]
        for i, (name, tags) in enumerate(opened):
            plan, stats, audit_rows = plans[n_steps + i // 3]
            assert (tags["step"], tags["grid"], tags["tokens"]) \
                == (i // 3, 64, stats["seq_tokens"])
            if name == "serve.seq_issue":
                assert tags["bytes"] == sum(
                    a.nbytes for a in jax.tree_util.tree_leaves(plan))
            elif name == "serve.seq_fetch":
                # surprisals and expert counts; the audit logits only
                # where the step holds an audit tenant's last token
                assert tags["bytes"] == 64 * 4 + 2 * 4 * 4 \
                    + (64 * 2048 * 4 if audit_rows else 0)
                fetched += tags["bytes"]
        n_steps += 3
    c = traced.counters
    assert c["seq_steps"] == n_steps == len(plans) == 9
    assert c["seq_plan_bytes"] == sum(
        a.nbytes for plan, *_ in plans
        for a in jax.tree_util.tree_leaves(plan)) > 0
    assert c["seq_fetch_bytes"] == fetched > 9 * 64 * 4
    # to the bit what the plane without a tracer scores
    assert c == plain.counters
    assert list(traced.scores) == list(plain.scores) and traced.scores
    assert len(traced.audit_logits) == len(plain.audit_logits) > 0
    for a, b in zip(traced.audit_logits, plain.audit_logits):
        assert a[:3] == b[:3] and a[3].tobytes() == b[3].tobytes()
    for a, b in zip(traced.audit_segments, plain.audit_segments):
        assert a[4].tobytes() == b[4].tobytes()
