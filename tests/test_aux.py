"""Aux subsystems: checkpoint/resume, tracing, explicit collectives."""

import numpy as np
import pytest

from anomod.utils.checkpoint import restore_train_state, save_train_state
from anomod.utils.tracing import Tracer


def test_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp
    import optax
    params = {"dense": {"kernel": jnp.arange(12.0).reshape(3, 4),
                        "bias": jnp.zeros(4)}}
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)
    backend = save_train_state(tmp_path / "ck", params, opt_state, step=42,
                               meta={"model": "gcn"})
    assert backend in ("orbax", "pickle")
    p2, o2, step, meta = restore_train_state(tmp_path / "ck")
    assert step == 42
    assert meta["model"] == "gcn"
    np.testing.assert_array_equal(np.asarray(p2["dense"]["kernel"]),
                                  np.arange(12.0).reshape(3, 4))
    # structure must survive (optax namedtuples), not just leaf values:
    # a resumed tx.update must work on the restored state
    import jax
    import jax.numpy as jnp2
    assert (jax.tree_util.tree_structure(o2)
            == jax.tree_util.tree_structure(opt_state))
    grads = jax.tree_util.tree_map(jnp2.ones_like, p2)
    updates, _ = tx.update(grads, o2, p2)
    assert jax.tree_util.tree_leaves(updates)


def test_checkpoint_meta_cannot_clobber_step(tmp_path):
    import jax.numpy as jnp
    save_train_state(tmp_path / "ck", {"w": jnp.ones(2)}, (), step=42,
                     meta={"step": 99})
    _, _, step, _ = restore_train_state(tmp_path / "ck")
    assert step == 42


def test_tracer_jaeger_roundtrip(tmp_path):
    from anomod.io.sn_traces import load_jaeger_json
    tr = Tracer("anomod-test")
    with tr.span("pipeline"):
        with tr.span("load"):
            pass
        with tr.span("detect"):
            pass
    path = tmp_path / "trace.json"
    tr.dump(path)
    batch = load_jaeger_json(path)
    assert batch.n_spans == 3
    assert batch.services == ("anomod-test",)
    # parent structure: load/detect are children of pipeline
    assert (batch.parent == -1).sum() == 1


def test_ring_allreduce_matches_psum():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from anomod.parallel import make_mesh
    from anomod.parallel.collectives import ring_allreduce

    mesh = make_mesh(8)
    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)

    def body(xs):
        local = xs[0]
        return ring_allreduce(local, "data")[None]

    fn = shard_map(body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    out = np.asarray(jax.jit(fn)(x))
    expect = x.sum(axis=0)
    for d in range(8):
        np.testing.assert_allclose(out[d], expect, rtol=1e-6)


def test_hll_pmax_merge_across_shards():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from anomod.ops import hll_add, hll_estimate, hll_init
    from anomod.parallel import make_mesh
    from anomod.parallel.collectives import pmax_merge_hll

    p = 10
    items = (np.arange(64_000, dtype=np.int64) * 2654435761 % (2**31)
             ).astype(np.int32).reshape(8, -1)
    mesh = make_mesh(8)

    def body(shard_items):
        regs = hll_add(hll_init(p, xp=jnp), shard_items[0], p=p, xp=jnp)
        return pmax_merge_hll(regs, "data")[None]

    fn = shard_map(body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
    out = np.asarray(jax.jit(fn)(items))
    est = hll_estimate(out[0])
    assert abs(est - 64_000) / 64_000 < 0.08
    # all shards hold the identical merged state
    for d in range(1, 8):
        np.testing.assert_array_equal(out[d], out[0])


def test_tdigest_allgather_merge_across_shards():
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from anomod.ops import tdigest_build, tdigest_quantile
    from anomod.parallel import make_mesh
    from anomod.parallel.collectives import allgather_merge_tdigests

    rng = np.random.default_rng(0)
    vals = rng.lognormal(3.0, 1.0, (8, 4000)).astype(np.float32)
    mesh = make_mesh(8)

    def body(shard_vals):
        d = tdigest_build(shard_vals[0], k=64, xp=jnp)
        m, w = allgather_merge_tdigests(d.mean, d.weight, "data", k=64)
        return m[None], w[None]

    fn = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                   out_specs=(P("data"), P("data")))
    mean, weight = jax.jit(fn)(vals)
    from anomod.ops.tdigest import TDigest
    d = TDigest(mean=np.asarray(mean)[0], weight=np.asarray(weight)[0])
    for q in (0.5, 0.99):
        exact = np.quantile(vals.reshape(-1), q)
        assert abs(tdigest_quantile(d, q) - exact) / exact < 0.05


def test_train_rca_checkpoint_resume(tmp_path):
    """An interrupted training run resumes from its checkpoint: train N
    epochs with a checkpoint dir, then 'resume' a fresh call which must
    (a) load the saved epoch instead of restarting, (b) produce a valid
    eval, and (c) refuse a checkpoint from a different model."""
    import pytest

    from anomod.rca import train_rca

    ck = tmp_path / "ck"
    kwargs = dict(testbed="TT", model_name="gcn", train_seeds=range(2),
                  eval_seeds=range(100, 101), n_traces=12, save_every=10)
    train_rca(epochs=12, checkpoint_dir=ck, **kwargs)
    # saved at epoch 10 (periodic) and 12 (final); final wins
    import json
    assert json.loads((ck / "meta.json").read_text())["step"] == 12
    r = train_rca(epochs=16, checkpoint_dir=ck, resume=True, **kwargs)
    assert json.loads((ck / "meta.json").read_text())["step"] == 16
    assert 0.0 <= r.top1 <= 1.0
    # a no-op resume (target epochs already reached) must not rewind the
    # completed-epoch counter
    train_rca(epochs=12, checkpoint_dir=ck, resume=True, **kwargs)
    assert json.loads((ck / "meta.json").read_text())["step"] == 16
    # model / testbed mismatches are rejected
    with pytest.raises(ValueError, match="model"):
        train_rca(epochs=16, model_name="gat", testbed="TT",
                  train_seeds=range(2), eval_seeds=range(100, 101),
                  n_traces=12, checkpoint_dir=ck, resume=True)
    with pytest.raises(ValueError, match="testbed"):
        train_rca(epochs=16, model_name="gcn", testbed="SN",
                  train_seeds=range(2), eval_seeds=range(100, 101),
                  n_traces=12, checkpoint_dir=ck, resume=True)
    # resume with no checkpoint yet starts fresh instead of crashing
    # (always-pass-resume job scripts)
    fresh = tmp_path / "fresh"
    train_rca(epochs=2, checkpoint_dir=fresh, resume=True, **kwargs)
    assert json.loads((fresh / "meta.json").read_text())["step"] == 2


def test_checkpoint_versioned_publish(tmp_path):
    """Crash-safety layout: state lives in a v<step> dir named by meta.json
    (written last, atomically); superseded versions are GC'd; the legacy
    flat layout still restores."""
    import json
    import pickle

    import numpy as np

    from anomod.utils.checkpoint import (has_checkpoint, restore_train_state,
                                         save_train_state)

    ck = tmp_path / "ck"
    assert not has_checkpoint(ck)
    params = {"w": np.arange(4, dtype=np.float32)}
    save_train_state(ck, params, {"m": np.zeros(4, np.float32)}, step=10)
    assert has_checkpoint(ck)
    meta = json.loads((ck / "meta.json").read_text())
    assert meta["version"] == "v10" and (ck / "v10").is_dir()
    save_train_state(ck, params, {"m": np.ones(4, np.float32)}, step=20)
    assert not (ck / "v10").exists()        # GC'd after publish
    p, o, step, _ = restore_train_state(ck)
    assert step == 20 and float(o["m"][0]) == 1.0
    # legacy flat layout (pre-versioning checkpoints) still restores
    legacy = tmp_path / "legacy"
    legacy.mkdir()
    with open(legacy / "state.pkl", "wb") as f:
        pickle.dump((params, {"m": np.full(4, 7.0, np.float32)}), f)
    (legacy / "meta.json").write_text(json.dumps({"step": 5}))
    p, o, step, _ = restore_train_state(legacy)
    assert step == 5 and float(o["m"][0]) == 7.0
    assert has_checkpoint(legacy)
    # a torn legacy checkpoint (meta written, state never landed) is NOT
    # restorable and must read as no-checkpoint so resume starts fresh
    torn = tmp_path / "torn"
    torn.mkdir()
    (torn / "meta.json").write_text(json.dumps({"step": 50}))
    assert not has_checkpoint(torn)
    # orbax state without its treedef companion is equally unrestorable
    torn2 = tmp_path / "torn2"
    (torn2 / "v9" / "state.orbax").mkdir(parents=True)
    (torn2 / "meta.json").write_text(json.dumps({"step": 9,
                                                 "version": "v9"}))
    assert not has_checkpoint(torn2)
