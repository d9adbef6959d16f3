"""RCA training/eval harness: GNNs trained on chaos fault labels.

Dataset: synthetic experiment corpora (many seeds per fault label — the
reference ships one run per label; seeds are the augmentation axis), features
relative to the same-seed normal baseline (exactly what an operator has: a
healthy profile of the same deployment).  Targets: the culprit service from
the chaos metadata (anomod.labels).  Eval: top-k hit-rate on held-out seeds,
the metric BASELINE.json ties to the numpy-baseline parity requirement.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from anomod import detect, labels as labels_mod, synth
from anomod.graph import build_service_graph
from anomod.rca_features import (edge_feature_block as _edge_feature_block,
                                 pad_edge_arrays,
                                 windowed_features as _windowed_features)
from anomod.replay import ReplayConfig


@dataclasses.dataclass
class RCASample:
    experiment: str
    x: np.ndarray          # [S, F] baseline-relative features
    x_t: np.ndarray        # [S, W, Ft] windowed temporal features
    adj: np.ndarray        # [S, S] call counts
    edge_src: np.ndarray   # [E_max] int32 (padded)
    edge_dst: np.ndarray   # [E_max] int32
    edge_mask: np.ndarray  # [E_max] bool
    target: int            # culprit service index (-1 if none)
    is_anomaly: bool
    #: [E_max, W, 4] baseline-relative PER-EDGE temporal features aligned
    #: with edge_src/edge_dst (built when edge_features=True) — the
    #: line-graph model's token inputs; None otherwise
    edge_x: Optional[np.ndarray] = None


# _windowed_features / _edge_feature_block moved to anomod.rca_features
# (ONE definition shared with the online serve-tick RCA plane,
# anomod.serve.rca; the underscore aliases keep this module's historical
# names importable).  tests/test_rca_features.py pins bit-exact parity
# between the offline batch path here and the online extraction.


def _edge_x_relative(exp_spans, services, g, cfg,
                     base_edge: Dict[tuple, np.ndarray]) -> np.ndarray:
    """Baseline-relative per-edge features: the normal run's edge set can
    differ, so rows align by (src, dst) pair; edges unseen in the
    baseline keep their raw values (their baseline is zero traffic)."""
    raw = _edge_feature_block(exp_spans, services, g, cfg)
    for i, (a, b) in enumerate(zip(g.edge_src, g.edge_dst)):
        base = base_edge.get((int(a), int(b)))
        if base is not None:
            raw[i] = raw[i] - base
    return raw


def _pick_confounders(label, services: Tuple[str, ...], seed: int,
                      n: int) -> Tuple[str, ...]:
    """Deterministic decoy services for one (label, seed): never the culprit."""
    cands = [s for s in services if s != label.target_service]
    rng = np.random.default_rng(synth._seed_for(label.experiment, 13) + seed)
    return tuple(rng.choice(cands, size=min(n, len(cands)), replace=False))


def experiment_stream(testbed: str, seed: int, n_traces: int = 80,
                      hard: Optional["synth.HardMode"] = None,
                      n_confounders: int = 0,
                      experiments: Optional[Sequence[str]] = None):
    """Yield ``(label, experiment)`` for every label of one seed — THE
    corpus definition for quality evaluation.  ``experiments`` filters by
    name BEFORE generation (a consumer-side filter would still pay the
    synthesis cost of every skipped bundle).

    This is the single builder consumed by both the learned-model dataset
    (:func:`build_dataset`) and the training-free baselines
    (anomod.quality._zscore_eval), so every cell of the quality table
    scores byte-identical experiment bundles; round 2's sweep regenerated
    the zscore corpora separately, which made the model-vs-baseline
    comparison cross-sample noise-coupled.

    Seeds are process-stable per (seed, experiment): Python's ``hash()`` is
    salted per interpreter, which would make every call produce different
    corpora across processes (synth._seed_for is the stable hash).
    """
    svc_list = synth.SN_SERVICES if testbed == "SN" else synth.TT_SERVICES
    services = tuple(svc_list)
    for label in labels_mod.labels_for_testbed(testbed):
        if experiments is not None and label.experiment not in experiments:
            continue
        mode = hard or synth.HardMode()
        if n_confounders and label.is_anomaly:
            mode = dataclasses.replace(
                mode, confounders=_pick_confounders(
                    label, services, seed, n_confounders))
        yield label, synth.generate_experiment(
            label, n_traces=n_traces, hard=mode,
            seed=seed * 1000 + synth._seed_for(label.experiment) % 997)


def build_dataset(testbed: str, seeds: Sequence[int], n_traces: int = 80,
                  n_windows: int = 8,
                  hard: Optional["synth.HardMode"] = None,
                  n_confounders: int = 0,
                  edge_features: bool = False
                  ) -> Tuple[List[RCASample], Tuple[str, ...]]:
    """One sample per (fault label, seed), features relative to the same-seed
    normal baseline.

    ``hard`` applies HardMode difficulty (severity/noise) to the FAULT
    experiments; the normal baseline stays easy (it is the healthy profile).
    ``n_confounders`` > 0 additionally plants that many per-(label, seed)
    decoy services into each fault experiment.  ``edge_features`` doubles
    the windowed block with per-service OUT-EDGE aggregates (opt-in: the
    canonical quality tables use node features; the edge-aware variant
    needs this channel to learn link-fault attribution).
    """
    svc_list = synth.SN_SERVICES if testbed == "SN" else synth.TT_SERVICES
    services = tuple(svc_list)
    cfg = ReplayConfig(n_services=len(services), n_windows=n_windows,
                       chunk_size=2048, window_us=300_000_000)
    samples: List[RCASample] = []
    e_max = 0
    raw: List[tuple] = []
    for seed in seeds:
        normal_label = next(l for l in labels_mod.labels_for_testbed(testbed)
                            if not l.is_anomaly)
        normal = synth.generate_experiment(normal_label, n_traces=n_traces,
                                           seed=seed * 1000)
        base_x = detect.extract_features(normal, services).x
        base_t = _windowed_features(normal.spans, services, cfg,
                                    edge_features=edge_features)
        base_edge: Dict[tuple, np.ndarray] = {}
        if edge_features:
            g_n = build_service_graph(normal.spans, services=services)
            nb = _edge_feature_block(normal.spans, services, g_n, cfg)
            base_edge = {(int(a), int(b)): nb[i] for i, (a, b) in
                         enumerate(zip(g_n.edge_src, g_n.edge_dst))}
        for label, exp in experiment_stream(testbed, seed, n_traces=n_traces,
                                            hard=hard,
                                            n_confounders=n_confounders):
            x = detect.extract_features(exp, services).x - base_x
            x_t = _windowed_features(exp.spans, services, cfg,
                                     edge_features=edge_features) - base_t
            g = build_service_graph(exp.spans, services=services)
            e_max = max(e_max, g.n_edges)
            target = (services.index(label.target_service)
                      if label.target_service in services else -1)
            ex = (_edge_x_relative(exp.spans, services, g, cfg, base_edge)
                  if edge_features else None)
            raw.append((label.experiment, x, x_t, g, target,
                        label.is_anomaly, ex))
    for name, x, x_t, g, target, is_anom, ex in raw:
        E = e_max
        src, dst, mask = pad_edge_arrays(g, E)
        if ex is not None:
            ex = np.pad(ex.astype(np.float32),
                        ((0, E - ex.shape[0]), (0, 0), (0, 0)))
        samples.append(RCASample(name, x.astype(np.float32), x_t, g.adj_counts,
                                 src, dst, mask, target, is_anom, edge_x=ex))
    return samples, services


def _stack(samples: List[RCASample]) -> Dict[str, np.ndarray]:
    out = {
        "x": np.stack([s.x for s in samples]),
        "x_t": np.stack([s.x_t for s in samples]),
        "adj": np.stack([s.adj for s in samples]).astype(np.float32),
        "edge_src": np.stack([s.edge_src for s in samples]),
        "edge_dst": np.stack([s.edge_dst for s in samples]),
        "edge_mask": np.stack([s.edge_mask for s in samples]),
        "target": np.array([s.target for s in samples], np.int32),
        "is_anomaly": np.array([s.is_anomaly for s in samples], np.float32),
    }
    if samples and samples[0].edge_x is not None:
        out["edge_x"] = np.stack([s.edge_x for s in samples])
    return out


def _apply_model(model_name: str, model, params, batch):
    import jax
    if model_name in ("gcn",):
        return jax.vmap(lambda x, a: model.apply(params, x, a))(
            batch["x"], batch["adj"])
    if model_name == "linegraph":
        if "edge_x" not in batch:
            raise ValueError("the linegraph model needs per-edge features "
                             "(build_dataset(edge_features=True) / quality "
                             "sweeps with edge_aware)")
        return jax.vmap(
            lambda x, xt, ex, s, d, m:
            model.apply(params, x, xt, ex, s, d, m))(
            batch["x"], batch["x_t"], batch["edge_x"], batch["edge_src"],
            batch["edge_dst"], batch["edge_mask"])
    if model_name in ("temporal", "lru", "transformer", "moe"):
        import jax.numpy as jnp
        # fuse static multimodal features (logs etc.) into every window
        W = batch["x_t"].shape[2]
        x_full = jnp.concatenate(
            [batch["x_t"],
             jnp.repeat(batch["x"][:, :, None, :], W, axis=2)], axis=-1)
        return jax.vmap(lambda x, a: model.apply(params, x, a))(
            x_full, batch["adj"])
    return jax.vmap(lambda x, s, d, m: model.apply(params, x, s, d, m))(
        batch["x"], batch["edge_src"], batch["edge_dst"], batch["edge_mask"])


def init_params(model_name: str, model, sample0: Dict[str, np.ndarray], rng):
    """Per-model-family parameter init (single source for train_rca, the
    distributed train steps, and the quality sweep)."""
    if model_name == "gcn":
        return model.init(rng, sample0["x"], sample0["adj"])
    if model_name == "linegraph":
        return model.init(rng, sample0["x"], sample0["x_t"],
                          sample0["edge_x"], sample0["edge_src"],
                          sample0["edge_dst"], sample0["edge_mask"])
    if model_name in ("temporal", "lru", "transformer", "moe"):
        W = sample0["x_t"].shape[1]
        fused = np.concatenate(
            [sample0["x_t"],
             np.repeat(sample0["x"][:, None, :], W, axis=1)], axis=-1)
        return model.init(rng, fused, sample0["adj"])
    return model.init(rng, sample0["x"], sample0["edge_src"],
                      sample0["edge_dst"], sample0["edge_mask"])


def standardize_features(train: Dict[str, np.ndarray],
                         evals: Sequence[Dict[str, np.ndarray]]) -> None:
    """Standardize x/x_t (and edge_x when present) on train statistics,
    in place (shared with eval)."""
    for key in ("x", "x_t", "edge_x"):
        if key not in train:
            continue
        axes = tuple(range(train[key].ndim - 1))  # all but the feature axis
        mu = train[key].mean(axis=axes, keepdims=True)
        sd = train[key].std(axis=axes, keepdims=True) + 1e-6
        train[key] = (train[key] - mu) / sd
        for ev in evals:
            if key in ev:
                ev[key] = (ev[key] - mu) / sd


def topk_eval(scores: np.ndarray,
              batch: Dict[str, np.ndarray]) -> Tuple[float, float, float, int]:
    """(top1, top3, detection_auc, n_rca) from [B, S] scores vs labels.
    AUC is rank-based (max score as the experiment-level statistic)."""
    tgt = batch["target"]
    rca_mask = tgt >= 0
    order = np.argsort(-scores, axis=-1)
    rank = np.array([np.where(order[i] == tgt[i])[0][0] if rca_mask[i] else -1
                     for i in range(len(tgt))])
    top1 = float((rank[rca_mask] == 0).mean()) if rca_mask.any() else 0.0
    top3 = float((rank[rca_mask] < 3).mean()) if rca_mask.any() else 0.0
    det = scores.max(axis=-1)
    y = batch["is_anomaly"]
    pos, neg = det[y > 0], det[y == 0]
    auc = float((pos[:, None] > neg[None, :]).mean()) \
        if len(neg) and len(pos) else 1.0
    return top1, top3, auc, int(rca_mask.sum())


def rca_loss(scores, batch):
    """Shared training objective: CE over culprit services (where a chaos
    label names one) + 0.3 × detection BCE on the max score.  Single source
    of truth for the local, dp×tp, and pipeline train steps."""
    import jax
    import jax.numpy as jnp
    import optax
    has_target = batch["target"] >= 0
    logp = jax.nn.log_softmax(scores, axis=-1)
    tgt = jnp.clip(batch["target"], 0, scores.shape[-1] - 1)
    ce = -jnp.take_along_axis(logp, tgt[:, None], axis=1)[:, 0]
    rca = jnp.sum(ce * has_target) / jnp.maximum(has_target.sum(), 1)
    det = optax.sigmoid_binary_cross_entropy(
        scores.max(axis=-1), batch["is_anomaly"]).mean()
    return rca + 0.3 * det


def make_model(model_name: str):
    from anomod.models import GAT, GCN, GraphSAGE, MoERCA, TemporalGCN
    from anomod.models.linegraph import LineGraphRCA
    from anomod.models.lru import TemporalLRU
    from anomod.models.transformer import TraceTransformer
    return {"gcn": GCN(), "gat": GAT(), "sage": GraphSAGE(),
            "temporal": TemporalGCN(), "lru": TemporalLRU(),
            "transformer": TraceTransformer(), "moe": MoERCA(),
            "linegraph": LineGraphRCA()}[model_name]


@dataclasses.dataclass
class TrainResult:
    model_name: str
    top1: float
    top3: float
    detection_auc: float
    n_eval: int
    params: object
    #: per-epoch training loss of THIS invocation (empty on a no-op resume)
    losses: Tuple[float, ...] = ()


def train_rca(testbed: str = "TT", model_name: str = "gcn",
              train_seeds: Sequence[int] = range(8),
              eval_seeds: Sequence[int] = range(100, 104),
              epochs: int = 150, lr: float = 3e-3,
              n_traces: int = 80, verbose: bool = False,
              checkpoint_dir=None, resume: bool = False,
              save_every: int = 50) -> TrainResult:
    """Train a GNN RCA scorer on chaos labels; report held-out top-k.

    ``checkpoint_dir`` persists params + opt_state + epoch counter
    (anomod.utils.checkpoint) every ``save_every`` epochs and at the end;
    with ``resume=True`` training continues from the saved epoch — the
    checkpoint/resume plane the reference lacks (SURVEY.md §5), wired into
    the training entry point so an interrupted run loses at most
    ``save_every`` epochs (``save_every <= 0`` = final save only)."""
    import jax
    import jax.numpy as jnp
    import optax

    # the edge-native model consumes the per-edge feature plane; every
    # other model keeps the lighter node-only dataset
    edge_features = model_name == "linegraph"
    train_samples, services = build_dataset(testbed, train_seeds, n_traces,
                                            edge_features=edge_features)
    eval_samples, _ = build_dataset(testbed, eval_seeds, n_traces,
                                    edge_features=edge_features)
    # pad eval edge arrays to the train E_max (or vice versa)
    E = max(train_samples[0].edge_src.shape[0], eval_samples[0].edge_src.shape[0])
    def repad(samples):
        for s in samples:
            cur = s.edge_src.shape[0]
            if cur < E:
                s.edge_src = np.pad(s.edge_src, (0, E - cur))
                s.edge_dst = np.pad(s.edge_dst, (0, E - cur))
                s.edge_mask = np.pad(s.edge_mask, (0, E - cur))
                if s.edge_x is not None:
                    s.edge_x = np.pad(s.edge_x,
                                      ((0, E - cur), (0, 0), (0, 0)))
    repad(train_samples); repad(eval_samples)
    train = _stack([s for s in train_samples])
    evalb = _stack(eval_samples)

    standardize_features(train, [evalb])

    model = make_model(model_name)
    rng = jax.random.PRNGKey(0)
    sample0 = {k: v[0] for k, v in train.items()}
    params = init_params(model_name, model, sample0, rng)

    tx = optax.adamw(lr, weight_decay=1e-4)
    opt_state = tx.init(params)

    def loss_fn(params, batch):
        scores = _apply_model(model_name, model, params, batch)  # [B, S]
        return rca_loss(scores, batch)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    start_ep = 0
    if checkpoint_dir is not None and resume:
        from anomod.utils.checkpoint import (has_checkpoint,
                                             restore_train_state)
        # no checkpoint yet = first attempt of an always-pass-resume job:
        # start fresh instead of crashing
        if has_checkpoint(checkpoint_dir):
            params, opt_state, start_ep, meta = \
                restore_train_state(checkpoint_dir)
            for key, want in (("model", model_name), ("testbed", testbed)):
                if meta.get(key) not in (None, want):
                    raise ValueError(
                        f"checkpoint at {checkpoint_dir} was trained with "
                        f"{key}={meta.get(key)!r}, not {want!r}")
            if verbose:
                print(f"resumed from epoch {start_ep}")
        elif verbose:
            print(f"no checkpoint at {checkpoint_dir} yet; starting fresh")

    def _save(completed: int):
        """Persist with step = number of COMPLETED epochs, so resume's
        range(start_ep, epochs) never re-applies a baked-in update."""
        if checkpoint_dir is not None:
            from anomod.utils.checkpoint import save_train_state
            save_train_state(checkpoint_dir, params, opt_state, completed,
                             meta={"model": model_name, "testbed": testbed})

    batch = {k: jnp.asarray(v) for k, v in train.items()}
    last_saved = start_ep
    losses = []          # device scalars: read back once, after the loop
    for ep in range(start_ep, epochs):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(loss)
        if verbose and ep % 20 == 0:
            print(f"epoch {ep}: loss {float(loss):.4f}")
        if save_every > 0 and (ep + 1) % save_every == 0:
            _save(ep + 1)
            last_saved = ep + 1
    if start_ep < epochs and last_saved != epochs:
        # final save, unless the periodic save just wrote this exact state;
        # a no-op resume must not rewind the counter either
        _save(epochs)

    # eval
    scores = np.asarray(_apply_model(model_name, model, params,
                                     {k: jnp.asarray(v) for k, v in evalb.items()}))
    top1, top3, auc, n_eval = topk_eval(scores, evalb)
    return TrainResult(model_name=model_name, top1=top1, top3=top3,
                       detection_auc=auc, n_eval=n_eval, params=params,
                       losses=tuple(float(l) for l in losses))
