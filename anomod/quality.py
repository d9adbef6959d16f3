"""De-saturated quality benchmark: effect-size sweeps + degradation curves.

The full-strength synthetic faults (6-20x latency, 0.5-0.7 error rates —
synth._fault_effects) are trivially detectable: every model and the z-score
baseline hit top-1 = 1.0, so the benchmark can neither rank the model zoo nor
catch regressions.  This harness evaluates along three difficulty axes
(synth.HardMode):

  - severity: fault effects interpolated toward baseline (0.05 ≈ 1.25x
    latency / 2.5% errors — the regime where detectors genuinely differ);
  - noise: wider baseline distributions (lower SNR);
  - confounders: decoy services that also degrade, which the ranking must
    not confuse with the labeled culprit.

Models train ONCE on a mixed-severity corpus (full + mid + low) and are then
evaluated at each sweep point on held-out seeds — degradation curves measure
robustness, not per-point refitting.  The z-score detector (anomod.detect)
runs as the training-free baseline.  No reference counterpart: the reference
ships fixed-intensity chaos (chaos-experiments/*.yaml); the sweep fills the
taxonomy's intensity axis for evaluation purposes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from anomod import detect, synth
from anomod.rca import (_apply_model, _stack, build_dataset,
                        experiment_stream, init_params, make_model, rca_loss,
                        standardize_features, topk_eval)

#: The default sweep grid: full-strength down to the hard regime.
SEVERITIES = (1.0, 0.4, 0.2, 0.1, 0.05)

#: The de-saturated operating point used by the regression floor test and
#: docs/QUALITY.md "hard regime" table: mild effects + decoys + noise.
HARD_POINT = dict(severity=0.12, noise=0.5, n_confounders=2)


#: Named distribution shifts for the train-shift/eval-shift table: models
#: train on the default effect model ("in-dist") and are evaluated under
#: each shifted generator (synth.HardMode's effect_shape / fault_profile /
#: fault_locus axes).
SHIFTS: Dict[str, Dict[str, str]] = {
    "in-dist": {},
    "additive": {"effect_shape": "add"},
    "tail-only": {"effect_shape": "tail"},
    "bursty": {"fault_profile": "bursty"},
    "partial-window": {"fault_profile": "partial"},
    "edge-locus": {"fault_locus": "edge"},
}


@dataclasses.dataclass
class QualityPoint:
    model: str
    severity: float
    noise: float
    n_confounders: int
    top1: float
    top3: float
    detection_auc: float
    n_eval: int
    shift: str = "in-dist"


def _repad_edges(stacked: Dict[str, np.ndarray], e_max: int) -> None:
    cur = stacked["edge_src"].shape[1]
    if cur < e_max:
        pad = ((0, 0), (0, e_max - cur))
        for k in ("edge_src", "edge_dst"):
            stacked[k] = np.pad(stacked[k], pad)
        stacked["edge_mask"] = np.pad(stacked["edge_mask"], pad)
        if "edge_x" in stacked:
            stacked["edge_x"] = np.pad(
                stacked["edge_x"], pad + ((0, 0), (0, 0)))


def _train_model(model_name: str, train: Dict[str, np.ndarray],
                 epochs: int = 150, lr: float = 3e-3):
    import jax
    import jax.numpy as jnp
    import optax

    model = make_model(model_name)
    rng = jax.random.PRNGKey(0)
    sample0 = {k: v[0] for k, v in train.items()}
    params = init_params(model_name, model, sample0, rng)
    tx = optax.adamw(lr, weight_decay=1e-4)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p, b: rca_loss(_apply_model(model_name, model, p, b), b)
        )(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    batch = {k: jnp.asarray(v) for k, v in train.items()}
    for _ in range(epochs):
        params, opt_state, _ = step(params, opt_state, batch)
    return model, params


def _zscore_eval(testbed: str, seeds: Sequence[int],
                 hard: "synth.HardMode", n_confounders: int,
                 n_traces: int) -> Tuple[float, float, float, int]:
    """Training-free z-score detector over hard corpora (per-seed corpus
    evaluation via detect.evaluate_corpus, averaged).

    The experiments come from rca.experiment_stream — the SAME builder,
    arguments, and seeds the learned-model eval consumes through
    build_dataset — so every quality-table cell scores identical bundles
    (regenerating is cheap: generation is ~1% of sweep wall time, which
    training dominates).  The detection statistic is a rank-based AUC over
    experiment scores, same definition as rca.topk_eval, so the column is
    comparable across zscore and learned models.
    """
    top1s, top3s, aucs, n = [], [], [], 0
    for seed in seeds:
        exps = [exp for _, exp in experiment_stream(
            testbed, seed, n_traces=n_traces, hard=hard,
            n_confounders=n_confounders)]
        s = detect.evaluate_corpus(exps)
        top1s.append(s.top1)
        top3s.append(s.top3)
        pos = np.array([r.score for r in s.results if r.is_anomaly_true])
        neg = np.array([r.score for r in s.results if not r.is_anomaly_true])
        aucs.append(float((pos[:, None] > neg[None, :]).mean())
                    if len(pos) and len(neg) else 1.0)
        n += s.n_rca_cases
    return (float(np.mean(top1s)), float(np.mean(top3s)),
            float(np.mean(aucs)), n)


def _stream_eval(testbed: str, seeds: Sequence[int],
                 hard: "synth.HardMode", n_confounders: int,
                 n_traces: int) -> Tuple[float, float, float, int]:
    """Training-free multimodal STREAMING detector over the same corpora.

    Same contract as :func:`_zscore_eval` (identical bundles via
    rca.experiment_stream, rank-based AUC over per-experiment detection
    scores) so `stream` sits in the quality table cell-for-cell with the
    offline rows.  Note the sweep's corpora are much sparser than live
    traffic (n_traces=60 vs the streaming benchmark's 400) — this row
    measures the detector under the OFFLINE sweep's density, its hardest
    setting.
    """
    from anomod.stream import stream_experiment_multimodal
    top1s, top3s, aucs, n = [], [], [], 0
    for seed in seeds:
        hits1 = hits3 = cases = 0
        pos, neg = [], []
        for label, exp in experiment_stream(
                testbed, seed, n_traces=n_traces, hard=hard,
                n_confounders=n_confounders):
            det = stream_experiment_multimodal(exp)
            score = max((a.score for a in det.alerts), default=0.0)
            (pos if label.is_anomaly else neg).append(score)
            if label.is_anomaly and label.target_service:
                ranked = det.ranked_services()
                hits1 += bool(ranked) and ranked[0] == label.target_service
                hits3 += label.target_service in ranked[:3]
                cases += 1
        top1s.append(hits1 / cases if cases else 0.0)
        top3s.append(hits3 / cases if cases else 0.0)
        p, q = np.asarray(pos), np.asarray(neg)
        aucs.append(float((p[:, None] > q[None, :]).mean())
                    if len(p) and len(q) else 1.0)
        n += cases
    return (float(np.mean(top1s)), float(np.mean(top3s)),
            float(np.mean(aucs)), n)


def severity_sweep(testbed: str = "TT",
                   model_names: Sequence[str] = ("zscore", "gcn", "gat",
                                                 "sage", "temporal", "lru",
                                                 "transformer", "moe"),
                   severities: Sequence[float] = SEVERITIES,
                   train_seeds: Sequence[int] = range(6),
                   eval_seeds: Sequence[int] = range(100, 103),
                   n_traces: int = 60, epochs: int = 120,
                   noise: float = 0.5, n_confounders: int = 2,
                   verbose: bool = False) -> List[QualityPoint]:
    """Degradation curves: train once on mixed severity, eval per point.

    Every eval point uses noise + confounders (the hard axes are on by
    default); severity is the swept axis.  Returns one QualityPoint per
    (model, severity).
    """
    eval_modes = {sev: synth.HardMode(severity=sev, noise=noise)
                  for sev in severities}
    cells = _eval_grid(testbed, model_names, eval_modes, train_seeds,
                       eval_seeds, n_traces, epochs, noise, n_confounders,
                       verbose)
    return [QualityPoint(name, sev, noise, n_confounders, *cell)
            for (name, sev), cell in cells.items()]


def shift_sweep(testbed: str = "TT",
                model_names: Sequence[str] = ("zscore", "gcn", "gat",
                                              "sage", "temporal", "lru",
                                              "transformer", "moe"),
                shifts: Sequence[str] = tuple(SHIFTS),
                severity: float = 0.3,
                train_seeds: Sequence[int] = range(6),
                eval_seeds: Sequence[int] = range(100, 103),
                n_traces: int = 60, epochs: int = 120,
                noise: float = 0.5, n_confounders: int = 2,
                verbose: bool = False,
                edge_aware: bool = False) -> List[QualityPoint]:
    """Train-shift/eval-shift table (round-2 weak #4): models train ONCE on
    the default effect model (the same mixed-severity corpus as
    severity_sweep) and are evaluated under each shifted generator in
    :data:`SHIFTS` at one fixed severity.  A ranking that only holds
    in-distribution is a statement about the generator; this sweep shows
    which model ordering survives effect-shape, fault-timing, and
    fault-locus shift.

    ``edge_aware``: opt-in variant — out-edge feature blocks plus a
    node+edge mixed-locus training corpus, the supervised counterpart of
    the streaming out-edge plane.  The canonical table keeps node
    features and node-locus training (the honest shift premise); this
    variant answers "CAN the models attribute link faults when given the
    evidence channel and training exposure"."""
    eval_modes = {name: synth.HardMode(severity=severity, noise=noise,
                                       **SHIFTS[name])
                  for name in shifts}
    cells = _eval_grid(testbed, model_names, eval_modes, train_seeds,
                       eval_seeds, n_traces, epochs, noise, n_confounders,
                       verbose, edge_features=edge_aware,
                       train_loci=("node", "edge") if edge_aware
                       else ("node",))
    return [QualityPoint(name, severity, noise, n_confounders, *cell,
                         shift=shift)
            for (name, shift), cell in cells.items()]


def _eval_grid(testbed, model_names, eval_modes: Dict[object, "synth.HardMode"],
               train_seeds, eval_seeds, n_traces, epochs, noise,
               n_confounders, verbose=False, edge_features=False,
               train_loci=("node",)):
    """Shared sweep engine: one unshifted mixed-severity training pass,
    then every model evaluated on every eval-mode corpus.  Returns
    {(model, mode_key): (top1, top3, auc, n_eval)}; corpora per cell are
    identical across models (rca.experiment_stream via build_dataset).

    ``edge_features`` / ``train_loci`` configure the EDGE-AWARE variant:
    out-edge feature blocks plus a training mixture that includes
    edge-locus corpora — without both, link-fault attribution is
    architecturally outside the models' evidence (training on node
    faults alone leaves the out-edge channel with nothing to learn
    from).  The canonical tables keep the defaults."""
    # zscore and stream are training-free rows — only the learned models
    # need the mixed-severity training corpus and eval batches
    needs_training = any(name not in ("zscore", "stream")
                         for name in model_names)
    train = None
    if needs_training:
        # mixed-severity training corpus: full + mid + low thirds of the seeds
        thirds = np.array_split(np.asarray(list(train_seeds)), 3)
        train_parts = []
        for sev, part in zip((1.0, 0.4, 0.15), thirds):
            if len(part) == 0:
                continue
            for locus in train_loci:
                samples, services = build_dataset(
                    testbed, [int(s) for s in part], n_traces=n_traces,
                    hard=synth.HardMode(severity=sev, noise=noise,
                                        fault_locus=locus),
                    n_confounders=n_confounders,
                    edge_features=edge_features)
                train_parts.append(_stack(samples))
        e_max = max(p["edge_src"].shape[1] for p in train_parts)
        for p in train_parts:
            _repad_edges(p, e_max)
        train = {k: np.concatenate([p[k] for p in train_parts])
                 for k in train_parts[0]}

        # eval batches per mode (held-out seeds; the zscore path regenerates
        # the identical corpora via experiment_stream, so nothing here is
        # needed for a zscore-only sweep)
        eval_batches: Dict[object, Dict[str, np.ndarray]] = {}
        for key, mode in eval_modes.items():
            samples, _ = build_dataset(testbed, eval_seeds, n_traces=n_traces,
                                       hard=mode, n_confounders=n_confounders,
                                       edge_features=edge_features)
            ev = _stack(samples)
            e_max = max(e_max, ev["edge_src"].shape[1])
            eval_batches[key] = ev
        _repad_edges(train, e_max)
        for ev in eval_batches.values():
            _repad_edges(ev, e_max)
        standardize_features(train, list(eval_batches.values()))

    def _train_and_eval(name):
        """One model's train + full eval row (host-input → host-output, so a
        backend failover can redo it wholesale: corpora and finished cells
        live in numpy, only params/compiled fns die with the device)."""
        import jax.numpy as jnp
        row = {}
        model, params = _train_model(name, train, epochs=epochs)
        for key in eval_modes:
            ev = eval_batches[key]
            scores = np.asarray(_apply_model(
                name, model, params,
                {k: jnp.asarray(v) for k, v in ev.items()}))
            row[(name, key)] = topk_eval(scores, ev)
        return row

    cells: Dict[Tuple[str, object], Tuple[float, float, float, int]] = {}
    for name in model_names:
        if name in ("zscore", "stream"):
            ev_fn = _zscore_eval if name == "zscore" else _stream_eval
            for key, mode in eval_modes.items():
                cells[(name, key)] = ev_fn(
                    testbed, eval_seeds, mode, n_confounders, n_traces)
                if verbose:
                    print(f"{name} {key}: top1={cells[(name, key)][0]:.2f}")
            continue
        row = _train_and_eval(name)
        cells.update(row)
        if verbose:
            for (n, key), cell in row.items():
                print(f"{n} {key}: top1={cell[0]:.2f}")
    return cells


def render_shift_markdown(points: Sequence[QualityPoint]) -> str:
    """Train-shift/eval-shift table: one row per model, one top1 column per
    shifted generator (training is always in-distribution)."""
    shifts = list(dict.fromkeys(p.shift for p in points))
    models: Dict[str, Dict[str, QualityPoint]] = {}
    for p in points:
        models.setdefault(p.model, {})[p.shift] = p
    head = "| model | " + " | ".join(f"top1 {s}" for s in shifts) + " |"
    rows = [head, "|" + "---|" * (1 + len(shifts))]
    for name, by_shift in models.items():
        cells = " | ".join(f"{by_shift[s].top1:.2f}" if s in by_shift else "-"
                           for s in shifts)
        rows.append(f"| {name} | {cells} |")
    return "\n".join(rows)


def render_markdown(points: Sequence[QualityPoint]) -> str:
    """Degradation-curve table: one row per model, one column per severity."""
    severities = sorted({p.severity for p in points}, reverse=True)
    models: Dict[str, Dict[float, QualityPoint]] = {}
    for p in points:
        models.setdefault(p.model, {})[p.severity] = p
    head = "| model | " + " | ".join(f"top1@{s:g}" for s in severities) + \
        " | " + " | ".join(f"top3@{s:g}" for s in severities) + " |"
    sep = "|" + "---|" * (1 + 2 * len(severities))
    rows = [head, sep]
    for name, by_sev in models.items():
        t1 = " | ".join(f"{by_sev[s].top1:.2f}" if s in by_sev else "-"
                        for s in severities)
        t3 = " | ".join(f"{by_sev[s].top3:.2f}" if s in by_sev else "-"
                        for s in severities)
        rows.append(f"| {name} | {t1} | {t3} |")
    return "\n".join(rows)
