"""Non-interactive CLI — the counterpart of the reference's numbered menus
(automated_multimodal_collection.sh:845-888, run_all_experiments.sh:601-638)
as flags instead of prompts.

Subcommands grow with the framework; `list` and `synth` are available from
day one so every experiment the reference menus offer is addressable by name.
"""

from __future__ import annotations

import argparse
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    """Every sub-command and flag of ``anomod`` (tests/test_docs.py parses
    the documents' command lines with it)."""
    parser = argparse.ArgumentParser(
        prog="anomod",
        description="TPU-native anomaly-detection & RCA framework (AnoMod capabilities)")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_list = sub.add_parser("list", help="list experiments + fault labels")
    p_list.add_argument("--testbed", choices=["SN", "TT"], default=None)

    p_synth = sub.add_parser("synth", help="generate a synthetic experiment summary")
    p_synth.add_argument("experiment")
    p_synth.add_argument("--traces", type=int, default=100)

    p_detect = sub.add_parser(
        "detect", help="run the z-score detector + RCA ranking over a corpus")
    p_detect.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    p_detect.add_argument("--backend", choices=["cpu", "jax"], default="cpu")
    p_detect.add_argument("--traces", type=int, default=100)
    p_detect.add_argument("--from-data", action="store_true",
                          help="load from the data root (LFS stubs -> synth)")

    p_rca = sub.add_parser("rca", help="train a GNN RCA model on chaos labels")
    p_rca.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    p_rca.add_argument("--model",
                       choices=["gcn", "gat", "sage", "temporal", "lru",
                                "transformer", "moe", "linegraph"],
                       default="gcn")
    p_rca.add_argument("--epochs", type=int, default=300)
    p_rca.add_argument("--train-seeds", type=int, default=6)
    p_rca.add_argument("--eval-seeds", type=int, default=2)
    p_rca.add_argument("--checkpoint-dir", default=None,
                       help="persist params/opt_state every 50 epochs "
                            "(orbax, pickle fallback)")
    p_rca.add_argument("--resume", action="store_true",
                       help="continue from the epoch saved in "
                            "--checkpoint-dir")

    p_camp = sub.add_parser(
        "campaign", help="run the full 13-experiment collection campaign "
        "and archive a reference-shaped dataset tree")
    p_camp.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    p_camp.add_argument("--out", required=True)
    p_camp.add_argument("--traces", type=int, default=200)
    p_camp.add_argument("--experiments", nargs="*", default=None)

    p_coll = sub.add_parser(
        "collect", help="live-transport collection: pull from a running "
        "Prometheus / Jaeger / SkyWalking / Elasticsearch endpoint "
        "(anomod.io.live) or through kubectl/docker exec transports "
        "(anomod.io.live_exec) and write loader-compatible artifacts")
    p_coll.add_argument("kind", choices=["prometheus", "jaeger",
                                         "skywalking", "es", "kube-logs",
                                         "docker-logs", "jacoco", "gcov"])
    p_coll.add_argument("--url",
                        help="base URL (prometheus/jaeger/es) or the "
                             "GraphQL endpoint (skywalking); unused by "
                             "the exec transports")
    p_coll.add_argument("--namespace", default="default",
                        help="kube-logs/jacoco: kubernetes namespace")
    p_coll.add_argument("--tail", type=int, default=1000,
                        help="kube-logs: lines per pod")
    p_coll.add_argument("--since", default=None,
                        help="docker-logs: docker logs --since window "
                             "(default: full history, the collect_log.sh "
                             "default)")
    p_coll.add_argument("--report-dir", default=None,
                        help="jacoco: coverage_report output tree "
                             "(default: <out>/../coverage_report)")
    p_coll.add_argument("--mount-root", default="./coverage-reports",
                        help="gcov: the compose-mounted coverage-reports "
                             "dir the in-container collect scripts write "
                             "into (collect_all_data.sh:535)")
    p_coll.add_argument("--out", required=True,
                        help="output dir (prometheus) or artifact file "
                             "path (jaeger/skywalking/es)")
    p_coll.add_argument("--testbed", choices=["SN", "TT"], default="SN",
                        help="prometheus only: SN = per-query CSV dir from "
                             "the SN catalog; TT = one long CSV from the "
                             "TT catalog")
    p_coll.add_argument("--hours-back", type=float, default=1.0)
    p_coll.add_argument("--step", default="15s",
                        help="prometheus query_range step")
    p_coll.add_argument("--limit", type=int, default=1000,
                        help="jaeger: traces per service; skywalking: "
                             "total trace budget; es: segment budget")
    p_coll.add_argument("--experiment", default="live",
                        help="skywalking: experiment name stamped into "
                             "the artifact metadata; gcov: the "
                             "EXPERIMENT_BASE_NAME forwarded to the "
                             "in-container collect scripts")
    p_coll.add_argument("--timeout", type=float, default=30.0)
    p_coll.add_argument("--retries", type=int, default=3)

    p_gold = sub.add_parser(
        "golden", help="golden run over the REAL reference dataset trees: "
        "loadability census + coverage-modality detection on the non-LFS "
        "artifacts (anomod.golden)")
    p_gold.add_argument("--markdown", action="store_true",
                        help="emit the docs/GOLDEN_REPORT.md body instead "
                             "of JSON")

    p_ing = sub.add_parser(
        "ingest", help="ingest-cache management (anomod.io.cache): warm the "
        "content-addressed corpus cache ahead of a run, report its "
        "state, or clear it")
    p_ing.add_argument("--warm-cache", action="store_true",
                       help="load the full corpus (and the chip_smoke.py "
                            "span corpus) through the cache so later runs "
                            "are warm")
    p_ing.add_argument("--testbed", choices=["SN", "TT", "both"],
                       default="TT")
    p_ing.add_argument("--traces", type=int, default=200,
                       help="n_synth_traces for the corpus loaders")
    p_ing.add_argument("--bench-traces", type=int, default=2_000,
                       help="n_traces of the chip_smoke.py replay corpus "
                            "to warm (0 skips it; 2000 is its size)")
    p_ing.add_argument("--workers", type=int, default=None,
                       help="process-pool size for the corpus load "
                            "(default: ANOMOD_INGEST_WORKERS)")
    p_ing.add_argument("--cache-dir", default=None,
                       help="override ANOMOD_CACHE_DIR for this invocation")
    p_ing.add_argument("--data-root", default=None,
                       help="override ANOMOD_DATA_ROOT for this invocation")
    p_ing.add_argument("--clear", action="store_true",
                       help="delete every cache entry first")

    p_val = sub.add_parser("validate", help="data-quality validation report "
                           "over a corpus (reference-style embedded checks)")
    p_val.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    p_val.add_argument("--traces", type=int, default=60)
    p_val.add_argument("--from-data", action="store_true")

    p_lint = sub.add_parser(
        "lint", help="contract-checking static analysis "
        "(anomod.analysis): AST lint of the determinism / env-contract "
        "/ seam / lock contracts plus the parity-surface audit "
        "(ServeReport fields and flight-record keys vs their declared "
        "variant lists).  Pure stdlib ast — never touches the backend. "
        "Catalog: docs/CONTRACTS.md")
    p_lint.add_argument("--root", default=None,
                        help="repo root to scan (default: this checkout)")
    p_lint.add_argument("--json", action="store_true",
                        help="machine output only (one JSON document, "
                             "findings inlined)")
    p_lint.add_argument("--baseline", default=None,
                        help="baseline file (default: "
                             "scripts/lint_baseline.json)")
    p_lint.add_argument("--update-baseline", action="store_true",
                        help="rewrite the baseline to exactly the "
                             "current findings (the ratchet only "
                             "shrinks unless you run this)")
    p_lint.add_argument("--no-parity", action="store_true",
                        help="skip the parity-surface audit (AST rule "
                             "families only)")
    p_lint.add_argument("--show-suppressed", action="store_true",
                        help="also list suppressed findings with their "
                             "reasons")
    p_lint.add_argument("--rules", action="store_true",
                        help="print the rule catalog and exit")

    p_chaos = sub.add_parser(
        "chaos", help="render the fault-injection plan for an experiment "
        "(Chaos Mesh CRD YAML / ChaosBlade argv / docker argv)")
    p_chaos.add_argument("experiment")
    p_chaos.add_argument("--format", choices=["yaml", "json"], default="yaml")

    p_scen = sub.add_parser(
        "scenario", help="drive the TT user-journey workload against the "
        "synthetic SUT (optionally under an injected fault)")
    p_scen.add_argument("--iterations", type=int, default=1)
    p_scen.add_argument("--seed", type=int, default=0)
    p_scen.add_argument("--chaos", default=None,
                        help="experiment name to inject during the run")

    p_deploy = sub.add_parser(
        "deploy", help="render the deployment plan (helm/kubectl action "
        "list for TT, compose lifecycle for SN)")
    p_deploy.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    # the deploy.sh argument surface, as real flags
    p_deploy.add_argument("--all", action="store_true", dest="deploy_all")
    p_deploy.add_argument("--independent-db", action="store_true")
    p_deploy.add_argument("--with-monitoring", action="store_true")
    p_deploy.add_argument("--with-tracing", action="store_true")
    p_deploy.add_argument("--down", action="store_true",
                          help="SN only: render the teardown instead")
    p_deploy.add_argument("--secrets", action="store_true",
                          help="TT only: print the 27 per-service DB secrets")

    p_mon = sub.add_parser(
        "monitor", help="SN API-response monitor over the synthetic SUT "
        "(active: 12 wrk2-api endpoints; passive: GET-only fallback)")
    p_mon.add_argument("--mode", choices=["active", "passive"],
                       default="active")
    p_mon.add_argument("--cycles", type=int, default=10)
    p_mon.add_argument("--seed", type=int, default=0)
    p_mon.add_argument("--chaos", default=None,
                       help="experiment name to inject during the capture")
    p_mon.add_argument("--out", default=None,
                       help="materialize the api_responses artifact family")
    p_mon.add_argument("--wrk2-requests", type=int, default=0,
                       help="interleave N wrk2 mixed-workload requests "
                            "(full compose content model) with the capture")

    p_logscan = sub.add_parser(
        "logscan", help="per-file log summary sweep over a directory "
        "(collect_log.sh summary pass; native thread-pool when built)")
    p_logscan.add_argument("dir")
    p_logscan.add_argument("--glob", default="**/*.log")

    p_replay = sub.add_parser("replay", help="measure span replay throughput")
    p_replay.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    p_replay.add_argument("--traces", type=int, default=2000)
    p_replay.add_argument("--replicate", type=int, default=1)
    p_replay.add_argument("--kernel",
                          choices=["xla", "pallas", "pallas-sorted", "numpy"],
                          default="xla",
                          help="aggregation path: XLA scan (default; runs "
                               "anywhere), the fused pallas kernel (the "
                               "TPU fast path; interpret-mode off-TPU), its "
                               "sorted-window variant (128-lane one-hot via "
                               "host pre-sort; single-chip only), or "
                               "the numpy cpu-backend engine (fastest on a "
                               "host core; single-chip only)")
    p_replay.add_argument("--percentiles", action="store_true",
                          help="also report corpus-wide p50/p95/p99 from the "
                               "per-segment t-digest plane (XLA build on "
                               "TPU, host build elsewhere; "
                               "ANOMOD_TDIGEST_ENGINE=pallas opts into the "
                               "Mosaic kernel)")
    p_replay.add_argument("--edge-percentiles", action="store_true",
                          help="also report the slowest call-graph edges by "
                               "p99 from the PER-EDGE t-digest plane "
                               "(caller->callee keyed segments; the "
                               "per-edge featurization view)")
    p_replay.add_argument("--devices", type=int, default=0,
                          help="shard the stream over an N-device 1-D mesh "
                               "(shard_map + psum merge over ICI) instead of "
                               "the single-chip path; requires >= N attached "
                               "devices (JAX_PLATFORMS=cpu + "
                               "JAX_NUM_CPU_DEVICES=N gives a virtual mesh). "
                               "--percentiles still computes its digest "
                               "plane in a separate single-chip pass")

    p_stream = sub.add_parser(
        "stream", help="online detection: replay an experiment's spans in "
        "arrival order through the incremental replay state and report the "
        "alert timeline + detection latency (streaming analog of `detect`)")
    p_stream.add_argument("experiment", nargs="?", default=None)
    p_stream.add_argument("--all", action="store_true",
                          help="run every experiment of --testbed and "
                               "report the taxonomy-wide quality table "
                               "(localization + detection latency); "
                               "writes a bench_runs/ provenance record")
    p_stream.add_argument("--testbed", choices=["SN", "TT"], default="TT",
                          help="with --all: which taxonomy to run; "
                               "single-experiment mode infers the testbed "
                               "from the name")
    p_stream.add_argument("--traces", type=int, default=400)
    p_stream.add_argument("--seed", type=int, default=0)
    p_stream.add_argument("--slice-seconds", type=float, default=60.0,
                          help="micro-batch width of the simulated feed")
    p_stream.add_argument("--threshold", type=float, default=4.0,
                          help="z-score alert threshold")
    p_stream.add_argument("--baseline-windows", type=int, default=8)
    p_stream.add_argument("--consecutive", type=int, default=1,
                          help="windows above threshold before alerting")
    p_stream.add_argument("--multimodal", action="store_true",
                          help="fuse the log/metric/api planes with the "
                               "span stream (streaming counterpart of the "
                               "offline five-modality detector)")
    p_stream.add_argument("--devices", type=int, default=0,
                          help="shard the streaming replay plane (incl. "
                               "the edge-attribution id space) over an "
                               "N-device mesh (JAX_PLATFORMS=cpu + "
                               "JAX_NUM_CPU_DEVICES=N gives a virtual mesh)")
    p_stream.add_argument("--severity", type=float, default=1.0,
                          help="de-saturate the fault effects "
                               "(synth.HardMode) — the streaming "
                               "degradation-curve knob")
    p_stream.add_argument("--noise", type=float, default=0.0,
                          help="widen baseline distributions (HardMode)")
    p_stream.add_argument("--confounders", type=int, default=0,
                          help="decoy services per experiment (--all only; "
                               "same corpus builder as the quality sweep)")
    p_stream.add_argument("--shift", default="in-dist",
                          choices=["in-dist", "additive", "tail-only",
                                   "bursty", "partial-window", "edge-locus"],
                          help="--all only: evaluate under a shifted "
                               "generator (quality.SHIFTS axes)")
    p_stream.add_argument("--from-data", action="store_true",
                          help="replay the experiment from the archived "
                               "dataset tree (io.dataset loaders; LFS "
                               "stubs -> synth) instead of generating — "
                               "single-experiment mode only")
    p_stream.add_argument("--no-edge-attribution", action="store_true",
                          help="disable the out-edge attribution plane "
                               "(default on): skips the per-push span-batch "
                               "duplication and the 3x replay-plane rows, "
                               "restoring pre-edge-plane throughput (and "
                               "spans_per_sec comparability with those "
                               "records) at the cost of edge-locus RCA")

    p_serve = sub.add_parser(
        "serve", help="multi-tenant serving plane: admission control + "
        "dynamic micro-batching + SLO-aware load shedding over the "
        "streaming detectors, driven by a seeded power-law tenant fleet "
        "on a deterministic virtual clock (anomod.serve)")
    p_serve.add_argument("--tenants", type=int, default=200)
    p_serve.add_argument("--services", type=int, default=8)
    p_serve.add_argument("--duration", type=float, default=120.0,
                         help="virtual seconds to serve")
    p_serve.add_argument("--tick", type=float, default=1.0,
                         help="virtual scheduler tick (seconds)")
    p_serve.add_argument("--capacity", type=float, default=20_000.0,
                         help="serving capacity in spans/sec")
    p_serve.add_argument("--overload", type=float, default=1.0,
                         help="offered load as a multiple of capacity "
                              "(2.0 = the shed regime)")
    p_serve.add_argument("--alpha", type=float, default=1.2,
                         help="power-law exponent of the tenant rate "
                              "distribution (0 = equal rates)")
    p_serve.add_argument("--seed", type=int, default=0)
    p_serve.add_argument("--window-seconds", type=float, default=5.0,
                         help="detector window width on the virtual clock")
    p_serve.add_argument("--baseline-windows", type=int, default=4)
    p_serve.add_argument("--threshold", type=float, default=4.0)
    p_serve.add_argument("--shards", type=int, default=None,
                         help="tenant-sharded engine workers (default: "
                              "ANOMOD_SERVE_SHARDS, 1 = the single-"
                              "threaded engine; N-shard output is "
                              "identical to 1-shard on the same seed)")
    p_serve.add_argument("--pipeline", type=int, default=None,
                         help="in-flight fused dispatches per shard "
                              "(default: ANOMOD_SERVE_PIPELINE; 1 = "
                              "synchronous, >1 = async double-buffered "
                              "staging — bit-identical at any depth)")
    p_serve.add_argument("--no-fuse", action="store_true",
                         help="disable tenant-fused (lane-stacked) "
                              "dispatch: one dispatch per tenant "
                              "micro-batch, as before ANOMOD_SERVE_FUSE")
    p_serve.add_argument("--lane-buckets", default=None,
                         help="comma-separated fused-dispatch lane "
                              "counts (default ANOMOD_SERVE_LANE_BUCKETS)")
    p_serve.add_argument("--buckets", default=None,
                         help="comma-separated micro-batch bucket widths "
                              "(default: ANOMOD_SERVE_BUCKETS)")
    p_serve.add_argument("--max-backlog", type=int, default=None,
                         help="global backlog bound in spans "
                              "(default: ANOMOD_SERVE_MAX_BACKLOG)")
    p_serve.add_argument("--fault-tenants", type=int, default=2,
                         help="tenants given a scripted latency fault at "
                              "mid-run (alert latency under load)")
    p_serve.add_argument("--rca", action="store_true",
                         help="online root-cause inference in the serve "
                              "tick: a firing detector queues GNN culprit "
                              "inference over the tenant's live service "
                              "graph (anomod.serve.rca; default: "
                              "ANOMOD_SERVE_RCA)")
    p_serve.add_argument("--seq-model", default=None, metavar="CONFIG.json",
                         help="score every served span as an event token "
                              "of its tenant's session with the latent-"
                              "attention routed-expert decoder this "
                              "configuration file describes "
                              "(anomod.serve.seqplane; default: off)")
    p_serve.add_argument("--state", choices=["auto", "host", "device"],
                         default=None,
                         help="tenant replay state residency: device = "
                              "shard-owned device pool, on-device scatter "
                              "fold + fused score gather (bit-identical); "
                              "host = the per-tenant numpy seam "
                              "(default: ANOMOD_SERVE_STATE, auto=device)")
    p_serve.add_argument("--no-native", action="store_true",
                         help="disable the GIL-free C++ lane staging for "
                              "this run: the interpreter fill, as before "
                              "ANOMOD_NATIVE (byte-identical output)")
    p_serve.add_argument("--async-commit", action="store_true",
                         help="deferred-commit tick: issue the fold/"
                              "score dispatches without waiting, run "
                              "the next tick's admission/drain/shed/SLO "
                              "under the in-flight XLA work, commit at "
                              "the next barrier — states/alerts/SLO/"
                              "shed and the canonical flight journal "
                              "byte-identical to the synchronous "
                              "engine (default: "
                              "ANOMOD_SERVE_ASYNC_COMMIT)")
    p_serve.add_argument("--no-async-commit", action="store_true",
                         help="force the synchronous tick (the parity "
                              "oracle) even when "
                              "ANOMOD_SERVE_ASYNC_COMMIT is on")
    p_serve.add_argument("--worker", choices=["thread", "process"],
                         default=None,
                         help="shard worker engine: thread = in-process "
                              "shard threads (the byte-parity oracle); "
                              "process = spawn-context worker processes "
                              "owning their shard's detectors/replays/"
                              "runner — escapes the GIL; states/alerts/"
                              "SLO/shed and the canonical flight journal "
                              "byte-identical to the thread engine "
                              "(default: ANOMOD_SERVE_WORKER)")
    p_serve.add_argument("--fold", choices=["dense", "sparse"],
                         default=None,
                         help="per-tick cross-shard registry barrier "
                              "fold: sparse = touched-key deltas "
                              "combined through a deterministic binary "
                              "fold tree; dense = full-walk snapshots "
                              "(the parity oracle) — scrape output "
                              "byte-identical either way (default: "
                              "ANOMOD_SERVE_FOLD)")
    p_serve.add_argument("--native-drain",
                         choices=["auto", "on", "off"], default=None,
                         help="columnar SFQ drain/shed engine for the "
                              "admission hot loop: auto = native C++ "
                              "kernels when the toolchain has them, "
                              "NumPy-columnar otherwise; off = the "
                              "Python heap loop (the byte-parity "
                              "oracle); on = require the native "
                              "kernels (default: "
                              "ANOMOD_SERVE_NATIVE_DRAIN)")
    p_serve.add_argument("--no-score", action="store_true",
                         help="replay-plane only (skip per-tenant window "
                              "scoring) — isolates the serving overhead")
    p_serve.add_argument("--chaos", default=None,
                         help="scripted serve-plane fault injection, "
                              "e.g. 'crash@5:shard=1;stall@8:ms=20' "
                              "(anomod.serve.chaos; default: "
                              "ANOMOD_SERVE_CHAOS, empty = off)")
    p_serve.add_argument("--ckpt-every", type=int, default=None,
                         help="shard-checkpoint cadence in ticks for "
                              "supervised no-score-gap recovery "
                              "(default: ANOMOD_SERVE_CKPT_EVERY; "
                              "0 disables supervision)")
    p_serve.add_argument("--policy", choices=["off", "auto", "script"],
                         default=None,
                         help="elastic scaling policy "
                              "(anomod.serve.policy): auto = signal-fed "
                              "autoscaler at every tick boundary, "
                              "script = fixed schedule from "
                              "--policy-script; scaling episodes are "
                              "seed-deterministic and leave tenant "
                              "states/alerts/SLO/shed byte-identical to "
                              "a static run (default: "
                              "ANOMOD_SERVE_POLICY)")
    p_serve.add_argument("--policy-script", default=None,
                         help="scaling schedule for --policy script, "
                              "e.g. 'up@10;rebalance@25:k=2;down@40' "
                              "(default: ANOMOD_SERVE_POLICY_SCRIPT)")
    p_serve.add_argument("--min-shards", type=int, default=None,
                         help="elastic scale-down floor (default: "
                              "ANOMOD_SERVE_POLICY_MIN_SHARDS)")
    p_serve.add_argument("--max-shards", type=int, default=None,
                         help="elastic scale-up ceiling (default: "
                              "ANOMOD_SERVE_POLICY_MAX_SHARDS; past it "
                              "sustained overload climbs the brownout "
                              "ladder)")
    p_serve.add_argument("--devices", type=int, default=0,
                         help="serve over an N-device mesh plane "
                              "(ShardedStreamReplay per tenant; "
                              "JAX_PLATFORMS=cpu + JAX_NUM_CPU_DEVICES=N "
                              "gives a virtual mesh)")
    p_serve.add_argument("--trace-out", default=None,
                         help="dump the engine's own Jaeger-shaped trace "
                              "(anomod.utils.tracing.Tracer)")
    p_serve.add_argument("--from-live", default=None, metavar="URL",
                         help="drive the tick from a LIVE Prometheus "
                              "text-exposition endpoint instead of the "
                              "synthetic fleet (anomod.serve.feed); "
                              "'self' starts the embedded /metrics "
                              "endpoint (anomod.obs.http) and scrapes "
                              "this process's OWN registry — the "
                              "dogfood closed loop")
    p_serve.add_argument("--live-replay", default=None, metavar="JOURNAL",
                         help="re-run a recorded live-feed wire journal "
                              "(ANOMOD_FEED_JOURNAL) through the replay "
                              "transport: byte-identical planes, no "
                              "network; the feed shape comes from the "
                              "journal header (--tenants/--services are "
                              "ignored)")
    p_serve.add_argument("--feed-lag", type=float, default=None,
                         help="live-feed wall->virtual lag budget in "
                              "seconds (default: ANOMOD_SERVE_FEED_LAG_S)")
    p_serve.add_argument("--feed-journal", default=None,
                         help="record the live feed's wire journal to "
                              "this path (default: ANOMOD_FEED_JOURNAL)")

    p_obs = sub.add_parser(
        "obs", help="self-scraping telemetry plane (anomod.obs): snapshot "
        "the metrics registry, export it (Prometheus text / the "
        "framework's own TT metric CSV), or score a self-scrape capture "
        "through the framework's own OnlineDetector stack")
    p_obs.add_argument("action", choices=["snapshot", "export", "score"])
    p_obs.add_argument("--from", dest="from_path", default=None,
                       help="score: TT-CSV self-scrape capture to load "
                            "(default: run the self-exercise and score "
                            "its own telemetry)")
    p_obs.add_argument("--out", default=None,
                       help="export: output file path (required)")
    p_obs.add_argument("--format", choices=["json", "prom", "tt-csv",
                                            "chrome", "jaeger"],
                       default=None,
                       help="snapshot: json (default) or prom; "
                            "export: tt-csv (default), prom, or the "
                            "self-exercise engine's own SPAN trace as "
                            "chrome (trace-event array, loads in "
                            "chrome://tracing / Perfetto) or jaeger")
    p_obs.add_argument("--serve-seconds", type=float, default=20.0,
                       help="virtual seconds of the seeded self-exercise "
                            "serve run that populates the registry")
    p_obs.add_argument("--tenants", type=int, default=24)
    p_obs.add_argument("--capacity", type=float, default=4000.0,
                       help="self-exercise serving capacity (spans/sec)")
    p_obs.add_argument("--seed", type=int, default=0)
    p_obs.add_argument("--window-seconds", type=float, default=5.0,
                       help="score: detector window width")
    p_obs.add_argument("--baseline-windows", type=int, default=4)
    p_obs.add_argument("--threshold", type=float, default=4.0)

    p_audit = sub.add_parser(
        "audit", help="black-box flight-recorder forensics (anomod.obs."
        "flight): `record` runs seeded traffic with the tick journal on "
        "and dumps it, `replay` re-executes a journal from its header's "
        "seed+config (optionally at a different shard count / pipeline "
        "depth / state residency — the determinism contracts under "
        "test), `diff` compares two journals tick-aligned and reports "
        "the first divergent tick and which plane (admission / dispatch "
        "/ fold / score / rca) diverged, exiting nonzero")
    p_audit.add_argument("action", choices=["record", "replay", "diff"])
    p_audit.add_argument("journals", nargs="*",
                         help="replay: the journal to re-execute; diff: "
                              "the two journals to compare")
    p_audit.add_argument("--out", default=None,
                         help="record/replay: journal output path "
                              "(required)")
    # record-run shape flags default to None so the replay/diff branches
    # can tell "passed" from "absent" without a second copy of the
    # defaults; the record branch resolves the real defaults below
    p_audit.add_argument("--tenants", type=int, default=None,
                         help="record only (default 24)")
    p_audit.add_argument("--services", type=int, default=None,
                         help="record only (default 8)")
    p_audit.add_argument("--duration", type=float, default=None,
                         help="record: virtual seconds to serve "
                              "(default 30)")
    p_audit.add_argument("--tick", type=float, default=None,
                         help="record only (default 0.5)")
    p_audit.add_argument("--capacity", type=float, default=None,
                         help="record only (default 4000)")
    p_audit.add_argument("--overload", type=float, default=None,
                         help="record only (default 1.5)")
    p_audit.add_argument("--seed", type=int, default=None,
                         help="record only (default 0)")
    p_audit.add_argument("--window-seconds", type=float, default=None,
                         help="record only (default 5.0)")
    p_audit.add_argument("--baseline-windows", type=int, default=None,
                         help="record only (default 2)")
    p_audit.add_argument("--threshold", type=float, default=None,
                         help="record only (default 4.0)")
    p_audit.add_argument("--fault-tenants", type=int, default=None,
                         help="record only (default 1)")
    p_audit.add_argument("--rca", action="store_true",
                         help="record: journal the online-RCA verdict "
                              "plane too")
    p_audit.add_argument("--digest-every", type=int, default=None,
                         help="record: tenant-state digest cadence in "
                              "ticks (default: ANOMOD_FLIGHT_DIGEST_"
                              "EVERY)")
    p_audit.add_argument("--shards", type=int, default=None,
                         help="record: engine shard count; replay: "
                              "OVERRIDE the recorded shard count (the "
                              "N-way-pinned-to-1-way forensic replay)")
    p_audit.add_argument("--pipeline", type=int, default=None,
                         help="record: dispatch pipeline depth; replay: "
                              "override the recorded depth")
    p_audit.add_argument("--state", choices=["auto", "host", "device"],
                         default=None,
                         help="record: tenant-state residency; replay: "
                              "override the recorded residency")

    p_cen = sub.add_parser(
        "census", help="fleet census observatory (anomod.obs.census): "
        "`record` runs seeded traffic with the deterministic resident-"
        "bytes + hot-set/Zipf census on and dumps the census timeline, "
        "`probe` sweeps registered-fleet sizes at fixed hot traffic and "
        "fits the O(registered) per-tick wall and resident-bytes "
        "slopes (the baseline the million-tenant tiering refactor must "
        "flatten), and `diff` compares two captures' census "
        "blocks — byte counts exact (deterministic, so every delta is "
        "real), wall slopes within the explicit box noise tolerance — "
        "exiting nonzero on a regression: the tiering PR's "
        "before/after judge")
    p_cen.add_argument("action", choices=["record", "probe", "diff"])
    p_cen.add_argument("paths", nargs="*",
                       help="diff: the two capture JSONs (A then B)")
    p_cen.add_argument("--out", default=None,
                       help="record: census-timeline JSON output path "
                            "(required); probe: optional sweep output "
                            "path")
    # every shape flag defaults to None so the other actions can tell
    # "passed" from "absent" and refuse it loudly (the audit-branch
    # discipline: a silently ignored flag makes the user believe they
    # parameterized the run); each action resolves its real defaults
    p_cen.add_argument("--tenants", type=int, default=None,
                       help="record only (default 24)")
    p_cen.add_argument("--duration", type=float, default=None,
                       help="record: virtual seconds to serve "
                            "(default 30)")
    p_cen.add_argument("--tick", type=float, default=None,
                       help="record only (default 0.5)")
    p_cen.add_argument("--capacity", type=float, default=None,
                       help="record only (default 4000)")
    p_cen.add_argument("--overload", type=float, default=None,
                       help="record only (default 1.5)")
    p_cen.add_argument("--seed", type=int, default=None,
                       help="record/probe (default 0)")
    p_cen.add_argument("--shards", type=int, default=None,
                       help="record: engine shard count (default: "
                            "ANOMOD_SERVE_SHARDS)")
    p_cen.add_argument("--every", type=int, default=None,
                       help="record: census cadence in ticks "
                            "(default: ANOMOD_CENSUS_EVERY)")
    p_cen.add_argument("--sizes", default=None,
                       help="probe: comma-separated registered-fleet "
                            "sizes (default: ANOMOD_CENSUS_SWEEP)")
    p_cen.add_argument("--hot", type=int, default=None,
                       help="probe: fixed hot-traffic tenant count "
                            "(default 1000)")
    p_cen.add_argument("--ticks", type=int, default=None,
                       help="probe: measured ticks per sweep size "
                            "(default 8)")
    p_cen.add_argument("--tolerance", type=float, default=None,
                       help="diff: wall-slope noise tolerance the B/A "
                            "ratio must clear (default 0.35)")

    p_q = sub.add_parser(
        "quality", help="de-saturated quality sweep: degradation curves over "
        "fault severity with noise + confounders (HardMode)")
    p_q.add_argument("--testbed", choices=["SN", "TT"], default="TT")
    p_q.add_argument("--models", nargs="*",
                     default=["zscore", "gcn", "gat", "sage", "temporal",
                              "lru", "transformer", "moe"])
    p_q.add_argument("--severities", nargs="*", type=float,
                     default=[1.0, 0.4, 0.2, 0.1, 0.05])
    p_q.add_argument("--train-seeds", type=int, default=6)
    p_q.add_argument("--eval-seeds", type=int, default=3)
    p_q.add_argument("--traces", type=int, default=60)
    p_q.add_argument("--epochs", type=int, default=120)
    p_q.add_argument("--noise", type=float, default=0.5)
    p_q.add_argument("--confounders", type=int, default=2)
    p_q.add_argument("--sweep", choices=["severity", "shift"],
                     default="severity",
                     help="severity: degradation curves; shift: train on the "
                          "default effect model, eval under shifted "
                          "generators (effect shape / fault timing / locus)")
    p_q.add_argument("--shift-severity", type=float, default=0.3,
                     help="fixed fault severity for the shift sweep")
    p_q.add_argument("--edge-aware", action="store_true",
                     help="--sweep shift only: out-edge feature blocks + "
                          "node+edge mixed-locus training (the supervised "
                          "counterpart of the streaming out-edge plane; "
                          "the canonical table keeps node features and "
                          "node-locus training)")
    p_q.add_argument("--json", action="store_true",
                     help="emit one JSON object per sweep point")
    return parser


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)

    # the subcommands that compile: place the persistent compilation
    # cache before any of them touches the backend (the rest stay
    # jax-free and start in milliseconds)
    if args.cmd in ("detect", "stream", "obs", "serve", "census",
                    "audit", "quality", "rca", "replay"):
        from anomod.utils.platform import enable_compile_cache
        enable_compile_cache()

    if args.cmd == "lint":
        import dataclasses as _dc

        from anomod.analysis import lint as _lint
        if args.rules:
            print(json.dumps({rid: _dc.asdict(r) for rid, r
                              in sorted(_lint.RULES.items())}, indent=2))
            return 0
        root = _lint.repo_root() if args.root is None else args.root
        bpath = args.baseline or _lint.baseline_path(root)
        doc, findings = _lint.run_gate(
            root, include_parity=not args.no_parity,
            baseline_file=bpath)
        if args.update_baseline:
            _lint.save_baseline(
                bpath, [f.key for f in findings if not f.suppressed])
            doc, findings = _lint.run_gate(
                root, include_parity=not args.no_parity,
                baseline_file=bpath)
        if args.json:
            if args.show_suppressed:
                doc["suppressed_findings"] = [
                    {"finding": f.render(), "reason": f.reason}
                    for f in findings if f.suppressed]
            print(json.dumps(doc))
        else:
            for line in doc["new"]:
                print(line, file=sys.stderr)
            if args.show_suppressed:
                for f in findings:
                    if f.suppressed:
                        print(f"{f.render()} [suppressed: {f.reason}]",
                              file=sys.stderr)
            print(json.dumps({k: v for k, v in doc.items()
                              if k != "new"}))
        return 0 if doc["status"] == "ok" else 1

    if args.cmd == "list":
        from anomod import labels
        rows = labels.ALL_LABELS if args.testbed is None else \
            labels.labels_for_testbed(args.testbed)
        for l in rows:
            print(f"{l.testbed}  {l.experiment:40s} {l.anomaly_level:12s} "
                  f"{l.anomaly_type:28s} {l.target_service}")
        return 0

    if args.cmd == "synth":
        from anomod import synth
        exp = synth.generate_experiment(args.experiment, n_traces=args.traces)
        print(json.dumps({
            "experiment": exp.name, "testbed": exp.testbed,
            "spans": exp.spans.n_spans, "traces": exp.spans.n_traces,
            "services": exp.spans.n_services,
            "metric_samples": exp.metrics.n_samples,
            "log_lines": exp.logs.n_lines,
            "api_records": exp.api.n_records,
        }))
        return 0

    if args.cmd == "detect":
        from anomod import detect, labels, synth
        from anomod.io import dataset
        if args.from_data:
            corpus = dataset.load_corpus(args.testbed,
                                         n_synth_traces=args.traces)
        else:
            corpus = [synth.generate_experiment(l, n_traces=args.traces)
                      for l in labels.labels_for_testbed(args.testbed)]
        s = detect.evaluate_corpus(corpus, backend=args.backend)
        print(json.dumps({
            "testbed": args.testbed, "backend": args.backend,
            "top1": s.top1, "top3": s.top3, "top5": s.top5,
            "detection_accuracy": s.detection_accuracy,
            "n_rca_cases": s.n_rca_cases,
            "per_level": detect.per_level_breakdown(s),
            "per_experiment": {r.experiment: {
                "score": round(r.score, 4),
                "top3": r.ranked_services[:3],
                "target": r.target_service} for r in s.results},
        }, indent=2))
        return 0

    if args.cmd == "stream":
        import dataclasses as _dc

        from anomod import labels, synth
        from anomod.stream import stream_experiment
        if bool(args.experiment) == bool(args.all):
            parser.error("give an experiment name OR --all")
        if args.all and args.from_data:
            parser.error("--from-data is single-experiment only; --all "
                         "sweeps the generator taxonomy")
        if args.all:
            from anomod.stream import stream_quality
            mesh_kw = {}
            if args.devices:
                from anomod.parallel import make_mesh
                mesh_kw["mesh"] = make_mesh(args.devices)
            if args.no_edge_attribution:
                mesh_kw["edge_attribution"] = False
            rows = stream_quality(
                args.testbed, n_traces=args.traces, seed=args.seed,
                multimodal=args.multimodal,
                severity=args.severity, noise=args.noise,
                n_confounders=args.confounders, shift=args.shift,
                slice_s=args.slice_seconds, z_threshold=args.threshold,
                baseline_windows=args.baseline_windows,
                consecutive=args.consecutive, **mesh_kw)
            for r in rows:
                print(json.dumps(r))
            import statistics
            rca_rows = [r for r in rows if "top1_hit" in r]
            lats = [r["detection_latency_windows"] for r in rca_rows
                    if r.get("detection_latency_windows") is not None]
            summary = {
                "testbed": args.testbed, "n_experiments": len(rows),
                "top1": (sum(r["top1_hit"] for r in rca_rows)
                         / len(rca_rows)) if rca_rows else None,
                "top3": (sum(r["top3_hit"] for r in rca_rows)
                         / len(rca_rows)) if rca_rows else None,
                "median_detection_latency_windows":
                    (statistics.median(lats) if lats else None),
            }
            print(json.dumps({"summary": summary}))
            try:
                import jax

                from anomod.provenance import capture_record, write_capture
                rec = capture_record(
                    "stream_quality", float(len(rows)), "experiments",
                    device=str(jax.devices()[0]), testbed=args.testbed,
                    params=dict(n_traces=args.traces, seed=args.seed,
                                multimodal=args.multimodal,
                                severity=args.severity, noise=args.noise,
                                confounders=args.confounders,
                                shift=args.shift,
                                slice_seconds=args.slice_seconds,
                                threshold=args.threshold,
                                baseline_windows=args.baseline_windows,
                                consecutive=args.consecutive,
                                edge_attribution=not
                                args.no_edge_attribution),
                    summary=summary, rows=rows)
                path = write_capture(rec)
                if path:
                    print(f"capture: {path}", file=sys.stderr)
            except Exception:
                pass
            return 0
        label = labels.label_for(args.experiment)
        if label is None:
            parser.error(f"unknown experiment {args.experiment!r}")
        # a non-default --testbed that contradicts the experiment's own
        # testbed must not be silently dropped (same contract as the
        # quality subcommand's cross-mode flag checks); the TT default
        # can't be told apart from an explicit --testbed TT, hence only
        # the detectable mismatch errors
        if args.testbed != "TT" and label.testbed != args.testbed:
            parser.error(f"{label.experiment} is a {label.testbed} "
                         f"experiment; --testbed {args.testbed} "
                         "contradicts it")
        if args.confounders:
            parser.error("--confounders applies to --all (the corpus "
                         "builder picks per-experiment decoys); it would "
                         "be silently ignored here")
        if args.shift != "in-dist":
            parser.error("--shift applies to --all; it would be silently "
                         "ignored here")
        if args.from_data and (args.severity != 1.0 or args.noise != 0.0
                               or args.seed != 0):
            parser.error("--severity/--noise/--seed shape the GENERATOR; "
                         "with --from-data the archived experiment is what "
                         "it is")
        if args.from_data:
            from anomod.io import dataset
            # load only what the detector consumes (coverage is not
            # time-resolved and never streams)
            mods = (["traces", "metrics", "logs", "api"]
                    if args.multimodal else ["traces"])
            exp = dataset.load_experiment(label.experiment,
                                          modalities=mods,
                                          n_synth_traces=args.traces)
        else:
            exp = synth.generate_experiment(
                label, n_traces=args.traces, seed=args.seed,
                hard=synth.HardMode(severity=args.severity,
                                    noise=args.noise))
        _kw = dict(slice_s=args.slice_seconds, z_threshold=args.threshold,
                   baseline_windows=args.baseline_windows,
                   consecutive=args.consecutive)
        if args.no_edge_attribution:
            _kw["edge_attribution"] = False
        if args.devices:
            from anomod.parallel import make_mesh
            _kw["mesh"] = make_mesh(args.devices)
        if args.multimodal:
            from anomod.stream import stream_experiment_multimodal
            det = stream_experiment_multimodal(exp, **_kw)
        else:
            det = stream_experiment(exp.spans, **_kw)
        ranked = det.ranked_services()
        win_s = det.replay.cfg.window_us / 1e6
        out = {
            "experiment": label.experiment, "testbed": label.testbed,
            "target_service": label.target_service,
            "n_spans": det.n_spans_in,
            "window_seconds": win_s,
            "n_alerts": len(det.alerts),
            "ranked_services": ranked[:5],
            # steady pipeline cost of the simulated live feed (staging +
            # jitted chunk steps + modality planes + window scoring);
            # one-time jit compilation is warmed in the constructor and
            # reported separately
            "push_wall_s": round(det.push_wall_s, 4),
            "compile_s": round(det.replay.compile_s, 3),
            "spans_per_sec": round(det.n_spans_in
                                   / max(det.push_wall_s, 1e-9), 1),
            "alerts": [_dc.asdict(a) for a in det.alerts[:50]],
        }
        # onset/latency report only when the corpus satisfies the synth
        # fault-window invariant (onset 600 s).  Generated corpora always
        # do; --from-data corpora may mix real archived artifacts (whose
        # fault timing is arbitrary) with synth fallbacks, so no latency
        # claim is made for them — localization fields still report.
        if label.is_anomaly and not args.from_data:
            # synth faults activate in the middle third: onset 600 s
            onset_w = int(600.0 // win_s)
            fw = det.first_alert_window(label.target_service
                                        or (ranked[0] if ranked else None))
            out["fault_onset_window"] = onset_w
            out["first_culprit_alert_window"] = fw
            # signed: negative = the culprit alerted BEFORE the fault
            # (a pre-onset false positive must not read as instant
            # detection)
            out["detection_latency_windows"] = \
                None if fw is None else fw - onset_w
            if label.target_service:
                out["top1_hit"] = bool(ranked) and \
                    ranked[0] == label.target_service
        print(json.dumps(out, indent=2))
        return 0

    if args.cmd == "obs":
        if args.action == "export" and not args.out:
            parser.error("obs export needs --out")
        if args.action != "score" and args.from_path:
            parser.error("--from applies to obs score")
        if args.action == "snapshot" and args.format in ("tt-csv", "chrome",
                                                         "jaeger"):
            parser.error("snapshot prints point-in-time state; the time "
                         "series export is `obs export` (tt-csv), the "
                         "span trace is `obs export --format "
                         "chrome|jaeger`")
        if args.action == "export" and args.format == "json":
            parser.error("obs export writes prom, tt-csv, chrome or "
                         "jaeger; `obs snapshot` is the JSON view")
        if args.action == "score" and args.format in ("chrome", "jaeger"):
            parser.error("--format chrome/jaeger applies to obs export")
        from anomod.obs.selfscrape import score_self_scrape
        if args.action == "score" and args.from_path:
            # scoring an existing capture needs jax (the detector stack)
            # but no serve run
            print(json.dumps(score_self_scrape(
                args.from_path, window_s=args.window_seconds,
                baseline_windows=args.baseline_windows,
                z_threshold=args.threshold), indent=2))
            return 0
        from anomod.obs.selfscrape import self_exercise
        tracer = None
        if args.action == "export" and args.format in ("chrome", "jaeger"):
            # the span exporters dump the self-exercise ENGINE's own
            # trace (the Tracer rides the run), not the metric registry
            from anomod.utils.tracing import Tracer
            tracer = Tracer("anomod-serve")
        reg = self_exercise(duration_s=args.serve_seconds,
                            n_tenants=args.tenants,
                            capacity_spans_per_s=args.capacity,
                            seed=args.seed, tracer=tracer)
        if tracer is not None:
            from pathlib import Path as _P
            if args.format == "chrome":
                tracer.dump_chrome(_P(args.out))
            else:
                tracer.dump(_P(args.out))
            print(json.dumps({"out": args.out, "format": args.format,
                              "spans": tracer.n_spans}))
            return 0
        if args.action == "snapshot":
            if args.format == "prom":
                from anomod.obs.export import to_prometheus_text
                print(to_prometheus_text(reg), end="")
            else:
                print(json.dumps({"n_journal_samples": reg.n_samples,
                                  "metrics": reg.snapshot()}, indent=2))
            return 0
        if args.action == "export":
            if args.format == "prom":
                from anomod.obs.export import export_prometheus_text
                n = export_prometheus_text(reg, args.out)
                # prom is a point-in-time view: count METRICS, not the
                # journal's time-series samples
                print(json.dumps({"out": args.out, "format": "prom",
                                  "metrics": n}))
            else:
                from anomod.obs.export import export_tt_csv
                n = export_tt_csv(reg, args.out)
                print(json.dumps({"out": args.out, "format": "tt-csv",
                                  "samples": n}))
            return 0
        # score the self-exercise's own telemetry (registry -> MetricBatch
        # -> detector), no file round trip
        from anomod.obs.export import to_metric_batch
        print(json.dumps(score_self_scrape(
            to_metric_batch(reg), window_s=args.window_seconds,
            baseline_windows=args.baseline_windows,
            z_threshold=args.threshold), indent=2))
        return 0

    if args.cmd == "serve":
        if args.tenants < 1:
            parser.error("--tenants must be >= 1")
        if args.services < 1:
            parser.error("--services must be >= 1")
        if args.capacity <= 0:
            parser.error("--capacity must be positive")
        if args.tick <= 0:
            parser.error("--tick must be positive")
        if args.window_seconds <= 0:
            parser.error("--window-seconds must be positive")
        if args.overload <= 0:
            parser.error("--overload must be positive")
        if args.fault_tenants < 0:
            parser.error("--fault-tenants must be >= 0")
        if args.shards is not None and args.shards < 1:
            parser.error("--shards must be >= 1")
        if args.pipeline is not None and args.pipeline < 1:
            parser.error("--pipeline must be >= 1")
        if args.rca and args.no_score:
            parser.error("--rca consumes the detectors' alert stream; "
                         "it cannot combine with --no-score")
        if args.ckpt_every is not None and args.ckpt_every < 0:
            parser.error("--ckpt-every must be >= 0 (0 = supervision "
                         "off)")
        if args.devices and args.ckpt_every:
            parser.error("shard supervision cannot checkpoint the mesh "
                         "plane's sharded state; --devices runs with "
                         "--ckpt-every 0")
        from anomod.config import get_config
        policy_mode = (args.policy if args.policy is not None
                       else get_config().serve_policy)
        if args.policy_script is not None:
            from anomod.config import validate_policy_script
            try:
                validate_policy_script(args.policy_script)
            except ValueError as e:
                parser.error(f"--policy-script: {e}")
            if policy_mode != "script":
                parser.error("--policy-script applies to --policy "
                             "script (it would be silently ignored)")
        for flag, val in (("--min-shards", args.min_shards),
                          ("--max-shards", args.max_shards)):
            if val is not None:
                if policy_mode == "off":
                    parser.error(f"{flag} applies to an elastic policy "
                                 "(--policy auto|script)")
                if val < 1:
                    parser.error(f"{flag} must be >= 1")
        if args.devices and args.policy is not None \
                and args.policy != "off":
            # only an EXPLICIT --policy conflicts hard; an env-sourced
            # ANOMOD_SERVE_POLICY=auto degrades to off at the engine
            # (the mesh plane is outside the migration seams — the
            # supervision idiom), so existing --devices workflows keep
            # working under a globally exported policy
            parser.error("the elastic policy migrates tenants through "
                         "the bucket-runner state seams; --devices "
                         "runs with --policy off")
        if args.async_commit and args.no_async_commit:
            parser.error("--async-commit contradicts --no-async-commit")
        if args.devices and args.async_commit:
            # only an EXPLICIT --async-commit conflicts hard; an
            # env-sourced ANOMOD_SERVE_ASYNC_COMMIT=1 degrades to the
            # synchronous tick at the engine (the mesh plane manages
            # its own sharded dispatch), so existing --devices
            # workflows keep working under a globally exported knob
            parser.error("the deferred-commit tick splits the bucket-"
                         "runner issue/commit seam; --devices runs "
                         "with the synchronous tick "
                         "(drop --async-commit)")
        if args.devices and args.worker == "process":
            # only an EXPLICIT --worker process conflicts hard; an
            # env-sourced ANOMOD_SERVE_WORKER=process degrades to the
            # thread engine at the engine (the mesh plane owns its own
            # device-sharded dispatch), so existing --devices workflows
            # keep working under a globally exported knob
            parser.error("the mesh plane shards across devices inside "
                         "one process; --devices runs with the thread "
                         "worker engine (drop --worker process)")
        if args.chaos:
            from anomod.config import validate_chaos_script
            try:
                faults = validate_chaos_script(args.chaos)
            except ValueError as e:
                parser.error(f"--chaos: {e}")
            n_sh = (args.shards if args.shards is not None
                    else get_config().serve_shards)
            if policy_mode != "off":
                # an elastic run can legitimately target any shard id
                # the scale-up ceiling makes reachable
                n_sh = max(n_sh, args.max_shards
                           if args.max_shards is not None
                           else get_config().serve_policy_max_shards)
            bad = sorted({f["shard"] for f in faults
                          if f["kind"] != "surge" and f["shard"] >= n_sh})
            if bad:
                parser.error(
                    f"--chaos targets shard(s) {bad} but the run has "
                    f"{n_sh} reachable shard(s) (ids 0..{n_sh - 1}) — "
                    "the fault(s) could never fire")
        from anomod.serve.batcher import validate_buckets
        from anomod.serve.engine import run_power_law
        buckets = None
        if args.buckets is not None:
            try:
                buckets = validate_buckets(
                    [p.strip() for p in args.buckets.split(",")
                     if p.strip()])
            except ValueError as e:
                parser.error(f"--buckets: {e}")
        lane_buckets = None
        if args.lane_buckets is not None:
            from anomod.config import validate_lane_buckets
            try:
                lane_buckets = validate_lane_buckets(
                    [p.strip() for p in args.lane_buckets.split(",")
                     if p.strip()])
            except ValueError as e:
                parser.error(f"--lane-buckets: {e}")
        if args.from_live or args.live_replay:
            if args.from_live and args.live_replay:
                parser.error("--from-live contradicts --live-replay")
            for flag, bad in (("--devices", args.devices),
                              ("--chaos", args.chaos),
                              ("--rca", args.rca),
                              ("--policy", args.policy),
                              ("--policy-script", args.policy_script),
                              ("--async-commit", args.async_commit),
                              ("--worker", args.worker),
                              ("--fold", args.fold),
                              ("--state", args.state),
                              ("--ckpt-every", args.ckpt_every),
                              ("--trace-out", args.trace_out)):
                if bad:
                    parser.error(f"{flag} is not supported on the "
                                 "live-feed path")
            from anomod.serve.feed import run_live_feed
            endpoint = None
            scrape_url = args.from_live
            if scrape_url and scrape_url.strip().lower() == "self":
                # the dogfood closed loop: serve this process's own
                # registry over real HTTP and point the feed at it
                from anomod.obs.http import ObsHttpServer
                endpoint = ObsHttpServer(
                    port=get_config().obs_http_port).start()
                scrape_url = f"{endpoint.url}/metrics"
            elif scrape_url and "://" not in scrape_url:
                parser.error("--from-live takes a URL (or 'self')")
            try:
                if args.live_replay:
                    _, report, _ = run_live_feed(
                        replay=args.live_replay,
                        capacity_spans_per_s=args.capacity,
                        duration_s=args.duration, tick_s=args.tick,
                        lag_s=args.feed_lag,
                        window_s=args.window_seconds,
                        baseline_windows=args.baseline_windows,
                        z_threshold=args.threshold, buckets=buckets,
                        lane_buckets=lane_buckets,
                        max_backlog=args.max_backlog,
                        score=not args.no_score,
                        fuse=False if args.no_fuse else None,
                        shards=args.shards, pipeline=args.pipeline)
                else:
                    _, report, _ = run_live_feed(
                        scrape_url=scrape_url,
                        n_tenants=args.tenants,
                        n_services=args.services,
                        capacity_spans_per_s=args.capacity,
                        duration_s=args.duration, tick_s=args.tick,
                        lag_s=args.feed_lag,
                        window_s=args.window_seconds,
                        baseline_windows=args.baseline_windows,
                        z_threshold=args.threshold, buckets=buckets,
                        lane_buckets=lane_buckets,
                        max_backlog=args.max_backlog,
                        score=not args.no_score,
                        fuse=False if args.no_fuse else None,
                        shards=args.shards, pipeline=args.pipeline,
                        journal=args.feed_journal)
            finally:
                if endpoint is not None:
                    endpoint.stop()
            print(json.dumps(report.to_dict(), indent=2))
            return 0
        mesh = None
        if args.devices:
            from anomod.parallel import make_mesh
            mesh = make_mesh(args.devices)
        tracer = None
        if args.trace_out:
            from anomod.utils.tracing import Tracer
            tracer = Tracer("anomod-serve")
        # the endpoint plane rides any serve run when ANOMOD_OBS_HTTP is
        # on: pure registry reads, decisions byte-identical either way
        from anomod.obs.http import maybe_serve
        _endpoint = maybe_serve()
        engine, report = run_power_law(
            n_tenants=args.tenants, n_services=args.services,
            capacity_spans_per_s=args.capacity, overload=args.overload,
            duration_s=args.duration, tick_s=args.tick, seed=args.seed,
            alpha=args.alpha, window_s=args.window_seconds,
            baseline_windows=args.baseline_windows,
            z_threshold=args.threshold, buckets=buckets,
            max_backlog=args.max_backlog,
            fault_tenants=args.fault_tenants, score=not args.no_score,
            mesh=mesh, tracer=tracer,
            fuse=False if args.no_fuse else None,
            lane_buckets=lane_buckets, shards=args.shards,
            pipeline=args.pipeline,
            native=False if args.no_native else None,
            state=args.state, chaos=args.chaos,
            ckpt_every=args.ckpt_every,
            policy=args.policy, policy_script=args.policy_script,
            min_shards=args.min_shards, max_shards=args.max_shards,
            async_commit=(True if args.async_commit
                          else (False if args.no_async_commit
                                else None)),
            worker=args.worker, fold=args.fold,
            native_drain=args.native_drain, seq_model=args.seq_model,
            # --no-score forces RCA off even when ANOMOD_SERVE_RCA=1
            # (the explicit CLI ask wins over the env default; the
            # --rca + --no-score combination already parser.error'd)
            rca=True if args.rca else (False if args.no_score else None))
        if _endpoint is not None:
            _endpoint.stop()
        if tracer is not None:
            from pathlib import Path as _P
            tracer.dump(_P(args.trace_out))
        out = report.to_dict()
        if engine.seq_counters is not None:
            out["seq_model"] = dict(engine.seq_counters,
                                    windows_scored=len(engine.seq_scores))
        print(json.dumps(out, indent=2))
        return 0

    if args.cmd == "census":
        from pathlib import Path as _P
        # mode-mismatched flags fail loud, never silently ignored
        # (the audit-branch discipline): record-only and
        # probe-only flags are refused by the other actions
        _record_only = (("--tenants", args.tenants),
                        ("--duration", args.duration),
                        ("--tick", args.tick),
                        ("--capacity", args.capacity),
                        ("--overload", args.overload),
                        ("--shards", args.shards),
                        ("--every", args.every))
        _probe_only = (("--sizes", args.sizes), ("--hot", args.hot),
                       ("--ticks", args.ticks))
        if args.action != "record":
            for flag, got in _record_only:
                if got is not None:
                    parser.error(f"{flag} applies to census record, "
                                 f"not {args.action}")
        if args.action != "probe":
            for flag, got in _probe_only:
                if got is not None:
                    parser.error(f"{flag} applies to census probe, "
                                 f"not {args.action}")
        if args.action == "diff":
            if len(args.paths) != 2:
                parser.error("census diff takes exactly two capture "
                             "paths (A then B)")
            if args.out:
                parser.error("--out applies to census record/probe")
            if args.seed is not None:
                parser.error("--seed applies to census record/probe")
            from anomod.obs.census import diff_census
            try:
                a = json.loads(_P(args.paths[0]).read_text())
                b = json.loads(_P(args.paths[1]).read_text())
            except (OSError, ValueError) as e:
                parser.error(f"cannot load capture: {e}")
            doc = diff_census(a, b, tolerance=args.tolerance)
            print(json.dumps(doc, indent=2))
            if doc["status"] == "census-missing":
                print("census diff: capture(s) carry no census block "
                      f"(missing in {doc['missing_in']}) — nothing was "
                      "compared, so this verdict must not pass a gate",
                      file=sys.stderr)
                return 2
            if doc["status"] == "bytes-regression":
                r = doc["bytes_regressions"][0]
                print(f"census diff: resident bytes grew on the "
                      f"{r['plane']!r} plane ({r['a']} -> {r['b']}) — "
                      "byte counts are deterministic; this is real "
                      "growth, not noise", file=sys.stderr)
                return 1
            if doc["status"] == "slope-regression":
                r = doc["slope_regressions"][0]
                if r["exact"]:
                    # the bytes slope is deterministic — the verdict
                    # is exact growth, never a tolerance breach
                    print(f"census diff: the {r['slope']} baseline "
                          f"grew (a={r['a']}, b={r['b']}) — this "
                          "slope is deterministic; any growth is "
                          "real, not noise", file=sys.stderr)
                else:
                    print(f"census diff: the {r['slope']} baseline "
                          f"regressed (a={r['a']}, b={r['b']}) past "
                          f"the 1+{doc['tolerance']} noise tolerance",
                          file=sys.stderr)
                return 1
            return 0
        if args.tolerance is not None:
            parser.error("--tolerance applies to census diff")
        if args.paths:
            parser.error(f"census {args.action} takes no positional "
                         "paths")
        if args.action == "probe":
            sizes = None
            if args.sizes is not None:
                try:
                    sizes = tuple(int(p.strip())
                                  for p in args.sizes.split(",")
                                  if p.strip())
                    if len(sizes) < 2 or any(s < 1 for s in sizes) \
                            or any(a >= b for a, b
                                   in zip(sizes, sizes[1:])):
                        raise ValueError(
                            "need >= 2 strictly ascending positive "
                            "sizes")
                except ValueError as e:
                    parser.error(f"--sizes: {e}")
            if args.ticks is not None and args.ticks < 1:
                parser.error("--ticks must be >= 1")
            if args.hot is not None and args.hot < 1:
                parser.error("--hot must be >= 1")
            from anomod.obs.census import CENSUS_FORMAT, fleet_probe
            doc = {"census_format": CENSUS_FORMAT,
                   "sweep": fleet_probe(
                       sizes=sizes,
                       hot=1000 if args.hot is None else args.hot,
                       ticks=8 if args.ticks is None else args.ticks,
                       seed=0 if args.seed is None else args.seed)}
            if args.out:
                from anomod.obs.flight import _atomic_write_json
                _atomic_write_json(args.out, doc)
                doc["out"] = args.out
            print(json.dumps(doc, indent=2))
            return 0
        # record
        if not args.out:
            parser.error("census record needs --out")

        def _or(v, default):
            return default if v is None else v

        from anomod.obs.census import CENSUS_FORMAT
        from anomod.obs.flight import _atomic_write_json
        from anomod.serve.engine import run_power_law
        eng, rep = run_power_law(
            n_tenants=_or(args.tenants, 24), n_services=8,
            capacity_spans_per_s=_or(args.capacity, 4000.0),
            overload=_or(args.overload, 1.5),
            duration_s=_or(args.duration, 30.0),
            tick_s=_or(args.tick, 0.5), seed=_or(args.seed, 0),
            shards=args.shards, census=True, census_every=args.every,
            flight=True)
        stream = [rec["census"]
                  for rec in eng.flight_recorder.records()
                  if rec["census"]["planes"]]
        _atomic_write_json(args.out, {
            "census_format": CENSUS_FORMAT,
            "engine": {"shards": rep.shards, "seed": _or(args.seed, 0),
                       "tick_s": _or(args.tick, 0.5),
                       "census_every": eng.census_every},
            "report": {
                "census_ticks": rep.census_ticks,
                "census_hot_set": rep.census_hot_set,
                "census_resident_bytes": rep.census_resident_bytes},
            "stream": stream})
        print(json.dumps({
            "action": "record", "out": args.out,
            "census_ticks": rep.census_ticks,
            "resident_bytes":
                rep.census_resident_bytes.get("total"),
            "pool_reconciled":
                rep.census_resident_bytes.get("pool_reconciled"),
            "hot_set": rep.census_hot_set}, indent=2))
        return 0

    if args.cmd == "audit":
        from anomod.obs.flight import diff_journals, load_journal
        # record-only flags must not be silently ignored by replay/diff
        # (replay takes its run from the journal header; an operator
        # passing --seed or --duration there would draw forensic
        # conclusions from a run they did not ask for)
        if args.action != "record":
            _record_only = (("--tenants", args.tenants),
                            ("--services", args.services),
                            ("--duration", args.duration),
                            ("--tick", args.tick),
                            ("--capacity", args.capacity),
                            ("--overload", args.overload),
                            ("--seed", args.seed),
                            ("--window-seconds", args.window_seconds),
                            ("--baseline-windows", args.baseline_windows),
                            ("--threshold", args.threshold),
                            ("--fault-tenants", args.fault_tenants),
                            ("--rca", args.rca or None))
            for flag, got in _record_only:
                if got is not None:
                    parser.error(
                        f"{flag} applies to audit record; "
                        f"{args.action} takes its run from the journal "
                        "header" + (" (--shards/--pipeline/--state/"
                                    "--digest-every override)"
                                    if args.action == "replay" else ""))
        if args.action == "diff":
            for flag, val in (("--shards", args.shards),
                              ("--pipeline", args.pipeline),
                              ("--state", args.state),
                              ("--digest-every", args.digest_every)):
                if val is not None:
                    parser.error(f"{flag} applies to audit record/replay")
            if len(args.journals) != 2:
                parser.error("audit diff takes exactly two journal paths")
            if args.out:
                parser.error("--out applies to audit record/replay")
            a = load_journal(args.journals[0])
            b = load_journal(args.journals[1])
            d = diff_journals(a, b)
            out = {"action": "diff",
                   "a": args.journals[0], "b": args.journals[1],
                   "ticks_a": len(a["ticks"]), "ticks_b": len(b["ticks"]),
                   "identical": d is None}
            if d is not None:
                out["divergence"] = d
            print(json.dumps(out, indent=2))
            if d is not None:
                print(f"audit diff: first divergence at tick "
                      f"{d['tick']} in the {d['plane']} plane",
                      file=sys.stderr)
                return 1
            return 0
        if not args.out:
            parser.error(f"audit {args.action} needs --out")
        if args.action == "record":
            if args.journals:
                parser.error("audit record takes no journal arguments")

            def _or(v, default):
                return default if v is None else v

            kw = dict(n_tenants=_or(args.tenants, 24),
                      n_services=_or(args.services, 8),
                      capacity_spans_per_s=_or(args.capacity, 4000.0),
                      overload=_or(args.overload, 1.5),
                      duration_s=_or(args.duration, 30.0),
                      tick_s=_or(args.tick, 0.5),
                      seed=_or(args.seed, 0),
                      window_s=_or(args.window_seconds, 5.0),
                      baseline_windows=_or(args.baseline_windows, 2),
                      z_threshold=_or(args.threshold, 4.0),
                      fault_tenants=_or(args.fault_tenants, 1),
                      shards=args.shards, pipeline=args.pipeline,
                      state=args.state,
                      rca=True if args.rca else None,
                      flight=True,
                      flight_digest_every=args.digest_every)
        else:
            if len(args.journals) != 1:
                parser.error("audit replay takes exactly one journal path")
            header = load_journal(args.journals[0]).get("header", {})
            run = header.get("run")
            if not run:
                parser.error("journal header carries no run parameters "
                             "(not recorded through `anomod audit "
                             "record` / run_power_law) — cannot replay")
            kw = dict(run)
            kw["buckets"] = tuple(kw["buckets"]) if kw.get("buckets") \
                else None
            kw["lane_buckets"] = tuple(kw["lane_buckets"]) \
                if kw.get("lane_buckets") else None
            # the forensic overrides: replay the SAME decisions at a
            # different shard count / pipeline depth / residency — diff
            # against the original is the determinism contract's probe
            for name, val in (("shards", args.shards),
                              ("pipeline", args.pipeline),
                              ("state", args.state),
                              ("flight_digest_every", args.digest_every)):
                if val is not None:
                    kw[name] = val
            kw["flight"] = True
        if kw.pop("traffic", None) == "live_feed":
            # a live-feed run replays through its WIRE journal (the
            # response sequence is the ground truth), not by re-polling
            if args.state is not None:
                parser.error("--state applies to power-law journals; "
                             "live-feed replays take the engine shape "
                             "from the journal header")
            from pathlib import Path as _P
            feed_journal = kw.pop("feed_journal", "")
            if not feed_journal or not _P(feed_journal).exists():
                parser.error(
                    "the run's wire journal is missing "
                    f"({feed_journal or 'not recorded'}) — record live "
                    "runs with ANOMOD_FEED_JOURNAL/--feed-journal to "
                    "make them replayable")
            from anomod.serve.feed import run_live_feed
            eng, rep, _ = run_live_feed(replay=feed_journal, **kw)
        else:
            from anomod.serve.engine import run_power_law
            # pre-tiering journals (recorded before the state-tiering
            # PR) carry no tier geometry: replay them tiering-OFF, never
            # under the replaying process's env knobs — env drift must
            # not masquerade as plane divergence
            kw.setdefault("tier_hot", 0)
            # likewise pre-procshard journals carry no worker/fold
            # keys: replay them on the thread engine with the dense
            # fold, never under the replaying process's env knobs
            kw.setdefault("worker", "thread")
            kw.setdefault("fold", "dense")
            # journals recorded before the perf observatory went carry
            # its enable bit; it never moved a canonical plane
            kw.pop("perf", None)
            eng, rep = run_power_law(**kw)
        doc = eng.flight_recorder.dump(args.out)
        print(json.dumps({
            "action": args.action, "out": args.out,
            "ticks": doc["n_recorded"], "dropped": doc["n_dropped"],
            "seed": doc["header"]["run"].get("seed"),
            "shards": doc["header"]["engine"]["shards"],
            "serve_state": doc["header"]["engine"]["serve_state"],
            "digest_every": doc["header"]["digest_every"],
            "served_spans": rep.served_spans,
            "n_alerts": rep.n_alerts,
        }))
        return 0

    if args.cmd == "quality":
        import dataclasses as _dc

        from anomod.quality import (render_markdown, render_shift_markdown,
                                    severity_sweep, shift_sweep)
        # a flag belonging to the other sweep kind must not be silently
        # dropped (defaults come from the parser, so a non-default value
        # means the user passed it)
        if args.sweep == "shift" and args.severities != [1.0, 0.4, 0.2, 0.1,
                                                         0.05]:
            parser.error("--severities applies to --sweep severity; "
                         "use --shift-severity for the shift sweep")
        if args.sweep == "severity" and args.shift_severity != 0.3:
            parser.error("--shift-severity applies to --sweep shift")
        if args.sweep == "severity" and args.edge_aware:
            parser.error("--edge-aware applies to --sweep shift")
        common = dict(
            testbed=args.testbed, model_names=args.models,
            train_seeds=range(args.train_seeds),
            eval_seeds=range(100, 100 + args.eval_seeds),
            n_traces=args.traces, epochs=args.epochs, noise=args.noise,
            n_confounders=args.confounders, verbose=not args.json)
        if args.sweep == "shift":
            pts = shift_sweep(severity=args.shift_severity,
                              edge_aware=args.edge_aware, **common)
            render = render_shift_markdown
        else:
            pts = severity_sweep(severities=args.severities, **common)
            render = render_markdown
        # committed provenance trail: every
        # sweep leaves a bench_runs/ record with the full table + device
        # string + git SHA, so docs tables cite re-checkable artifacts
        try:
            import jax

            from anomod.provenance import capture_record, write_capture
            rec = capture_record(
                f"quality_{args.sweep}_sweep", float(len(pts)), "points",
                device=str(jax.devices()[0]), testbed=args.testbed,
                models=list(args.models),
                params={**{k: (list(v) if isinstance(v, range) else v)
                           for k, v in common.items()
                           if k not in ("verbose", "testbed", "model_names")},
                        **({"shift_severity": args.shift_severity,
                            "edge_aware": bool(args.edge_aware)}
                           if args.sweep == "shift"
                           else {"severities": args.severities})},
                points=[_dc.asdict(p) for p in pts])
            capture_path = write_capture(rec)
        except Exception:
            capture_path = None
        if args.json:
            # one QualityPoint per stdout line (stream stays homogeneous);
            # the capture path goes to stderr
            for p in pts:
                print(json.dumps(_dc.asdict(p)))
            if capture_path:
                print(f"capture: {capture_path}", file=sys.stderr)
        else:
            print(render(pts))
            if capture_path:
                print(f"\ncapture: {capture_path}")
        return 0

    if args.cmd == "rca":
        if args.resume and not args.checkpoint_dir:
            parser.error("--resume requires --checkpoint-dir")
        from anomod.rca import train_rca
        r = train_rca(
            args.testbed, args.model,
            train_seeds=range(args.train_seeds),
            eval_seeds=range(100, 100 + args.eval_seeds),
            epochs=args.epochs,
            checkpoint_dir=args.checkpoint_dir,
            resume=args.resume)
        print(json.dumps({
            "testbed": args.testbed, "model": r.model_name,
            "top1": r.top1, "top3": r.top3,
            "detection_auc": r.detection_auc, "n_eval": r.n_eval,
        }))
        return 0

    if args.cmd == "collect":
        import time as _time

        from anomod.io.live import (ElasticsearchClient, HttpTransport,
                                    JaegerClient, PrometheusClient,
                                    SkyWalkingClient)
        if args.kind in ("kube-logs", "docker-logs", "jacoco", "gcov"):
            from pathlib import Path as _P

            from anomod.io.live_exec import (DockerLogCollector, ExecRunner,
                                             GcovCoverageCollector,
                                             JacocoCoverageCollector,
                                             KubeLogCollector)
            runner = ExecRunner(timeout=args.timeout)
            stamp = _time.strftime("%Y%m%d_%H%M%S")
            if args.kind == "kube-logs":
                rep = KubeLogCollector(
                    runner=runner, namespace=args.namespace).collect(
                    _P(args.out), stamp=stamp, tail=args.tail)
            elif args.kind == "docker-logs":
                rep = DockerLogCollector(runner=runner).collect(
                    _P(args.out), stamp=stamp, time_range=args.since)
            elif args.kind == "gcov":
                out = _P(args.out)
                rep = GcovCoverageCollector(runner=runner).collect(
                    _P(args.mount_root), out,
                    base=args.experiment, stamp=stamp)
            else:
                out = _P(args.out)
                report = (_P(args.report_dir) if args.report_dir
                          else out.parent / "coverage_report")
                rep = JacocoCoverageCollector(
                    runner=runner, namespace=args.namespace).collect(
                    out, report)
            print(json.dumps(rep.to_json()))
            return 0
        if not args.url:
            parser.error(f"--url is required for kind {args.kind}")
        tp = HttpTransport(timeout=args.timeout, max_retries=args.retries)
        now = _time.time()
        start = now - args.hours_back * 3600.0
        if args.kind == "prometheus":
            client = PrometheusClient(args.url, transport=tp)
            if args.testbed == "SN":
                # catalog names double as identity queries against a stub
                # or relabeling proxy; a real deployment maps names to the
                # recorded PromQL (collect_metric.sh's query table)
                from anomod.metrics_catalog import SN_METRIC_FILES
                rep = client.collect_sn({n: n for n in SN_METRIC_FILES},
                                        args.out, start, now,
                                        step=args.step)
            else:
                from anomod.metrics_catalog import TT_ALL_QUERIES
                rep = client.collect_tt(TT_ALL_QUERIES, args.out,
                                        start, now, step=args.step)
        elif args.kind == "jaeger":
            rep = JaegerClient(args.url, transport=tp).collect_all(
                args.out, limit=args.limit,
                lookback_ms=int(args.hours_back * 3_600_000))
        elif args.kind == "skywalking":
            rep = SkyWalkingClient(args.url, transport=tp).collect(
                args.out, experiment=args.experiment, limit=args.limit,
                hours_back=args.hours_back)
        else:
            rep = ElasticsearchClient(args.url, transport=tp).collect(
                args.out, size=args.limit, hours_back=args.hours_back)
        print(json.dumps(rep.to_json()))
        return 0

    if args.cmd == "golden":
        from anomod.golden import format_markdown, golden_report
        report = golden_report()
        print(format_markdown(report) if args.markdown
              else json.dumps(report, indent=1))
        return 0

    if args.cmd == "ingest":
        import dataclasses as _dc
        import time as _time

        from anomod.config import get_config
        from anomod.io import cache as ingest_cache
        from anomod.io import dataset
        cfg = get_config()
        from pathlib import Path as _P
        if args.cache_dir is not None:
            cfg = _dc.replace(cfg, cache_dir=_P(args.cache_dir))
        if args.data_root is not None:
            cfg = _dc.replace(cfg, data_root=_P(args.data_root))
        root = ingest_cache.cache_root(cfg)
        out = {"cache_dir": str(root) if root else None}
        if root is None:
            print(json.dumps({**out, "error":
                              "caching disabled (ANOMOD_CACHE_DIR=off)"}))
            return 1
        if args.clear:
            out["cleared"] = ingest_cache.clear(root)
        if args.warm_cache:
            ingest_cache.reset_stats()
            testbeds = (["SN", "TT"] if args.testbed == "both"
                        else [args.testbed])
            t0 = _time.perf_counter()
            for tb in testbeds:
                dataset.load_corpus(tb, cfg=cfg,
                                    n_synth_traces=args.traces,
                                    workers=args.workers)
                if args.bench_traces:
                    dataset.load_bench_corpus(tb, args.bench_traces, cfg)
            out.update(warmed=testbeds,
                       wall_s=round(_time.perf_counter() - t0, 3),
                       **ingest_cache.stats().to_dict())
        out["entries"] = ingest_cache.entry_count(root)
        print(json.dumps(out))
        return 0

    if args.cmd == "validate":
        from anomod import labels, synth
        from anomod.io import cache as ingest_cache
        from anomod.io import dataset
        from anomod.validate import corpus_summary, validate_experiment
        ingest_cache.reset_stats()
        if args.from_data:
            corpus = dataset.load_corpus(args.testbed, n_synth_traces=args.traces)
        else:
            corpus = [synth.generate_experiment(l, n_traces=args.traces)
                      for l in labels.labels_for_testbed(args.testbed)]
        reports = [validate_experiment(e) for e in corpus]
        cache_stats = None
        if args.from_data:
            # a fresh/empty cache dir (or one the counters can't be read
            # from) must degrade to zero counters, never crash the
            # validation report — the counters are a quality SIGNAL, not
            # a load-bearing dependency
            try:
                cache_stats = ingest_cache.stats().to_dict()
            except Exception:
                cache_stats = ingest_cache.CacheStats().to_dict()
        summary = corpus_summary(args.testbed, reports,
                                 cache_stats=cache_stats)
        # native-runtime health rides the validation document: the knob
        # value, availability, and — the part a silent fallback hides —
        # the recorded build-failure reason when the .so is unusable
        from anomod.io import native as native_io
        summary["native"] = native_io.status()
        # contract health rides the validation document too (the
        # static-analysis twin of the native block): rule inventory,
        # live finding counts and baseline size — an operator sees a
        # violated determinism/parity contract next to an unusable
        # native runtime, not in a separate tool
        from anomod.analysis import status_block as _lint_status
        summary["lint"] = _lint_status()
        print(json.dumps(summary, indent=2))
        return 0

    if args.cmd == "campaign":
        from anomod.campaign import run_campaign
        done = run_campaign(args.testbed, args.out,
                            experiments=args.experiments,
                            n_traces=args.traces)
        print(json.dumps({"testbed": args.testbed, "out": args.out,
                          "experiments": done}))
        return 0

    if args.cmd == "chaos":
        from anomod import chaos, labels
        label = labels.label_for(args.experiment)
        if label is None:
            print(f"unknown experiment: {args.experiment}", file=sys.stderr)
            return 1
        plan = {"experiment": label.experiment, "tool": label.chaos_tool}
        if label.chaos_tool == "chaosmesh":
            if args.format == "yaml":
                print(chaos.mesh_crd_yaml(label))
                return 0
            plan["crd"] = chaos.build_mesh_crd(label)
        elif label.chaos_tool == "chaosblade":
            cmd = chaos.blade_create_command(label)
            if cmd is not None:
                plan["blade"] = list(cmd.args)
                plan["needs_sudo"] = cmd.needs_sudo
            dc = chaos.docker_command(label)
            if dc is not None:
                plan["docker"] = list(dc)
        if args.format == "yaml":
            import yaml
            print(yaml.safe_dump(plan, sort_keys=False), end="")
        else:
            print(json.dumps(plan, indent=2))
        return 0

    if args.cmd == "scenario":
        import numpy as np

        from anomod import labels, scenario
        from anomod.chaos import ChaosController
        if args.iterations < 1:
            print("--iterations must be >= 1", file=sys.stderr)
            return 1
        ctl = None
        if args.chaos:
            label = labels.label_for(args.chaos)
            if label is None:
                print(f"unknown experiment: {args.chaos}", file=sys.stderr)
                return 1
            if label.testbed != "TT":
                print(f"{label.experiment} is an {label.testbed} fault; the "
                      "scenario workload drives the TT testbed", file=sys.stderr)
                return 1
            ctl = ChaosController()
            ctl.create(label)
        batch = scenario.run_scenario(iterations=args.iterations,
                                      seed=args.seed, controller=ctl)
        by_status = {str(c): int((batch.status == c).sum())
                     for c in np.unique(batch.status)}
        print(json.dumps({
            "requests": batch.n_records,
            "endpoints": len(batch.endpoints),
            "status_codes": by_status,
            "error_rate": round(float((batch.status >= 500).mean()), 4),
            "avg_latency_ms": round(float(batch.latency_ms.mean()), 2),
            "p99_latency_ms": round(float(np.percentile(batch.latency_ms, 99)), 2),
            "chaos": args.chaos,
        }))
        return 0

    if args.cmd == "deploy":
        from anomod import deploy
        if args.testbed == "SN":
            print(deploy.render_plan(deploy.sn_compose_plan(up=not args.down)),
                  end="")
            return 0
        flags = deploy.DeployFlags(
            all=args.deploy_all, independent_db=args.independent_db,
            with_monitoring=args.with_monitoring,
            with_tracing=args.with_tracing)
        if args.secrets:
            import yaml
            host = None if flags.independent_db else "tsdb-mysql-leader"
            print(yaml.safe_dump_all(deploy.gen_mysql_secrets(host),
                                     sort_keys=False), end="")
            return 0
        print(deploy.render_plan(deploy.tt_deploy_plan(flags)), end="")
        return 0

    if args.cmd == "monitor":
        import numpy as np

        from anomod.monitor import capture_openapi_responses
        report = capture_openapi_responses(
            args.out, mode=args.mode, cycles=args.cycles,
            seed=args.seed, chaos=args.chaos,
            wrk2_requests=args.wrk2_requests)
        b = report.batch
        print(json.dumps({
            "mode": report.mode, "cycles": report.n_cycles,
            "requests": b.n_records, "endpoints": len(b.endpoints),
            "reachable": sum(report.connectivity.values()),
            "status_codes": {str(c): int((b.status == c).sum())
                             for c in np.unique(b.status)},
            "error_rate": round(float((b.status >= 500).mean()), 4),
            "p99_latency_ms": round(float(np.percentile(b.latency_ms, 99)), 2),
            "out": args.out, "chaos": args.chaos,
        }))
        return 0

    if args.cmd == "logscan":
        from pathlib import Path

        from anomod.io import native
        from anomod.io.lfs import is_lfs_pointer
        from anomod.io.logs import summarize_log_files
        root = Path(args.dir)
        if not root.is_dir():
            print(f"not a directory: {root}", file=sys.stderr)
            return 1
        candidates = sorted(root.glob(args.glob))
        paths = [p for p in candidates if not is_lfs_pointer(p)]
        summaries = summarize_log_files(paths)
        print(json.dumps({
            "dir": str(root), "n_files": len(paths),
            "n_lfs_stubs": len(candidates) - len(paths),
            "native": native.enabled(),
            "totals": {
                "lines": sum(s.n_lines for s in summaries),
                "errors": sum(s.n_error for s in summaries),
                "warnings": sum(s.n_warn for s in summaries),
                "bytes": sum(s.size_bytes for s in summaries),
            },
            "files": [{
                "path": str(p.relative_to(root)), "service": s.service,
                "lines": s.n_lines, "errors": s.n_error,
                "warnings": s.n_warn, "info": s.n_info,
                "bytes": s.size_bytes,
            } for p, s in zip(paths, summaries)],
        }, indent=2))
        return 0

    if args.cmd == "replay":
        if args.devices and args.replicate != 1:
            parser.error("--replicate is not supported with --devices")
        if args.devices and args.kernel == "numpy":
            parser.error("--kernel numpy is the single-chip host engine; "
                         "the sharded path needs a device kernel")
        if args.devices and args.kernel == "pallas-sorted":
            parser.error("--kernel pallas-sorted stages on the host for one "
                         "chip; the sharded path uses 'xla' or 'pallas'")
        from anomod import labels, synth
        from anomod.replay import ReplayConfig, measure_throughput
        from anomod.schemas import concat_span_batches
        batch = concat_span_batches([
            synth.generate_spans(l, n_traces=args.traces)
            for l in labels.labels_for_testbed(args.testbed)])
        cfg = ReplayConfig(n_services=batch.n_services)
        if args.devices:
            from anomod.parallel import make_mesh, sharded_throughput
            mesh = make_mesh(args.devices)
            r = sharded_throughput(batch, mesh, cfg, kernel=args.kernel)
        else:
            r = measure_throughput(batch, cfg, replicate=args.replicate,
                                   kernel=args.kernel)
        out = {
            "n_spans": r.n_spans, "wall_s": round(r.wall_s, 4),
            "spans_per_sec": round(r.spans_per_sec, 1),
            "compile_s": round(r.compile_s, 2),
            "kernel": r.kernel,
        }
        if args.devices:
            out["devices"] = int(mesh.devices.size)
        if args.percentiles:
            import numpy as np

            from anomod.ops.tdigest import tdigest_build, tdigest_quantile
            from anomod.replay import replay_digests
            # per-segment digest plane, merged (weighted rebuild) into ONE
            # corpus digest so the reported tail is the true corpus-wide
            # p99, not a median across segments
            d = replay_digests(batch, cfg)
            corpus = tdigest_build(d.mean.reshape(-1), k=64,
                                   weights=d.weight.reshape(-1))
            out["latency_us"] = {
                name: round(float(np.expm1(tdigest_quantile(corpus, q))), 1)
                for name, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99))
            } if float(d.weight.sum()) > 0 else {}
        if args.edge_percentiles:
            import numpy as np

            from anomod.replay import replay_edge_features
            pct, distinct, table = replay_edge_features(batch, cfg)
            W = cfg.n_windows
            # per-edge p99 = worst window's p99 with traffic; rank the
            # cross edges (self-edges are the node view)
            p99 = np.nan_to_num(pct[:, -1].reshape(len(table), W))
            worst = p99.max(axis=1)
            rows = sorted(
                ((float(worst[i]), i, a, b)
                 for i, (a, b) in enumerate(table)
                 if a != b and worst[i] > 0), reverse=True)
            out["edge_p99_us_top"] = [
                {"edge": f"{batch.services[a]}->{batch.services[b]}",
                 "p99_us": round(v, 1),
                 "distinct_traces": round(float(distinct[i]), 1)}
                for v, i, a, b in rows[:5]]
        print(json.dumps(out))
        return 0

    return 1


if __name__ == "__main__":
    sys.exit(main())
