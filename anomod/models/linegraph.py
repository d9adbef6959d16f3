"""Edge-native RCA: line-graph scoring with edges as tokens.

Every other model in the zoo consumes per-SERVICE aggregates, so a fault
living on call-graph LINKS (anomod.synth fault_locus="edge": the callee
side of the culprit's outgoing calls degrades, the culprit's own spans
stay healthy) is architecturally outside their evidence — post-leak-fix,
all node-feature models score ≤0.06 edge-locus top-1, and even with the
out-edge feature BLOCK the best attention model reaches 0.39
(docs/QUALITY.md).  This model makes edges first-class: each observed
(caller, callee) edge is a token carrying its own windowed aggregates and
explicit CONTRAST features (its deviation from the callee's other
in-edges and the caller's other out-edges — the discriminative pattern
"this link is hot in a way its endpoints' other traffic is not",
hand-built instead of hoped-for from message passing), while the node
channel reuses the zoo's proven sequence backbone (TokenEmbed + attention
+ adjacency-hop pooling, anomod.models.transformer) so edge capability
never taxes in-distribution accuracy.  Service scores combine the node
logit with direction-aware peak/mean readouts of the incident-edge
logits — the caller's out-edge plane is exactly where a link fault lands.

Round-5 redesign notes (committed records in bench_runs/, table in
docs/QUALITY.md):
  - windowed inputs enter POOLED over windows (mean/max/mean-positive):
    the earlier flatten readout memorized window positions (train 1.00 /
    eval 0.42); pooling alone moved in-dist 0.42 -> 0.81.
  - the transformer node backbone restores in-dist to 0.97 across every
    non-edge shift at unchanged edge capability.
  - edge-locus attribution is DATA-limited at the sweep's 6-seed
    training protocol: 0.39 top-1 there (bench_runs/20260731T184051Z)
    vs 0.50 with 24 training seeds (bench_runs/20260731T210351Z, the
    committed data-scaling record; in-dist 0.97 at both protocols —
    see docs/QUALITY.md for the same-protocol comparison against
    the out-edge-block models).

TPU-first shape discipline: the edge list is padded to a static E_max
with a mask; the edge<->node exchanges are one-hot [E, S] matmuls (MXU)
instead of gather/scatter, and every stage is a fixed-depth compact
module — no data-dependent control flow anywhere.

No reference counterpart: the reference ships labeled data for this model
family but no model code (SURVEY.md §0).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp


def _pool_windows(t):
    """[..., W, F] -> [..., 3F]: mean / max / mean-positive over windows.

    The anti-memorization stage: a flatten readout lets a small corpus be
    memorized by window position; these order-free summaries keep the
    burst shape (max), the level (mean), and the one-sided heat
    (mean-positive) that fault effects actually live in."""
    return jnp.concatenate([t.mean(axis=-2), t.max(axis=-2),
                            nn.relu(t).mean(axis=-2)], axis=-1)


class LineGraphRCA(nn.Module):
    """Edge-token culprit scorer.

    ``__call__(x, x_t, edge_x, src, dst, mask) -> [S]`` scores:
      - ``x``       [S, Fs]     static multimodal features (logs/metrics/
                                api/coverage — the node evidence channel
                                every temporal-family model fuses)
      - ``x_t``     [S, W, Fn]  windowed node features
      - ``edge_x``  [E, W, 4]   windowed PER-EDGE features (padded)
      - ``src/dst`` [E] int32   edge endpoints, ``mask`` [E] bool
    """
    d_model: int = 48
    n_heads: int = 4
    n_layers: int = 2
    mlp_hidden: int = 96
    hidden: int = 64

    @nn.compact
    def __call__(self, x, x_t, edge_x, src, dst, mask):
        from anomod.models.transformer import (AttentionBlock, ScoreHead,
                                               TokenEmbed)
        S, W, _ = x_t.shape
        E = edge_x.shape[0]
        m = mask.astype(jnp.float32)[:, None]
        # one-hot incidence [E, S]: the edge<->node exchange operator (MXU
        # matmuls; masked rows contribute nothing anywhere)
        inc_src = jnp.eye(S, dtype=jnp.float32)[src] * m
        inc_dst = jnp.eye(S, dtype=jnp.float32)[dst] * m
        deg_out = jnp.maximum(inc_src.sum(axis=0), 1.0)[:, None]
        deg_in = jnp.maximum(inc_dst.sum(axis=0), 1.0)[:, None]

        # ---- node channel: the zoo's sequence backbone ----
        x_full = jnp.concatenate(
            [x_t, jnp.repeat(x[:, None, :], W, axis=1)], axis=-1)
        seq = TokenEmbed(self.d_model)(x_full)
        for _ in range(self.n_layers):
            seq = AttentionBlock(self.d_model, self.n_heads,
                                 self.mlp_hidden)(seq)
        adj = inc_src.T @ inc_dst        # call topology from the edge list
        node_logit = ScoreHead(n_services=S, n_windows=W,
                               hidden=self.hidden)(seq, adj)

        # ---- edge channel: pooled tokens + contrast features ----
        pe = _pool_windows(edge_x) * m                 # [E, 12]
        sum_out = inc_src.T @ pe                       # [S, 12]
        sum_in = inc_dst.T @ pe
        n_out = inc_src.sum(axis=0)[:, None]
        n_in = inc_dst.sum(axis=0)[:, None]
        # exclusive sibling means: the callee's OTHER in-edges and the
        # caller's OTHER out-edges — "hot unlike my siblings" is the
        # pattern that separates a link fault from endpoint-wide heat
        excl_in = (inc_dst @ sum_in - pe) / jnp.maximum(
            inc_dst @ n_in - 1.0, 1.0)
        excl_out = (inc_src @ sum_out - pe) / jnp.maximum(
            inc_src @ n_out - 1.0, 1.0)
        node_pool = _pool_windows(x_t)                 # [S, 3Fn]
        e_in = jnp.concatenate(
            [pe, pe - excl_in, pe - excl_out,
             inc_src @ node_pool, inc_dst @ node_pool], axis=-1)
        h_e = nn.relu(nn.Dense(self.hidden)(e_in)) * m
        h_e = nn.relu(nn.Dense(self.hidden)(h_e)) * m
        edge_logit = nn.Dense(1)(h_e)[:, 0]
        edge_logit = jnp.where(mask, edge_logit, -1e9)

        # per-service edge evidence: hottest incident edge by direction (a
        # link fault is the caller's MAX out-edge; the callee side sees it
        # as its max in-edge) plus the out-mean (an edge-locus fault heats
        # ALL the culprit's out-edges, not one)
        def peak(inc):
            v = jnp.where(inc.T > 0, edge_logit[None, :], -1e9).max(axis=1)
            return jnp.where(v < -1e8, 0.0, v)
        out_peak, in_peak = peak(inc_src), peak(inc_dst)
        out_mean = (inc_src.T @ jnp.where(mask, edge_logit, 0.0)[:, None]
                    / deg_out)[:, 0]
        feats = jnp.stack([node_logit, out_peak, in_peak, out_mean,
                           out_peak - in_peak,
                           jnp.maximum(out_peak - in_peak, 0.0)], axis=-1)
        hid = nn.relu(nn.Dense(16)(feats))
        return nn.Dense(1)(jnp.concatenate([feats, hid], -1))[:, 0]
