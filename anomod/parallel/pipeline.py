"""Pipeline parallelism (pp): stage-sharded transformer over a ``pipe`` axis.

The TraceTransformer block stack is partitioned into one stage per device
along a 1-D ``pipe`` mesh: stage parameters are stacked with a leading
``[n_stages, layers_per_stage, ...]`` axis and sharded ``P('pipe')``, so each
device holds only its own layers' weights.  Microbatches stream through the
ring GPipe-style: every tick each device applies its stage to its activation
buffer and ``ppermute``s the result to the next device, while stage 0 feeds
the next microbatch and the last stage banks finished outputs.  The tick loop
is a ``lax.scan``, so reverse-mode AD derives the backward pipeline schedule
automatically (``ppermute`` transposes to the reverse rotation) — no
hand-written backward pass.

Embedding and head stay replicated outside the pipelined region (they are a
tiny fraction of the FLOPs); the block stack — where a transformer's memory
actually lives — is what pp exists to partition.

No reference counterpart (the reference has no distributed compute,
SURVEY.md §2.4); this is the pp plane of the tp/pp/dp/sp/ep story, next to
:mod:`anomod.parallel.train` (dp×tp), :mod:`anomod.parallel.replay`
(stream/dp), and :mod:`anomod.parallel.ring_attention` (sp).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from anomod.models.transformer import AttentionBlock, ScoreHead, TokenEmbed

AXIS = "pipe"


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_microbatches: int = 2
    layers_per_stage: int = 1
    d_model: int = 32
    n_heads: int = 2
    mlp_hidden: int = 64
    hidden: int = 32


def make_pipe_mesh(n_devices=None):
    from anomod.parallel.mesh import make_mesh
    return make_mesh(n_devices, axis=AXIS)


def _modules(cfg: PipelineConfig, S: int, W: int):
    return (TokenEmbed(cfg.d_model),
            AttentionBlock(cfg.d_model, cfg.n_heads, cfg.mlp_hidden),
            ScoreHead(S, W, cfg.hidden))


def pipeline_shardings(mesh, params):
    """Stage stack sharded over ``pipe``; embed/head replicated."""
    rep = NamedSharding(mesh, P())
    stage = NamedSharding(mesh, P(AXIS))
    tree = jax.tree_util.tree_map
    return {"embed": tree(lambda _: rep, params["embed"]),
            "stages": tree(lambda _: stage, params["stages"]),
            "head": tree(lambda _: rep, params["head"])}


def init_pipeline(rng, mesh, cfg: PipelineConfig, S: int, W: int, F: int):
    """Init + place params: ``{embed, stages[P, lps, ...], head}``."""
    n_stages = mesh.shape[AXIS]
    n_layers = n_stages * cfg.layers_per_stage
    embed, block, head = _modules(cfg, S, W)
    r_embed, r_blocks, r_head = jax.random.split(rng, 3)
    x0 = jnp.zeros((S, W, F), jnp.float32)
    p_embed = embed.init(r_embed, x0)
    seq0 = embed.apply(p_embed, x0)
    p_blocks = jax.vmap(lambda r: block.init(r, seq0))(
        jax.random.split(r_blocks, n_layers))
    p_stages = jax.tree_util.tree_map(
        lambda a: a.reshape(n_stages, cfg.layers_per_stage, *a.shape[1:]),
        p_blocks)
    p_head = head.init(r_head, seq0, jnp.zeros((S, S), jnp.float32))
    params = {"embed": p_embed, "stages": p_stages, "head": p_head}
    return jax.device_put(params, pipeline_shardings(mesh, params))


def make_pipeline_forward(mesh, cfg: PipelineConfig, S: int, W: int):
    """Returns ``(forward, reference_forward)``.

    Both map ``(params, x [B, S, W, F], adj [B, S, S]) -> [B, S]`` scores;
    ``forward`` runs the block stack through the stage ring,
    ``reference_forward`` applies the same stacked layers sequentially
    (the single-program oracle the pipeline must match exactly).
    """
    n_stages = mesh.shape[AXIS]
    embed, block, head = _modules(cfg, S, W)
    L, M = S * W, cfg.n_microbatches

    def stage_fwd(stage_params, x):          # [lps, ...] params, [mb, L, d]
        def body(h, p):
            return jax.vmap(lambda s: block.apply(p, s))(h), None
        h, _ = lax.scan(body, x, stage_params)
        return h

    def _varying(x):
        return lax.pcast(x, (AXIS,), to="varying")

    def pipeline_local(stage_params, micro):
        # stage_params leading [1, lps, ...] (my shard); micro [M, mb, L, d]
        params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        idx = lax.axis_index(AXIS)
        T = M + n_stages - 1
        micro = _varying(micro)
        state0 = _varying(jnp.zeros(micro.shape[1:], micro.dtype))
        out0 = jnp.zeros_like(micro)         # inherits micro's vma
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (clamped in the drain phase,
            # whose outputs never get banked); later stages consume what
            # their predecessor ppermuted over last tick
            inp = jnp.where(idx == 0, micro[jnp.minimum(t, M - 1)], state)
            y = stage_fwd(params, inp)
            j = t - (n_stages - 1)           # microbatch finishing this tick
            jc = jnp.clip(j, 0, M - 1)
            bank = (idx == n_stages - 1) & (j >= 0)
            out = out.at[jc].set(jnp.where(bank, y, out[jc]))
            state = lax.ppermute(y, AXIS, perm)
            return (state, out), None

        (_, out), _ = lax.scan(tick, (state0, out0), jnp.arange(T))
        # finished outputs live on the last stage; psum broadcasts them
        mask = (idx == n_stages - 1).astype(micro.dtype)
        return lax.psum(out * mask, AXIS)

    pipe = jax.shard_map(pipeline_local, mesh=mesh,
                         in_specs=(P(AXIS), P()), out_specs=P())

    def _embed_all(params, x):
        return jax.vmap(lambda xi: embed.apply(params["embed"], xi))(x)

    def _head_all(params, seq, adj):
        return jax.vmap(lambda s, a: head.apply(params["head"], s, a))(
            seq, adj)

    def forward(params, x, adj):
        seq = _embed_all(params, x)                      # [B, L, d]
        B = seq.shape[0]
        assert B % M == 0, f"batch {B} must divide into {M} microbatches"
        micro = seq.reshape(M, B // M, L, cfg.d_model)
        out = pipe(params["stages"], micro).reshape(B, L, cfg.d_model)
        return _head_all(params, out, adj)

    def reference_forward(params, x, adj):
        seq = _embed_all(params, x)
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, *a.shape[2:]), params["stages"])
        return _head_all(params, stage_fwd(flat, seq), adj)

    return forward, reference_forward


def make_pipeline_train_step(mesh, cfg: PipelineConfig, sample_batch: dict,
                             lr: float = 1e-3):
    """(params, opt_state, step, put_batch) — pp train step on chaos labels.

    ``sample_batch``: stacked batch from :func:`anomod.rca._stack`; the
    fused (temporal + static) features feed the pipelined transformer, loss
    matches the RCA harness (CE over culprit services + detection BCE).
    """
    import optax

    from anomod.rca import rca_loss

    S, W = sample_batch["x_t"].shape[1:3]
    F = sample_batch["x_t"].shape[3] + sample_batch["x"].shape[2]
    forward, _ = make_pipeline_forward(mesh, cfg, S, W)
    params = init_pipeline(jax.random.PRNGKey(0), mesh, cfg, S, W, F)
    tx = optax.adamw(lr)
    opt_state = tx.init(params)

    def _fused(batch):
        return jnp.concatenate(
            [batch["x_t"],
             jnp.repeat(batch["x"][:, :, None, :], W, axis=2)], axis=-1)

    def loss_fn(params, batch):
        scores = forward(params, _fused(batch), batch["adj"])
        return rca_loss(scores, batch)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rep = NamedSharding(mesh, P())

    def put_batch(batch_np):
        return {k: jax.device_put(jnp.asarray(v), rep)
                for k, v in batch_np.items()}

    return params, opt_state, step, put_batch
