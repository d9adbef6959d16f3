"""A block-diffusion decoder with grouped-query attention and softmax-routed
experts over event tokens (the ``sdar_moe`` family's layers), scoring
each token by the family's own decoding loop, teacher-forced.

Layers: pre-norm RMSNorm, ``h <- h + W_o attention(RMSNorm(h))`` with a
per-head RMSNorm on queries and keys before the rotation (rotate-half on
the whole head, ``default`` rotary at ``rope_theta``), query head ``j``
reading key-value head ``j // (heads / kv)``; then ``h <- h + sum_e w_e
SwiGLU_e(RMSNorm(h))`` over the top-k of a float32 softmax router over all
experts, the chosen weights renormalised (``norm_topk_prob``), no shared
expert.  Untied embedding and head; bfloat16 weights, activations and
cache, float32 accumulation, softmax, router, norms and rotary tables.

The sequence is cut into blocks of ``block_length`` tokens; attention is
causal across blocks and bidirectional inside one.  Token ``j`` of block
``b`` is scored by one ROUND of ``block_length`` rows at the block's
positions whose inputs are the true tokens at offsets ``< j`` and the mask
token at the others: each row sees every cached key of the session's
earlier blocks and the round's own rows, and the token's surprisal is
``-log softmax(head(row j))[token]`` (no shift).  When a block is whole,
one COMMIT of its clean tokens writes its keys and values (after the
rotation) into the block-paged pool; its rows see the same prefix and
each other.  Rounds write nothing.  A token's score is a function of its
session's tokens alone, however they fall into steps: a block's earlier
tokens of an earlier step are fed again (the plane keeps them,
:class:`anomod.serve.seqplane.Session`).

:func:`append_step` is the serving step over a packed batch of rows: each
diffusion block a step touches is its commit (if it is whole) and then
the rounds of its new tokens, ``block_length`` rows a group, groups
aligned.  The cached prefix through :mod:`anomod.ops.gqa_attention`'s
block-causal form (each row's last visible position from the plan; the
kernel returns its log-sum-exp), the in-block part dense, merged by
log-sum-exp; the experts by grouped matmul, a step's pairs in one round.
The plain reference is the benchmark's
(``benchmark/reference/block_diffusion_moe_decoder.py``).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from anomod.models import seqcommon, swa_moe
from anomod.models.seqcommon import HEAD_SCOPE, PROJ_SCOPE, rmsnorm
from anomod.ops import gqa_attention as ga
from anomod.ops import routed_experts as rx

#: the call name of the in-block part and its merge with the kernel's
INBLOCK_SCOPE = "anomod_seq_inblock"
#: logits rows a step's plan keeps for the audit tenants
AUDIT_ROWS = 64


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    rms_norm_eps: float
    vocab_size: int
    vocab_held: int
    experts_held: int
    block_length: int
    mask_token_id: int
    experts_lo: int = 0
    context_tokens: int = 8192
    block_tokens: int = 128
    pool_tokens: int = 65536

    @classmethod
    def from_dict(cls, d: dict) -> "BlockDiffusionConfig":
        """From a configuration file: the public keys at the top level,
        the sizes and the decoding loop's numbers this repo set under
        ``assumed``."""
        flat = seqcommon.flat_spec(d)
        cfg = cls(**{f.name: flat[f.name] for f in dataclasses.fields(cls)
                     if f.name in flat})
        for key, want in (("attention_bias", False),
                          ("decoder_sparse_step", 1),
                          ("mlp_only_layers", []), ("rope_scaling", None),
                          ("use_sliding_window", False),
                          ("tie_word_embeddings", False),
                          ("denoising_steps", cfg.block_length)):
            if flat.get(key, want) != want:
                raise ValueError(f"{key} = {flat[key]!r} is not written here "
                                 f"(only {want!r})")
        L = cfg.block_length
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("the heads are no multiple of the key-value "
                             "heads")
        if ga.Q_TILE % L or cfg.block_tokens % L:
            raise ValueError(f"a diffusion block of {L} tokens does not "
                             f"divide {ga.Q_TILE} rows or a cache block")
        if cfg.context_tokens % cfg.block_tokens:
            raise ValueError("context_tokens is no multiple of block_tokens")
        if cfg.kv_row_width % 128:
            raise ValueError("a K/V row is no multiple of 128 columns")
        if not 0 <= cfg.mask_token_id < cfg.vocab_held:
            raise ValueError("the mask token lies outside the vocabulary "
                             "held")
        return cfg

    @property
    def kv_row_width(self) -> int:
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def pool_blocks(self) -> int:
        """Blocks of the pool, the never-allocated block 0 among them."""
        return self.pool_tokens // self.block_tokens

    @property
    def session_blocks(self) -> int:
        return self.context_tokens // self.block_tokens


# -- parameters ---------------------------------------------------------------

F32_LEAVES = ("router", "norm", "q_norm", "k_norm", "mlp_norm", "final_norm")


def param_shapes(cfg: BlockDiffusionConfig) -> dict:
    """name -> (shape, rule of the seeded draw); a group of leaves a
    layer, its attention's and then its experts'."""
    D, H, kv, hd = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    E, F = cfg.experts_held, cfg.moe_intermediate_size
    out = {"embed": ((cfg.vocab_held, D), 1),
           "head": ((D, cfg.vocab_held), D), "final_norm": ((D,), None)}
    for i in range(cfg.num_hidden_layers):
        out[swa_moe.layer_name(i)] = {
            "norm": ((D,), None), "w_q": ((D, H, hd), D),
            "w_k": ((D, kv, hd), D), "w_v": ((D, kv, hd), D),
            "q_norm": ((hd,), None), "k_norm": ((hd,), None),
            "w_o": ((H, hd, D), H * hd), "mlp_norm": ((D,), None),
            "router": ((D, cfg.num_experts), D),
            "e_gate": ((E, D, F), D), "e_up": ((E, D, F), D),
            "e_down": ((E, F, D), F)}
    return out


def init_params(cfg: BlockDiffusionConfig, seed: int, dtype=None) -> dict:
    """Seeded weights by the plane's rule
    (:func:`anomod.models.seqcommon.draw_params`)."""
    import jax.numpy as jnp
    return seqcommon.draw_params(
        param_shapes(cfg), seed, dtype or jnp.bfloat16, F32_LEAVES)


def param_count(cfg: BlockDiffusionConfig) -> int:
    return seqcommon.param_count(param_shapes(cfg))


# -- the plan -----------------------------------------------------------------

def units(cfg: BlockDiffusionConfig, start: int, n: int) -> tuple:
    """``(new tokens, rows)`` of each diffusion block that ``n`` tokens
    appended at ``start`` touch, in order: a round a new token and, where
    the block ends among them, its commit."""
    L = cfg.block_length
    b = np.arange(start // L, (start + n - 1) // L + 1)
    new = np.minimum((b + 1) * L, start + n) - np.maximum(b * L, start)
    whole = (b + 1) * L <= start + n
    return new, L * (new + whole)


def fit(cfg: BlockDiffusionConfig, start: int, n: int, room: int) -> tuple:
    """``(tokens, rows)`` of the longest head of ``n`` tokens appended at
    ``start`` that ends at a diffusion block's edge (or at the last token)
    and whose rows fit ``room``."""
    new, rows = units(cfg, start, n)
    k = int(np.searchsorted(np.cumsum(rows), room, side="right"))
    return int(new[:k].sum()), int(rows[:k].sum())


def plan_caps(cfg: BlockDiffusionConfig, rows: int, segments: int) -> dict:
    """Static sizes of a step's plan at ``rows`` packed rows: a scored
    token takes a round of ``block_length`` of them at least."""
    seg = min(rows, segments)
    return dict(tokens=rows, segments=seg, scored=rows // cfg.block_length,
                audit=AUDIT_ROWS, items=ga.items_needed(seg, rows))


def empty_plan(cfg: BlockDiffusionConfig, caps: dict, trash_row: int) -> dict:
    """A plan of no work at ``caps`` (numpy, int32): every row a pad that
    writes the never-allocated block 0, sees no cached key and is scored
    by no one; an item of no row."""
    T, S = caps["tokens"], caps["scored"]
    z = lambda n: np.zeros((n,), np.int32)
    return {"tok_id": z(T), "tok_pos": z(T),
            "tok_seg": np.full((T,), -1, np.int32), "tok_slot": z(T),
            "tok_lim": np.full((T,), -1, np.int32), "tok_commit": z(T),
            "score_row": z(S), "score_tok": z(S), "audit": z(caps["audit"]),
            "items": ga.empty_items(caps["items"], ga.blocks_needed(
                cfg.session_blocks, cfg.block_tokens))}


def build_plan(cfg: BlockDiffusionConfig, caps: dict, segments: list,
               tokens: np.ndarray, tenant_ids: np.ndarray,
               audit: frozenset) -> tuple:
    """A step's plan for ``segments`` ``(tenant, session number, start, n,
    blocks, open block)`` whose new tokens are packed in order in
    ``tokens``; ``open block``: the ids of the session's tokens from the
    start of ``start``'s diffusion block to ``start``.  Rows of a segment
    are one chunk of the kernel's work list; a block is its commit (if
    whole) and then a round a new token.  Returns ``(plan, stats,
    audit_rows)``: the kept logits rows are those that scored the last
    token of an audit tenant's last segment in the step."""
    del tenant_ids                        # no hidden state is carried
    L, B, M = cfg.block_length, cfg.block_tokens, cfg.mask_token_id
    plan = empty_plan(cfg, caps, 0)
    S = len(segments)
    if not S:
        return plan, {"seq_tokens": 0}, []
    tenant = np.asarray([s[0] for s in segments], np.int64)
    start = np.asarray([s[2] for s in segments], np.int64)
    n = np.asarray([s[3] for s in segments], np.int64)
    seg_blocks = np.zeros((S, cfg.session_blocks), np.int32)
    ids, at = [], 0
    for s, (_, _, st, k, blocks, *rest) in enumerate(segments):
        seg_blocks[s, :len(blocks)] = blocks
        head = np.asarray(rest[0] if rest else (), np.int32)
        head = head[len(head) - st % L:] if st % L else head[:0]
        ids.append(np.concatenate([head, tokens[at:at + k]]).astype(np.int32))
        at += k
    # the diffusion blocks touched, each with its new tokens' offsets
    b0, b1 = start // L, (start + n - 1) // L
    per = b1 - b0 + 1
    u_seg = np.repeat(np.arange(S), per)
    u_b = b0[u_seg] + np.arange(per.sum()) - np.repeat(np.cumsum(per) - per,
                                                       per)
    j0 = np.maximum(start[u_seg] - u_b * L, 0)
    j1 = np.minimum(start[u_seg] + n[u_seg] - u_b * L, L)  # past the last
    whole = (j1 == L).astype(np.int64)
    groups = whole + j1 - j0
    # rows: a unit's groups back to back, L rows a group
    g_unit = np.repeat(np.arange(len(u_b)), groups)
    g_k = np.arange(len(g_unit)) - np.repeat(np.cumsum(groups) - groups,
                                             groups)
    commit = (g_k < whole[g_unit])
    j = j0[g_unit] + g_k - whole[g_unit]             # a round's offset
    r_g = np.repeat(np.arange(len(g_unit)), L)
    i = np.tile(np.arange(L), len(g_unit))
    r_u, r_seg = g_unit[r_g], u_seg[g_unit[r_g]]
    pos = u_b[r_u] * L + i
    seq = np.concatenate(ids)
    lens = np.asarray([len(x) for x in ids], np.int64)
    seq_off = np.cumsum(lens) - lens
    # a row's place in its segment's ids (past the last: a masked input)
    known = (u_b[r_u] - b0[r_seg]) * L + i
    tok = seq[seq_off[r_seg] + np.minimum(known, lens[r_seg] - 1)]
    is_commit = commit[r_g]
    tok = np.where(is_commit | (i < j[r_g]), tok, M)
    R = len(r_g)
    if R > caps["tokens"]:
        raise ValueError(f"{R} rows for a plan of {caps['tokens']}")
    plan["tok_id"][:R] = tok
    plan["tok_pos"][:R] = pos
    plan["tok_seg"][:R] = r_seg
    plan["tok_slot"][:R] = np.where(
        is_commit, seg_blocks[r_seg, pos // B] * B + pos % B, 0)
    lim = u_b[r_u] * L - 1
    plan["tok_lim"][:R] = lim
    plan["tok_commit"][:R] = is_commit
    scored = np.nonzero(~is_commit & (i == j[r_g]))[0]
    plan["score_row"][:len(scored)] = scored
    plan["score_tok"][:len(scored)] = seq[seq_off[r_seg[scored]]
                                          + known[scored]]
    rows_of = np.bincount(r_seg, minlength=S).astype(np.int64)
    off = np.cumsum(rows_of) - rows_of
    n_items = ga.fill_items(plan["items"], start, rows_of, off, seg_blocks,
                            B, limits=lim)
    # the audit rows: the row that scored a segment's last token, of the
    # last segment of an audit tenant in this step
    last = np.ones(S, bool)
    last[:-1] = tenant[1:] != tenant[:-1]
    score_seg = r_seg[scored]
    last_scored = np.searchsorted(score_seg, np.arange(S), side="right") - 1
    kept = [s for s in range(S) if last[s] and int(tenant[s]) in audit][
        :caps["audit"]]
    for a, s in enumerate(kept):
        plan["audit"][a] = scored[last_scored[s]]
    audit_rows = [(int(tenant[s]), segments[s][1], int(start[s] + n[s] - 1))
                  for s in kept]
    n_commit = int(is_commit.sum())
    stats = {"seq_tokens": len(scored), "bd_round_rows": R - n_commit,
             "bd_commit_rows": n_commit,
             "bd_blocks_committed": n_commit // L,
             "bd_prefix_pairs": int((lim + 1).sum()),
             "bd_prefix_keys": int(np.maximum.reduceat(lim, off).sum()
                                   + S),
             "bd_inblock_pairs": R * L, "bd_items": n_items,
             "moe_one_round_layers": cfg.num_hidden_layers * rx.one_round(
                 caps["tokens"], cfg.num_experts_per_tok,
                 expert_capacity(cfg, caps["tokens"]))}
    return plan, stats, audit_rows


# -- the serving step ---------------------------------------------------------

def init_state(cfg: BlockDiffusionConfig, n_tenants: int, dtype=None) -> dict:
    """The donated device state: the K/V pool (block 0 is never allocated
    and takes the writes of pads and rounds; a row is a token's keys then
    its values, ``2 * kv * head_dim`` columns).  No hidden state is
    carried between steps."""
    import jax.numpy as jnp
    del n_tenants
    return {"pool": jnp.zeros(
        (cfg.num_hidden_layers, cfg.pool_blocks, cfg.block_tokens,
         cfg.kv_row_width), dtype or jnp.bfloat16)}


def inblock_see(plan: dict, block_length: int):
    """``[groups, L, L]``: row ``i`` of a group of ``block_length`` rows
    sees row ``j`` of it, every real row of its own group (bidirectional:
    a round's, a commit's) by the step's ``plan``."""
    valid = plan["tok_seg"] >= 0
    G = valid.shape[0] // block_length
    return valid.reshape(G, 1, block_length).repeat(block_length, axis=1)


def inblock_merge(cfg: BlockDiffusionConfig, q, k, v, see, o_pre, lse_pre):
    """Each group of ``block_length`` rows attends over its own rows (those
    ``see`` ``[groups, L, L]`` lets it), merged with the kernel's part
    over the cached prefix by log-sum-exp.  ``q`` ``[T, H, d]``, ``k`` /
    ``v`` ``[T, kv, d]``; returns ``[T, H, d]`` float32."""
    import jax.numpy as jnp
    f32 = jnp.float32
    T, H, d = q.shape
    L, kv = cfg.block_length, cfg.num_key_value_heads
    G, R = T // L, H // kv
    qg = q.reshape(G, L, kv, R, d)
    s = jnp.einsum("gikrd,gjkd->gkrij", qg, k.reshape(G, L, kv, d),
                   preferred_element_type=f32) * cfg.head_dim ** -0.5
    s = jnp.where(see[:, None, None], s, ga.NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    lse_in = (m + jnp.log(p.sum(axis=-1, keepdims=True)))[..., 0]
    o_in = jnp.einsum("gkrij,gjkd->gikrd", p.astype(v.dtype),
                      v.reshape(G, L, kv, d), preferred_element_type=f32)
    o_in = o_in / p.sum(axis=-1).transpose(0, 3, 1, 2)[..., None]
    lse_in = lse_in.transpose(0, 3, 1, 2).reshape(T, H)
    top = jnp.maximum(lse_pre, lse_in)
    a, b = jnp.exp(lse_pre - top), jnp.exp(lse_in - top)
    return ((a[..., None] * o_pre.astype(f32)
             + b[..., None] * o_in.reshape(T, H, d))
            / (a + b)[..., None])


def attention(cfg: BlockDiffusionConfig, lp: dict, u, plan: dict, pool,
              row0: int, rope):
    """One attention sub-layer over the packed rows ``u`` ``[T, D]``
    (normed): project, norm a head, rotate, write the commit rows' keys
    and values into ``pool`` (this layer's rows from ``row0``; rounds and
    pads into block 0), attend over the cached prefix (the kernel) and the
    group's own rows, project out.  Returns ``(out [T, D] float32,
    pool)``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T = u.shape[0]
    eps = cfg.rms_norm_eps
    dot = lambda a, b, spec: jnp.einsum(spec, a, b,
                                        preferred_element_type=f32)

    def project(u, slot, pool):
        q = rmsnorm(dot(u, lp["w_q"], "td,dhk->thk"), lp["q_norm"], eps)
        k = rmsnorm(dot(u, lp["w_k"], "td,dgk->tgk"), lp["k_norm"], eps)
        q = swa_moe.rotate(q, *rope).astype(u.dtype)
        k = swa_moe.rotate(k, *rope).astype(u.dtype)
        v = dot(u, lp["w_v"], "td,dgk->tgk").astype(u.dtype)
        return q, k, v, seqcommon.write_rows(
            pool, row0 * cfg.block_tokens + slot,
            jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], axis=1))

    q, k, v, pool = jax.named_call(project, name=PROJ_SCOPE)(
        u, plan["tok_slot"], pool)
    # one jitted function for every layer (the layer's first pool row is
    # an argument): the step's program traces and lowers the kernel once
    o_pre, lse_pre = jax.named_call(
        jax.jit(ga.append_attention, static_argnums=(4, 5, 6)),
        name=ga.BD_SCOPE)(q, pool, plan["items"], jnp.int32(row0),
                          cfg.num_key_value_heads, cfg.head_dim ** -0.5,
                          cfg.block_tokens, None, plan["tok_lim"])
    o = jax.named_call(
        lambda q, k, v, plan, *pre: inblock_merge(
            cfg, q, k, v, inblock_see(plan, cfg.block_length),
            *pre).astype(u.dtype),
        name=INBLOCK_SCOPE)(q, k, v, plan, o_pre, lse_pre)
    out = jax.named_call(lambda o: dot(o, lp["w_o"], "thk,hkd->td"),
                         name=PROJ_SCOPE)(o)
    return out, pool


def head_scores(cfg: BlockDiffusionConfig, params: dict, x, plan: dict):
    """``(surprisal [scored] float32, audit logits [A, vocab_held]
    float32)`` from the last layer's rows: a scored row's logits over the
    vocabulary slice read its own token, no shift."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = cfg.rms_norm_eps

    def score(x, params, rows, toks, audit):
        hs = rmsnorm(x[rows], params["final_norm"], eps)
        S = hs.shape[0]
        chunk = math.gcd(S, 512)

        def one(args):
            h, t = args
            logits = jnp.dot(h, params["head"], preferred_element_type=f32)
            return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, t[:, None], axis=1)[:, 0]

        surprisal = jax.lax.map(one, (hs.reshape(S // chunk, chunk, -1),
                                      toks.reshape(S // chunk, chunk)))
        kept = jnp.dot(rmsnorm(x[audit], params["final_norm"], eps),
                       params["head"], preferred_element_type=f32)
        return surprisal.reshape(S), kept

    return jax.named_call(score, name=HEAD_SCOPE)(
        x, params, plan["score_row"], plan["score_tok"], plan["audit"])


def expert_capacity(cfg: BlockDiffusionConfig, rows: int) -> int:
    """Rows a round of the routed experts takes in a step of ``rows``
    packed rows: every pair of the step."""
    return rows * cfg.num_experts_per_tok


def append_step(cfg: BlockDiffusionConfig, params: dict, state: dict,
                plan: dict):
    """One forward over the packed rows of a step.

    ``state`` (:func:`init_state`) is updated in place where the caller
    donates it.  Returns ``(state, surprisal [scored] float32, audit
    logits [A, vocab_held] float32, rows per held expert [layers,
    experts_held] int32)``.  The experts take a step's row-expert pairs in
    ONE round (``capacity`` is all of them)."""
    import jax
    import jax.numpy as jnp
    T = plan["tok_id"].shape[0]
    eps = cfg.rms_norm_eps
    shape = state["pool"].shape
    pool = state["pool"].reshape((-1,) + shape[2:])
    valid = plan["tok_seg"] >= 0
    freq, _ = swa_moe.rope_table({"rope_type": "default",
                                  "rope_theta": cfg.rope_theta},
                                 cfg.head_dim)
    rope = jax.named_call(lambda ang: (jnp.cos(ang), jnp.sin(ang)),
                          name=PROJ_SCOPE)(
        plan["tok_pos"].astype(jnp.float32)[:, None] * jnp.asarray(freq))
    x = params["embed"][plan["tok_id"]]
    counts = []
    for layer in range(cfg.num_hidden_layers):
        lp = params[swa_moe.layer_name(layer)]
        y, pool = attention(cfg, lp, rmsnorm(x, lp["norm"], eps), plan, pool,
                            layer * cfg.pool_blocks, rope)
        x = x + y.astype(x.dtype)
        u = rmsnorm(x, lp["mlp_norm"], eps)
        experts, weights = rx.route(
            u, lp["router"], None, cfg.num_experts_per_tok, 1.0,
            cfg.norm_topk_prob, score="softmax")
        y, n = rx.held_expert_sum(
            u, experts, weights, valid, rx.gated_silu,
            (lp["e_gate"], lp["e_up"], lp["e_down"]), cfg.experts_lo,
            expert_capacity(cfg, T))
        counts.append(n)
        x = x + y.astype(x.dtype)
    surprisal, audit = head_scores(cfg, params, x, plan)
    return {"pool": pool.reshape(shape)}, surprisal, audit, jnp.stack(counts)
