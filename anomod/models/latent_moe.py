"""A latent-attention, routed-expert decoder over event tokens.

Pre-norm RMSNorm decoder, untied embedding and head, bfloat16 weights and
activations with float32 accumulation, float32 softmax, router and norms.
Attention is multi-head LATENT attention: queries through a low-rank
bottleneck, keys and values through one shared latent ``c_kv`` plus one
rotary key ``k_pe`` for all heads, so the cache holds ``latent + rope``
values a token a layer and nothing per head.  The leading layers are dense
SwiGLU; the rest route every token to its top-k of ``n_routed`` experts
(sigmoid scores, a bias that only chooses, normalised and scaled weights)
and add a shared expert.  The layer is told which experts it holds
(``experts_lo``, ``experts_held``) and which slice of the vocabulary
(``vocab_held``): a chip's share of a deployment.  Keys of the
configuration are the public ``config.json``'s.

Two forwards:

- :func:`append_step`: the serving step.  A packed batch of appended
  chunks of many sessions against a block-paged latent pool, written in
  place; attention in two forms chosen by size
  (:mod:`anomod.ops.latent_attention`), experts by grouped matmul
  (:mod:`anomod.ops.routed_experts`).
- :func:`reference_logits`: the plain float32 forward of one whole
  session, no cache, no paging, a loop over experts.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from anomod.models import seqcommon
from anomod.models.seqcommon import MLP_SCOPE, PROJ_SCOPE
from anomod.models.seqcommon import rmsnorm  # noqa: F401  (the layers' norm)
from anomod.ops import latent_attention as la
from anomod.ops import routed_experts as rx


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    first_k_dense_replace: int
    num_hidden_layers: int
    rms_norm_eps: float
    rope_theta: float
    rope_scaling: tuple                # sorted (key, value) pairs, or ()
    vocab_size: int
    vocab_held: int
    experts_held: int
    experts_lo: int = 0
    context_tokens: int = 8192
    block_tokens: int = 128
    pool_tokens: int = 65536

    @classmethod
    def from_dict(cls, d: dict) -> "DecoderConfig":
        """From a configuration file: the public keys at the top level,
        the sizes this repo set under ``assumed``."""
        flat = seqcommon.flat_spec(d)
        kw = {f.name: flat[f.name] for f in dataclasses.fields(cls)
              if f.name in flat}
        kw["rope_scaling"] = tuple(sorted(
            (flat.get("rope_scaling") or {}).items()))
        cfg = cls(**kw)
        if d.get("scoring_func", "sigmoid") != "sigmoid" \
                or d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
            raise ValueError("only sigmoid routing without group limits "
                             "is written here")
        if cfg.context_tokens % cfg.block_tokens:
            raise ValueError("context_tokens is no multiple of block_tokens")
        return cfg

    @property
    def n_dense(self) -> int:
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    @property
    def n_moe(self) -> int:
        return self.num_hidden_layers - self.n_dense

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row_width(self) -> int:
        """Columns a token's latent is held at in the pool: a multiple of
        the 128-lane tile, or the device lays the pool out token-minor
        and every in-place write copies it whole (PERF.md, PR 27/28)."""
        return -(-self.latent_width // 128) * 128

    @property
    def pool_blocks(self) -> int:
        """Blocks of the pool, the never-allocated block 0 among them."""
        return self.pool_tokens // self.block_tokens

    @property
    def session_blocks(self) -> int:
        return self.context_tokens // self.block_tokens


# -- rotary positions ---------------------------------------------------------

def _yarn_mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def rope_inv_freq(cfg: DecoderConfig) -> np.ndarray:
    """``[rope / 2]`` float32 inverse frequencies, YaRN-interpolated where
    the configuration scales its rope (as the public implementation: the
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original context; equal ends get 0.001 added)."""
    dim = cfg.qk_rope_head_dim
    base = float(cfg.rope_theta)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = dict(cfg.rope_scaling)
    if not sc:
        return extra.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def turn_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turn_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(turn_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return (extra / sc["factor"] * ramp + extra * (1 - ramp)).astype(
        np.float32)


def softmax_scale(cfg: DecoderConfig) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = dict(cfg.rope_scaling)
    if sc:
        s *= _yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0)) ** 2
    return s


def _rope_amplitude(cfg: DecoderConfig) -> float:
    sc = dict(cfg.rope_scaling)
    if not sc:
        return 1.0
    return _yarn_mscale(sc["factor"], sc.get("mscale", 1)) \
        / _yarn_mscale(sc["factor"], sc.get("mscale_all_dim", 0))


def rope(x, pos, inv_freq, amplitude: float = 1.0):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the last axis
    by ``pos * inv_freq[i]``; the result holds the first components, then
    the second (the public implementation's layout; keys and queries
    alike, so their products are the pairwise rotation's).  ``x`` ``[...,
    T, (H,) rope]`` with ``pos`` ``[T]``; float32 inside."""
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: DecoderConfig) -> dict:
    """name -> (shape, fan_in or None for a norm weight, "f32" where the
    leaf stays float32); the stacks carry their layer axis first."""
    D, H = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kvw = cfg.qk_nope_head_dim + cfg.v_head_dim
    F, Fs = cfg.moe_intermediate_size, \
        cfg.moe_intermediate_size * cfg.n_shared_experts

    def attn(n):
        return {
            "attn_norm": ((n, D), None), "ffn_norm": ((n, D), None),
            "w_qa": ((n, D, cfg.q_lora_rank), D),
            "q_norm": ((n, cfg.q_lora_rank), None),
            "w_qb": ((n, cfg.q_lora_rank, H, qk), cfg.q_lora_rank),
            "w_kva": ((n, D, cfg.latent_width), D),
            "kv_norm": ((n, cfg.kv_lora_rank), None),
            "w_kvb": ((n, cfg.kv_lora_rank, H, kvw), cfg.kv_lora_rank),
            "w_o": ((n, H, cfg.v_head_dim, D), H * cfg.v_head_dim)}

    out = {"embed": ((cfg.vocab_held, D), 1),
           "head": ((D, cfg.vocab_held), D),
           "final_norm": ((D,), None), "dense": {}, "moe": {}}
    if cfg.n_dense:
        n, I = cfg.n_dense, cfg.intermediate_size
        out["dense"] = dict(attn(n), w_gate=((n, D, I), D),
                            w_up=((n, D, I), D), w_down=((n, I, D), I))
    if cfg.n_moe:
        n, E = cfg.n_moe, cfg.experts_held
        out["moe"] = dict(
            attn(n), router=((n, D, cfg.n_routed_experts), D),
            router_bias=((n, cfg.n_routed_experts), "bias"),
            e_gate=((n, E, D, F), D), e_up=((n, E, D, F), D),
            e_down=((n, E, F, D), F), s_gate=((n, D, Fs), D),
            s_up=((n, D, Fs), D), s_down=((n, Fs, D), Fs))
    return out


F32_LEAVES = ("router", "router_bias", "attn_norm", "ffn_norm", "q_norm",
              "kv_norm", "final_norm")


def init_params(cfg: DecoderConfig, seed: int, dtype=None) -> dict:
    """Seeded weights made on the device in one program by the plane's
    rule (:func:`anomod.models.seqcommon.draw_params`): a matrix over
    ``fan_in ** 0.5``, a norm weight ``1 + 0.1 u``, the router's bias
    ``0.1 u``.  A stack's experts are the held experts of THIS draw only
    (another share is another seed)."""
    import jax.numpy as jnp
    return seqcommon.draw_params(
        param_shapes(cfg), seed, dtype or jnp.bfloat16, F32_LEAVES,
        groups=("dense", "moe"))


def param_count(cfg: DecoderConfig) -> int:
    return seqcommon.param_count(param_shapes(cfg))


# -- the serving step ---------------------------------------------------------

#: the name of the call that holds the append-attention kernels (both forms
#: and the absorption of the query): every device op of theirs carries it
#: in the trace's op metadata, which is how a reduction finds them
ATTENTION_SCOPE = "anomod_seq_mla"


def plan_caps(cfg: DecoderConfig, tokens: int, segments: int) -> dict:
    """Static sizes of a step's plan at ``tokens`` packed tokens."""
    seg = min(tokens, segments)
    groups = -(-(tokens // la.GROUP + seg) // la.BATCH) * la.BATCH
    return {"tokens": tokens, "segments": seg, "groups": groups,
            "pairs": 256, "audit": 64}


def empty_plan(cfg: DecoderConfig, caps: dict, trash_row: int) -> dict:
    """A plan of no work at ``caps`` (numpy, int32): every token a pad
    that writes the never-read slot 0 and reads nothing; every segment
    leaves its hidden state in ``trash_row`` of ``h_last``."""
    T, G, P = (caps[k] for k in ("tokens", "groups", "pairs"))
    z = lambda n: np.zeros((n,), np.int32)
    return dict(
        seqcommon.empty_token_plan(T, caps["segments"], cfg.session_blocks,
                                   caps["audit"], trash_row),
        tok_expanded=z(T),
        groups={"tok0": z(G), "ntok": z(G), "seg": z(G), "nblk": z(G),
                "n_groups": np.int32(0)},
        pairs={"seg": z(P), "q0": z(P), "n_tiles": z(P), "blk0": z(P),
               "n_pairs": np.int32(0)})


def _pad_rows(a, n):
    import jax.numpy as jnp
    return jnp.concatenate([a, jnp.zeros((n,) + a.shape[1:], a.dtype)])


def attention_block(cfg: DecoderConfig, lp: dict, h, plan: dict, pool,
                    layer, inv_freq):
    """One layer's latent attention for the packed tokens ``h`` ``[T, D]``:
    project, write the new latents into ``pool`` (``[layers * blocks,
    block, pool_row_width]``, this layer's rows at ``layer * blocks``),
    attend in both forms, project out.  Returns ``(out [T, D], pool)``."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    f32 = jnp.float32
    dot = lambda a, b, spec: jnp.einsum(spec, a, b,
                                        preferred_element_type=f32)
    pos = plan["tok_pos"]
    amp = _rope_amplitude(cfg)
    rows = layer * cfg.pool_blocks
    scale = softmax_scale(cfg)

    def project(h, pos, slot, pool):
        c_q = rmsnorm(dot(h, lp["w_qa"], "td,dr->tr").astype(h.dtype),
                      lp["q_norm"], cfg.rms_norm_eps)
        q = dot(c_q, lp["w_qb"], "tr,rhk->thk").astype(h.dtype)
        kva = dot(h, lp["w_kva"], "td,dc->tc").astype(h.dtype)
        lat = jnp.concatenate([
            rmsnorm(kva[:, :R], lp["kv_norm"], cfg.rms_norm_eps),
            rope(kva[:, R:], pos, inv_freq, amp),
            jnp.zeros((T, cfg.pool_row_width - cfg.latent_width), h.dtype)],
            axis=1)
        pool = seqcommon.write_rows(pool, rows * cfg.block_tokens + slot, lat)
        return q[..., :nope], rope(q[..., nope:], pos, inv_freq, amp), pool

    q_nope, q_pe, pool = jax.named_call(project, name=PROJ_SCOPE)(
        h, pos, plan["tok_slot"], pool)
    blocks = plan["seg_blocks"] + rows

    def forms(q_nope, q_pe, pos, seg, expanded, pool, blocks, groups, pairs,
              w_kvb):
        # the absorbed form's query at the pool's row width, in its two
        # parts: through W_kvb's key half, and the rope part zero-padded
        q_lat = dot(_pad_rows(q_nope, la.GROUP), w_kvb[..., :nope],
                    "thn,chn->thc").astype(h.dtype)
        q_rope = jnp.pad(q_pe, ((0, la.GROUP), (0, 0),
                                (0, cfg.pool_row_width - cfg.latent_width)))
        o_abs = la.absorbed_attention(
            q_lat, q_rope, _pad_rows(pos, la.GROUP),
            _pad_rows((seg >= 0) & (expanded == 0), la.GROUP), pool, blocks,
            groups, w_kvb[..., nope:], scale, cfg.block_tokens)[:T]
        o_exp = la.expanded_attention(
            _pad_rows(q_nope, la.Q_TILE), _pad_rows(q_pe, la.Q_TILE),
            _pad_rows(pos, la.Q_TILE),
            jnp.concatenate([seg, jnp.full((la.Q_TILE,), -1, jnp.int32)]),
            pool, blocks, pairs, w_kvb, scale, R, cfg.qk_rope_head_dim,
            cfg.block_tokens)[:T]
        return jnp.where(expanded[:, None, None] > 0, o_exp, o_abs)

    # a NAMED CALL, not a named scope: inside the layer scan's body a
    # scope does not reach the device ops' metadata, a call's name does
    o = jax.named_call(forms, name=ATTENTION_SCOPE)(
        q_nope, q_pe, pos, plan["tok_seg"], plan["tok_expanded"], pool,
        blocks, plan["groups"], plan["pairs"], lp["w_kvb"])
    out = jax.named_call(
        lambda o, w_o: dot(o, w_o, "thv,hvd->td").astype(h.dtype),
        name=PROJ_SCOPE)(o, lp["w_o"])
    return out, pool


def swiglu(x, w_gate, w_up, w_down):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mlp(x, w_gate, w_up, w_down):
        g = jnp.dot(x, w_gate, preferred_element_type=f32)
        u = jnp.dot(x, w_up, preferred_element_type=f32)
        return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                       preferred_element_type=f32)

    return jax.named_call(mlp, name=MLP_SCOPE)(x, w_gate, w_up, w_down)


def moe_parts(cfg: DecoderConfig, lp: dict, h, valid, capacity: int):
    """``(routed part of the held experts, shared experts' part, tokens
    per held expert)`` for ``h`` ``[T, D]``, both parts float32."""
    experts, weights = rx.route(
        h, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    routed, counts = rx.held_expert_sum(
        h, experts, weights, valid, rx.gated_silu,
        (lp["e_gate"], lp["e_up"], lp["e_down"]), cfg.experts_lo, capacity)
    return routed, swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"]), counts


def append_step(cfg: DecoderConfig, params: dict, pool, h_last, plan: dict):
    """One forward over the packed appended chunks of a step.

    ``pool`` ``[layers, blocks, block, pool_row_width]`` and ``h_last``
    ``[tenants + 1, D]`` (each session's final-normed last hidden state:
    the context of its next token) are updated in place where the caller
    donates them.  Returns ``(pool, h_last, surprisal [T] float32, audit
    logits [A, vocab_held] float32, tokens per held expert [n_moe,
    experts_held] int32)``.  A token's surprisal is ``-log p(token | its
    session so far)`` over the vocabulary slice; a session's first token
    has no context and reads ``log(vocab_held)``."""
    import jax
    import jax.numpy as jnp
    T = plan["tok_id"].shape[0]
    shape = pool.shape
    pool = pool.reshape((-1,) + shape[2:])
    inv_freq = rope_inv_freq(cfg)
    valid = plan["tok_seg"] >= 0
    capacity = max(T // 2, 8)
    x = params["embed"][plan["tok_id"]]

    def layer(kind):
        def body(carry, lp):
            x, pool, i = carry
            a, pool = attention_block(
                cfg, lp, rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps),
                plan, pool, i, inv_freq)
            x = x + a
            h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps)
            if kind == "dense":
                y, counts = swiglu(h, lp["w_gate"], lp["w_up"],
                                   lp["w_down"]), None
            else:
                routed, shared, counts = moe_parts(cfg, lp, h, valid,
                                                   capacity)
                y = routed + shared
            return (x + y.astype(x.dtype), pool, i + 1), counts
        return body

    carry = (x, pool, jnp.int32(0))
    counts = jnp.zeros((0, cfg.experts_held), jnp.int32)
    if cfg.n_dense:
        carry, _ = jax.lax.scan(layer("dense"), carry, params["dense"])
    if cfg.n_moe:
        carry, counts = jax.lax.scan(layer("moe"), carry, params["moe"])
    x, pool, _ = carry
    h_last, surprisal, audit = seqcommon.score_step(
        x, params, h_last, plan, cfg.rms_norm_eps, cfg.vocab_held)
    return pool.reshape(shape), h_last, surprisal, audit, counts


# -- the plain reference ------------------------------------------------------

def reference_attention(cfg: DecoderConfig, lp: dict, h, pos):
    """Latent attention of one whole session, expanded, head by head."""
    import jax
    import jax.numpy as jnp
    R, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    amp = _rope_amplitude(cfg)
    inv_freq = rope_inv_freq(cfg)
    c_q = rmsnorm(h @ lp["w_qa"], lp["q_norm"], cfg.rms_norm_eps)
    q = jnp.einsum("tr,rhk->htk", c_q, lp["w_qb"])
    kva = h @ lp["w_kva"]
    c_kv = rmsnorm(kva[:, :R], lp["kv_norm"], cfg.rms_norm_eps)
    k_pe = rope(kva[:, R:], pos, inv_freq, amp)
    kv = jnp.einsum("tc,chd->htd", c_kv, lp["w_kvb"])
    causal = pos[:, None] >= pos[None, :]
    scale = softmax_scale(cfg)

    def head(args):
        qh, kvh = args
        s = (qh[:, :nope] @ kvh[:, :nope].T
             + rope(qh[:, nope:], pos, inv_freq, amp) @ k_pe.T) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return p @ kvh[:, nope:]

    o = jax.lax.map(head, (q, kv))                        # [H, T, v]
    return jnp.einsum("htv,hvd->td", o, lp["w_o"])


def reference_moe_parts(cfg: DecoderConfig, lp: dict, h):
    """``(routed part of the held experts, shared part)``, a loop over the
    held experts, every expert over every token."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(h @ lp["router"])
    _, choice = jax.lax.top_k(s + lp["router_bias"],
                              cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, choice, axis=1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    routed = jnp.zeros_like(h)
    for e in range(lp["e_gate"].shape[0]):
        w_e = jnp.where(choice == cfg.experts_lo + e, w, 0.0).sum(axis=1)
        y = (jax.nn.silu(h @ lp["e_gate"][e]) * (h @ lp["e_up"][e])) \
            @ lp["e_down"][e]
        routed = routed + w_e[:, None] * y
    shared = (jax.nn.silu(h @ lp["s_gate"]) * (h @ lp["s_up"])) \
        @ lp["s_down"]
    return routed, shared


def reference_logits(cfg: DecoderConfig, params: dict, tokens):
    """``[L, vocab_held]`` float32 logits of one whole session ``tokens``
    ``[L]``: position ``p``'s row predicts token ``p + 1``."""
    import jax
    import jax.numpy as jnp
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = f32(params["embed"])[tokens]
        for kind, n in (("dense", cfg.n_dense), ("moe", cfg.n_moe)):
            for i in range(n):
                lp = f32(jax.tree_util.tree_map(lambda a: a[i],
                                                params[kind]))
                x = x + reference_attention(
                    cfg, lp, rmsnorm(x, lp["attn_norm"], cfg.rms_norm_eps),
                    pos)
                h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps)
                if kind == "dense":
                    y = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
                        @ lp["w_down"]
                else:
                    y = sum(reference_moe_parts(cfg, lp, h))
                x = x + y
        return rmsnorm(x, f32(params["final_norm"]), cfg.rms_norm_eps) \
            @ f32(params["head"])


def reference_surprisal(logits, tokens, vocab_held: int):
    """Per-token surprisal of a session from its reference logits."""
    import jax
    import jax.numpy as jnp
    tokens = jnp.asarray(tokens, jnp.int32)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    rest = -jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]
    return jnp.concatenate([jnp.full((1,), math.log(vocab_held)), rest])
