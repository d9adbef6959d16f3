"""A window-and-full attention decoder with per-layer head counts and small
routed experts over event tokens (the ``laguna`` family's layers).

Pre-norm RMSNorm decoder, two sub-layers a layer: ``h <- h + W_o (g *
attention(RMSNorm(h)))`` and ``h <- h + mlp(RMSNorm(h))``.  A layer's kind
is the published ``layer_types``' (``full_attention`` |
``sliding_attention``): it sets the number of query heads
(``num_attention_heads_per_layer``; the key-value heads are the same
everywhere), the rotary form (``rope_parameters`` by kind: ``default`` or
``yarn``, on the first ``partial_rotary_factor`` of a head, rotate-half),
whether a query sees every earlier key or the newest ``sliding_window``
(its own among them), and which of the two K/V pools caches its keys.  The
gate ``g = sigmoid(RMSNorm(h) W_g)`` is one number a head.  The MLP is the
published ``mlp_layer_types``': ``dense`` (SwiGLU) or ``sparse`` (softmax
router in float32 over all experts, top-k renormalised and scaled, SwiGLU
experts, one ungated shared expert).  Untied embedding and head, bfloat16
weights, activations and caches, float32 accumulation, softmax, router,
norms and rotary tables.  The expert layer is told which experts it holds
(``experts_lo``, ``experts_held``) and which slice of the vocabulary
(``vocab_held``).  Keys of the configuration are the public
``config.json``'s; what it does not key is under the file's ``assumed``.

:func:`append_step` is the serving step: a packed batch of appended chunks
of many sessions against TWO block-paged K/V pools written in place, one
that grows with a session (the full layers) and one of which a session
holds a trailing ring (the sliding layers;
:class:`anomod.serve.seqplane.SessionTable`).  Keys are cached AFTER the
rotation.  Attention through :mod:`anomod.ops.gqa_attention` (a work list
a kind), the experts by grouped matmul (:mod:`anomod.ops.routed_experts`,
a step's pairs in one round).  The plain reference is the benchmark's
(``benchmark/reference/swa_moe_decoder.py``).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from anomod.models import seqcommon
from anomod.models.seqcommon import MLP_SCOPE, PROJ_SCOPE, rmsnorm
from anomod.ops import gqa_attention as ga
from anomod.ops import routed_experts as rx

FULL, SWA = "full_attention", "sliding_attention"
#: kind -> (the call name its attention runs under, the pool that caches
#: its keys, the plan's slot row and work list it reads)
KINDS = {FULL: (ga.SCOPE, "pool", "tok_slot", "items"),
         SWA: (ga.SWA_SCOPE, "wpool", "tok_wslot", "witems")}


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    hidden_size: int
    num_hidden_layers: int
    layer_types: tuple
    num_attention_heads_per_layer: tuple
    mlp_layer_types: tuple
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    rope_parameters: dict
    intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    moe_routed_scaling_factor: float
    rms_norm_eps: float
    vocab_size: int
    vocab_held: int
    experts_held: int
    experts_lo: int = 0
    context_tokens: int = 8192
    block_tokens: int = 128
    pool_tokens: int = 65536
    window_blocks: int = 512

    @classmethod
    def from_dict(cls, d: dict) -> "SwaMoeConfig":
        """From a configuration file: the public keys at the top level,
        the sizes this repo set under ``assumed``; the per-layer lists cut
        to the first ``num_hidden_layers`` entries."""
        flat = seqcommon.flat_spec(d)
        L = int(flat["num_hidden_layers"])
        for key in ("layer_types", "num_attention_heads_per_layer",
                    "mlp_layer_types"):
            if len(flat[key]) < L:
                raise ValueError(f"{key} names fewer than {L} layers")
            flat[key] = tuple(flat[key][:L])
        cfg = cls(**{f.name: flat[f.name] for f in dataclasses.fields(cls)
                     if f.name in flat})
        for key, want in (("attention_bias", False), ("gating", True),
                          ("moe_apply_router_weight_on_input", False),
                          ("tie_word_embeddings", False)):
            if d.get(key, want) != want:
                raise ValueError(f"{key} = {d[key]!r} is not written here "
                                 f"(only {want!r})")
        if set(cfg.layer_types) - {FULL, SWA} \
                or set(cfg.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("a layer kind that is not written here: "
                             f"{cfg.layer_types}, {cfg.mlp_layer_types}")
        for kind in set(cfg.layer_types):
            rope = cfg.rope_parameters.get(kind)
            if rope is None or rope.get("rope_type") not in ("default",
                                                             "yarn"):
                raise ValueError(f"rope_parameters of {kind}: {rope!r} is "
                                 "not written here (default | yarn)")
        if any(h % cfg.num_key_value_heads
               for h in cfg.num_attention_heads_per_layer):
            raise ValueError("a layer's heads are no multiple of the "
                             "key-value heads")
        if cfg.context_tokens % cfg.block_tokens:
            raise ValueError("context_tokens is no multiple of block_tokens")
        if cfg.kv_row_width % 128:
            raise ValueError("a K/V row is no multiple of 128 columns")
        return cfg

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @property
    def kv_row_width(self) -> int:
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def pool_blocks(self) -> int:
        """Blocks of the full layers' pool, the never-allocated block 0
        among them (``window_blocks`` counts the same way)."""
        return self.pool_tokens // self.block_tokens

    @property
    def session_blocks(self) -> int:
        return self.context_tokens // self.block_tokens


# -- rotary positions ---------------------------------------------------------

def rope_table(rope: dict, head_dim: int) -> tuple:
    """``(inverse frequencies [rotated dims / 2] float32, amplitude)`` of
    one kind's ``rope_parameters``: ``default`` ``theta ** (-2 i / dim)``;
    ``yarn`` blends them with the same divided by ``factor`` along a ramp
    between the dims that turn ``beta_fast`` and ``beta_slow`` times in
    ``original_max_position_embeddings`` positions, and scales cosine and
    sine by ``attention_factor``."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return freq.astype(np.float32), 1.0
    factor, orig = float(rope["factor"]), \
        float(rope["original_max_position_embeddings"])

    def turn_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turn_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(turn_dim(rope.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0, 1)
    freq = freq / factor * ramp + freq * (1 - ramp)
    amp = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return freq.astype(np.float32), float(amp)


def rotate(x, cos, sin):
    """Rotate-half on the first ``2 * cos.shape[-1]`` dims of each head of
    ``x`` ``[T, heads, head_dim]`` (float32); the rest pass through."""
    import jax.numpy as jnp
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


# -- parameters ---------------------------------------------------------------

F32_LEAVES = ("router", "norm", "mlp_norm", "final_norm")


def layer_name(i: int) -> str:
    return f"layer{i:02d}"


def param_shapes(cfg: SwaMoeConfig) -> dict:
    """name -> (shape, rule of the seeded draw); a group of leaves a layer
    (no stack: a layer's experts are never sliced out of one), its
    attention leaves by the layer's own head count, then its MLP's."""
    D, kv, hd = cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim
    I, F, Fs = (cfg.intermediate_size, cfg.moe_intermediate_size,
                cfg.shared_expert_intermediate_size)
    E, R = cfg.experts_held, cfg.num_experts
    mlps = {
        "dense": {"w_gate": ((D, I), D), "w_up": ((D, I), D),
                  "w_down": ((I, D), I)},
        "sparse": {"router": ((D, R), D),
                   "e_gate": ((E, D, F), D), "e_up": ((E, D, F), D),
                   "e_down": ((E, F, D), F),
                   "s_gate": ((D, Fs), D), "s_up": ((D, Fs), D),
                   "s_down": ((Fs, D), Fs)}}
    out = {"embed": ((cfg.vocab_held, D), 1),
           "head": ((D, cfg.vocab_held), D), "final_norm": ((D,), None)}
    for i, (H, mlp) in enumerate(zip(cfg.num_attention_heads_per_layer,
                                     cfg.mlp_layer_types)):
        out[layer_name(i)] = dict(
            {"norm": ((D,), None), "w_q": ((D, H, hd), D),
             "w_k": ((D, kv, hd), D), "w_v": ((D, kv, hd), D),
             "w_g": ((D, H), D), "w_o": ((H, hd, D), H * hd),
             "mlp_norm": ((D,), None)}, **mlps[mlp])
    return out


def init_params(cfg: SwaMoeConfig, seed: int, dtype=None) -> dict:
    """Seeded weights by the plane's rule
    (:func:`anomod.models.seqcommon.draw_params`)."""
    import jax.numpy as jnp
    return seqcommon.draw_params(
        param_shapes(cfg), seed, dtype or jnp.bfloat16, F32_LEAVES)


def param_count(cfg: SwaMoeConfig) -> int:
    return seqcommon.param_count(param_shapes(cfg))


# -- the plan -----------------------------------------------------------------

def plan_caps(cfg: SwaMoeConfig, tokens: int, segments: int) -> dict:
    """Static sizes of a step's plan at ``tokens`` packed tokens."""
    seg = min(tokens, segments)
    return dict(tokens=tokens, segments=seg, audit=64,
                items=ga.items_needed(seg, tokens))


def empty_plan(cfg: SwaMoeConfig, caps: dict, trash_row: int) -> dict:
    """A plan of no work at ``caps`` (numpy, int32): every token a pad
    that writes the never-allocated block 0 of either pool, an item of no
    token in either work list.  The block tables stay on the host: the
    work lists carry the rows their items read."""
    plan = seqcommon.empty_token_plan(
        caps["tokens"], caps["segments"], cfg.session_blocks, caps["audit"],
        trash_row)
    del plan["seg_blocks"]
    items = lambda window: ga.empty_items(caps["items"], ga.blocks_needed(
        cfg.session_blocks, cfg.block_tokens, window))
    return dict(plan, tok_wslot=np.zeros(caps["tokens"], np.int32),
                items=items(None), witems=items(cfg.sliding_window))


def build_plan(cfg: SwaMoeConfig, caps: dict, segments: list,
               tokens: np.ndarray, tenant_ids: np.ndarray,
               audit: frozenset) -> tuple:
    """A step's plan for ``segments`` ``(tenant, session number, start, n,
    blocks, (first window block's place, window blocks))`` whose tokens
    are packed in order in ``tokens``.  Returns ``(plan, stats,
    audit_rows)``: ``stats`` holds the step's share of the work counters,
    by layer kind: visible (new, cached) pairs and the cached keys a chunk
    reads (under the window: the newest ``sliding_window`` a token), and
    the kernel's work items."""
    plan = empty_plan(cfg, caps, len(tenant_ids))
    B, W, n_tok = cfg.block_tokens, cfg.sliding_window, len(tokens)
    T = caps["tokens"]
    # the sessions' block tables, for the host alone
    plan["seg_blocks"] = np.zeros((len(segments), cfg.session_blocks),
                                  np.int32)
    f = seqcommon.fill_token_plan(plan, caps, B, segments, tokens,
                                  tenant_ids, audit)
    blocks = plan.pop("seg_blocks")
    wblocks = np.zeros_like(blocks)
    start, n, off, total, seg = (f[k] for k in ("start", "n", "off",
                                                "total", "seg"))
    for s, (lo, ring) in enumerate(x[5] for x in segments):
        wblocks[s, lo:lo + len(ring)] = ring
    pos = plan["tok_pos"][:n_tok].astype(np.int64)
    plan["tok_wslot"][:n_tok] = wblocks[seg, pos // B] * B + pos % B
    n_items = ga.fill_items(plan["items"], start, n, off, blocks, B)
    ga.fill_items(plan["witems"], start, n, off, wblocks, B, W)
    stats = {"seq_tokens": n_tok, "full_items": n_items,
             "swa_items": n_items,
             "full_pairs": int((n * start + n * (n + 1) // 2).sum()),
             "full_keys": int(total.sum()),
             "swa_pairs": int(np.minimum(pos + 1, W).sum()),
             "swa_keys": int((total - np.maximum(start - W + 1, 0)).sum()),
             "moe_one_round_layers": cfg.mlp_layer_types.count("sparse")
             * rx.one_round(T, cfg.num_experts_per_tok,
                            expert_capacity(cfg, T))}
    return plan, stats, f["audit_rows"]


# -- the serving step ---------------------------------------------------------

def init_state(cfg: SwaMoeConfig, n_tenants: int, dtype=None) -> dict:
    """The donated device state: the full layers' K/V pool, the sliding
    layers' window pool (block 0 of each is never allocated and takes the
    pads' writes; a row is a token's keys then its values, ``2 * kv *
    head_dim`` columns) and each tenant's last hidden state."""
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    row = (cfg.block_tokens, cfg.kv_row_width)
    return {
        "pool": jnp.zeros((cfg.count(FULL), cfg.pool_blocks) + row, dtype),
        "wpool": jnp.zeros((cfg.count(SWA), cfg.window_blocks) + row,
                           dtype),
        "h_last": jnp.zeros((n_tenants + 1, cfg.hidden_size), dtype)}


def attention(cfg: SwaMoeConfig, kind: str, lp: dict, u, plan: dict, pool,
              row0: int, rope):
    """One attention sub-layer of ``kind`` over the packed tokens ``u``
    ``[T, D]`` (normed): project by the layer's own head count, rotate,
    write the new keys and values into ``pool`` (``[layers * blocks,
    block, row]``, this layer's rows from ``row0``), attend (under the
    window on a sliding layer), gate a head, project out.  Returns ``(out
    [T, D] float32, pool)``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T = u.shape[0]
    scope, _, slot_of, items_of = KINDS[kind]
    window = cfg.sliding_window if kind == SWA else None
    dot = lambda a, b, spec: jnp.einsum(spec, a, b,
                                        preferred_element_type=f32)

    def project(u, slot, pool):
        q = rotate(dot(u, lp["w_q"], "td,dhk->thk"), *rope).astype(u.dtype)
        k = rotate(dot(u, lp["w_k"], "td,dgk->tgk"), *rope).astype(u.dtype)
        v = dot(u, lp["w_v"], "td,dgk->tgk").astype(u.dtype)
        return q, seqcommon.write_rows(
            pool, row0 * cfg.block_tokens + slot,
            jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], axis=1))

    def gate_out(o, u):
        gate = jax.nn.sigmoid(jnp.dot(u, lp["w_g"],
                                      preferred_element_type=f32))
        o = (o.astype(f32) * gate[:, :, None]).astype(u.dtype)
        return dot(o, lp["w_o"], "thk,hkd->td")

    q, pool = jax.named_call(project, name=PROJ_SCOPE)(u, plan[slot_of], pool)
    # a NAMED CALL: its name reaches the device ops' metadata, which is
    # how a trace reduction tells the two kinds' kernels apart; and ONE
    # jitted function for the layers of a kind (the layer's first pool row
    # is an argument), so that a step's program traces and lowers each
    # kind's kernel once, not a layer
    o = jax.named_call(
        jax.jit(ga.append_attention, static_argnums=(4, 5, 6, 7)),
        name=scope)(q, pool, plan[items_of], jnp.int32(row0),
                    cfg.num_key_value_heads, cfg.head_dim ** -0.5,
                    cfg.block_tokens, window)
    return jax.named_call(gate_out, name=PROJ_SCOPE)(o, u), pool


def swiglu(x, w_gate, w_up, w_down):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mlp(x, w_gate, w_up, w_down):
        mid = (jax.nn.silu(jnp.dot(x, w_gate, preferred_element_type=f32))
               * jnp.dot(x, w_up, preferred_element_type=f32)
               ).astype(x.dtype)
        return jnp.dot(mid, w_down, preferred_element_type=f32)

    return jax.named_call(mlp, name=MLP_SCOPE)(x, w_gate, w_up, w_down)


def expert_capacity(cfg: SwaMoeConfig, tokens: int) -> int:
    """Rows a round of the routed experts takes in a step of ``tokens``
    packed tokens: every pair of the step."""
    return tokens * cfg.num_experts_per_tok


def moe_parts(cfg: SwaMoeConfig, lp: dict, h, valid, capacity: int):
    """``(routed part of the held experts, shared expert's part, tokens
    per held expert)`` for ``h`` ``[T, D]``, both parts float32."""
    experts, weights = rx.route(
        h, lp["router"], None, cfg.num_experts_per_tok,
        cfg.moe_routed_scaling_factor, True, score="softmax")
    routed, counts = rx.held_expert_sum(
        h, experts, weights, valid, rx.gated_silu,
        (lp["e_gate"], lp["e_up"], lp["e_down"]), cfg.experts_lo, capacity)
    return routed, swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"]), counts


def append_step(cfg: SwaMoeConfig, params: dict, state: dict, plan: dict):
    """One forward over the packed appended chunks of a step.

    ``state`` (:func:`init_state`) is updated in place where the caller
    donates it.  Returns ``(state, surprisal [T] float32, audit logits [A,
    vocab_held] float32, tokens per held expert [sparse layers,
    experts_held] int32)``.  A token's surprisal is ``-log p(token | its
    session so far)`` over the vocabulary slice; a session's first token
    has no context and reads ``log(vocab_held)``.  The experts take a
    step's token-expert pairs in ONE round (``capacity`` is all of
    them)."""
    import jax
    import jax.numpy as jnp
    T = plan["tok_id"].shape[0]
    eps = cfg.rms_norm_eps
    shapes = {k: state[k].shape for k in ("pool", "wpool")}
    pools = {k: state[k].reshape((-1,) + shapes[k][2:]) for k in shapes}
    blocks = {"pool": cfg.pool_blocks, "wpool": cfg.window_blocks}
    valid = plan["tok_seg"] >= 0
    pos = plan["tok_pos"].astype(jnp.float32)
    ropes = {}
    # in the order of first appearance: a set's order changes with the
    # process's string hashing, and with it the lowered module's text and
    # its compile-cache key (PR 38: two programs for one step, a cold
    # ~40 s compile on the runs that drew the other order)
    for kind in dict.fromkeys(cfg.layer_types):
        freq, amp = rope_table(cfg.rope_parameters[kind], cfg.head_dim)
        ropes[kind] = jax.named_call(
            lambda ang, amp=amp: (jnp.cos(ang) * amp, jnp.sin(ang) * amp),
            name=PROJ_SCOPE)(pos[:, None] * jnp.asarray(freq))
    x = params["embed"][plan["tok_id"]]
    at = dict.fromkeys(KINDS, 0)               # a layer's row of its pool
    counts = []
    for layer, (kind, mlp) in enumerate(zip(cfg.layer_types,
                                            cfg.mlp_layer_types)):
        lp = params[layer_name(layer)]
        which = KINDS[kind][1]
        y, pools[which] = attention(
            cfg, kind, lp, rmsnorm(x, lp["norm"], eps), plan, pools[which],
            at[kind] * blocks[which], ropes[kind])
        at[kind] += 1
        x = x + y.astype(x.dtype)
        u = rmsnorm(x, lp["mlp_norm"], eps)
        if mlp == "dense":
            y = swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            routed, shared, n = moe_parts(
                cfg, lp, u, valid, expert_capacity(cfg, T))
            y = routed + shared
            counts.append(n)
        x = x + y.astype(x.dtype)
    h_last, surprisal, audit = seqcommon.score_step(
        x, params, state["h_last"], plan, eps, cfg.vocab_held)
    counts = jnp.stack(counts) if counts else jnp.zeros(
        (0, cfg.experts_held), jnp.int32)
    return (dict({k: pools[k].reshape(shapes[k]) for k in shapes},
                 h_last=h_last), surprisal, audit, counts)
