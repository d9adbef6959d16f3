"""A hybrid state-space, attention and latent-expert decoder over event
tokens (the ``nemotron_h`` family's layers).

Pre-norm RMSNorm decoder, ``h <- h + mixer(RMSNorm(h))`` with ONE mixer a
layer, chosen by the published ``hybrid_override_pattern`` (its first
``num_hidden_layers`` characters): ``M`` a Mamba-2 mixer, ``*``
grouped-query attention with no positional encoding, ``E`` a LatentMoE
(the router scores the full width, the routed experts work in a latent
of ``moe_latent_size`` between a down and an up projection, ``relu^2``
and not gated; a shared expert on the full width).  Untied embedding and
head, bfloat16 weights and activations with float32 accumulation,
float32 softmax, router, norms and state arithmetic.  The expert layer is
told which experts it holds (``experts_lo``, ``experts_held``) and which
slice of the vocabulary (``vocab_held``): a chip's share of a deployment.
Keys of the configuration are the public ``config.json``'s.

:func:`append_step` is the serving step: a packed batch of appended
chunks of many sessions against TWO kinds of cache written in place, a
block-paged K/V pool that grows with a session (the attention layers) and
one fixed-size slot a session (each Mamba layer's recurrent state and the
tail of its causal convolution).  The recurrence runs in two forms chosen
by size (:mod:`anomod.ops.ssm_scan`), attention through
:mod:`anomod.ops.gqa_attention`, the experts by grouped matmul
(:mod:`anomod.ops.routed_experts`).  The plain reference is the
benchmark's (``benchmark/reference/hybrid_ssm_moe_decoder.py``).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from anomod.models import seqcommon
from anomod.models.seqcommon import MLP_SCOPE, PROJ_SCOPE, rmsnorm
from anomod.ops import gqa_attention as ga
from anomod.ops import routed_experts as rx
from anomod.ops import ssm_scan as ss

MIXERS = {"M": "mamba", "*": "attn", "E": "moe"}
#: the name of the call that holds a Mamba mixer's causal convolution (the
#: row gathers of the taps, their sum, the tail's write); one of the
#: step's parts, as ``seqcommon.PROJ_SCOPE`` is
CONV_SCOPE = "anomod_seq_conv"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    hidden_size: int
    hybrid_override_pattern: str
    num_hidden_layers: int
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    conv_kernel: int
    chunk_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_latent_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    layer_norm_epsilon: float
    time_step_min: float
    time_step_max: float
    time_step_floor: float
    vocab_size: int
    vocab_held: int
    experts_held: int
    experts_lo: int = 0
    context_tokens: int = 8192
    block_tokens: int = 128
    pool_tokens: int = 65536
    state_slots: int = 64

    @classmethod
    def from_dict(cls, d: dict) -> "HybridConfig":
        """From a configuration file: the public keys at the top level,
        the sizes this repo set under ``assumed``."""
        flat = seqcommon.flat_spec(d)
        cfg = cls(**{f.name: flat[f.name] for f in dataclasses.fields(cls)
                     if f.name in flat})
        for key, want in (("mlp_hidden_act", "relu2"),
                          ("mamba_hidden_act", "silu"), ("n_group", 1),
                          ("topk_group", 1), ("n_shared_experts", 1),
                          ("use_conv_bias", True), ("mamba_proj_bias", False),
                          ("attention_bias", False), ("mlp_bias", False)):
            if d.get(key, want) != want:
                raise ValueError(f"{key} = {d[key]!r} is not written here "
                                 f"(only {want!r})")
        if set(cfg.pattern) - set(MIXERS) \
                or len(cfg.pattern) != cfg.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern {cfg.hybrid_override_pattern!r} "
                f"gives no {cfg.num_hidden_layers} layers of {set(MIXERS)}")
        if cfg.context_tokens % cfg.block_tokens:
            raise ValueError("context_tokens is no multiple of block_tokens")
        if (2 * cfg.num_key_value_heads * cfg.head_dim) % 128:
            raise ValueError("a K/V row is no multiple of 128 columns")
        return cfg

    @property
    def pattern(self) -> str:
        """The mixer of each layer here: the published pattern's first
        ``num_hidden_layers`` characters."""
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    def count(self, kind: str) -> int:
        return sum(MIXERS[c] == kind for c in self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def kv_row_width(self) -> int:
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def pool_blocks(self) -> int:
        """Blocks of the K/V pool, the never-allocated block 0 among them."""
        return self.pool_tokens // self.block_tokens

    @property
    def session_blocks(self) -> int:
        return self.context_tokens // self.block_tokens


# -- parameters ---------------------------------------------------------------

F32_LEAVES = ("router", "router_bias", "norm", "gate_norm", "final_norm",
              "conv_b", "dt_bias", "a_log", "d")


def layer_name(i: int) -> str:
    return f"layer{i:02d}"


def param_shapes(cfg: HybridConfig) -> dict:
    """name -> (shape, rule of the seeded draw); a group of leaves a layer
    (``layer00`` ...: no stack, so a layer's weights are never sliced out
    of one), in the pattern's order."""
    D, H, N = cfg.hidden_size, cfg.mamba_num_heads, cfg.ssm_state_size
    di, C, K = cfg.d_inner, cfg.conv_dim, cfg.conv_kernel
    Hq, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    L, F, Fs = (cfg.moe_latent_size, cfg.moe_intermediate_size,
                cfg.moe_shared_expert_intermediate_size)
    E, R = cfg.experts_held, cfg.n_routed_experts
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def unit(u):                       # the draw's uniform on [0, 1)
        return u / 12.0 ** 0.5 + 0.5

    def a_log(u):                      # A = -exp(a_log), -A uniform on [1, 16)
        import jax.numpy as jnp
        return jnp.log(1.0 + 15.0 * unit(u))

    def dt_bias(u):
        # the family's initial time steps: log-uniform between
        # time_step_min and time_step_max, no less than the floor,
        # through the softplus's inverse
        import jax.numpy as jnp
        dt = jnp.maximum(jnp.exp(lo + (hi - lo) * unit(u)),
                         cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    kinds = {
        "mamba": {
            "norm": ((D,), None), "w_in": ((D, di + C + H), D),
            "conv_w": ((C, K), K), "conv_b": ((C,), "bias"),
            "dt_bias": ((H,), dt_bias), "a_log": ((H,), a_log),
            "d": ((H,), None), "gate_norm": ((di,), None),
            "w_out": ((di, D), di)},
        "attn": {
            "norm": ((D,), None), "w_q": ((D, Hq, hd), D),
            "w_k": ((D, kv, hd), D), "w_v": ((D, kv, hd), D),
            "w_o": ((Hq, hd, D), Hq * hd)},
        "moe": {
            "norm": ((D,), None), "router": ((D, R), D),
            "router_bias": ((R,), "bias"),
            "w_dn": ((D, L), D), "w_up": ((L, D), L),
            "e_1": ((E, L, F), L), "e_2": ((E, F, L), F),
            "s_1": ((D, Fs), D), "s_2": ((Fs, D), Fs)}}
    out = {"embed": ((cfg.vocab_held, D), 1),
           "head": ((D, cfg.vocab_held), D), "final_norm": ((D,), None)}
    for i, c in enumerate(cfg.pattern):
        out[layer_name(i)] = kinds[MIXERS[c]]
    return out


def init_params(cfg: HybridConfig, seed: int, dtype=None) -> dict:
    """Seeded weights by the plane's rule
    (:func:`anomod.models.seqcommon.draw_params`); the Mamba layers'
    ``a_log`` and ``dt_bias`` from the same uniforms through the family's
    initial ranges.  A stack's experts are the held experts of THIS draw
    only (another share is another seed)."""
    import jax.numpy as jnp
    return seqcommon.draw_params(
        param_shapes(cfg), seed, dtype or jnp.bfloat16, F32_LEAVES)


def param_count(cfg: HybridConfig) -> int:
    return seqcommon.param_count(param_shapes(cfg))


# -- the plan -----------------------------------------------------------------

def plan_caps(cfg: HybridConfig, tokens: int, segments: int) -> dict:
    """Static sizes of a step's plan at ``tokens`` packed tokens."""
    seg = min(tokens, segments)
    return dict(ss.work_caps(tokens, seg, cfg.chunk_size), tokens=tokens,
                segments=seg, audit=64, items=ga.items_needed(seg, tokens))


def _zero_row(cfg: HybridConfig, caps: dict) -> int:
    """The row of the convolution's source that holds zeros: after the
    packed tokens and every segment row's carried tail."""
    return caps["tokens"] + (caps["segments"] + 1) * (cfg.conv_kernel - 1)


def empty_plan(cfg: HybridConfig, caps: dict, trash_row: int) -> dict:
    """A plan of no work at ``caps`` (numpy, int32): every token a pad, an
    item of no token in attention's work list (which carries the block
    rows its items read: the block table stays on the host), no chunk in
    the scan's; the convolution reads the zero row everywhere and every
    segment row is the never-allocated slot 0."""
    T, S = caps["tokens"], caps["segments"]
    taps = cfg.conv_kernel - 1
    z = lambda *n: np.zeros(n, np.int32)
    zero_row = _zero_row(cfg, caps)
    plan = seqcommon.empty_token_plan(T, S, cfg.session_blocks,
                                      caps["audit"], trash_row)
    del plan["seg_blocks"]
    return dict(
        plan,
        items=ga.empty_items(caps["items"], ga.blocks_needed(
            cfg.session_blocks, cfg.block_tokens)),
        seg_slot=z(S + 1),
        conv_src=np.full((taps, T), zero_row, np.int32),
        tail_src=np.full((S + 1, taps), zero_row, np.int32),
        work=ss.empty_work(caps))


def build_plan(cfg: HybridConfig, caps: dict, segments: list,
               tokens: np.ndarray, tenant_ids: np.ndarray,
               audit: frozenset) -> tuple:
    """A step's plan for ``segments`` ``(tenant, session number, start, n,
    blocks, slot)`` whose tokens are packed in order in ``tokens``.
    Returns ``(plan, stats, audit_rows)``: ``stats`` holds the step's
    share of the work counters (tokens; visible (new, cached) attention
    pairs, the keys read and the kernel's work items; the tokens of each
    scan form, the chunked form's blocks, the slot states read and
    written)."""
    plan = empty_plan(cfg, caps, len(tenant_ids))
    T, S, n_tok = caps["tokens"], len(segments), len(tokens)
    # the sessions' block table, for the host alone
    plan["seg_blocks"] = np.zeros((S, cfg.session_blocks), np.int32)
    f = seqcommon.fill_token_plan(plan, caps, cfg.block_tokens, segments,
                                  tokens, tenant_ids, audit)
    start, n, off, total, seg = (f[k] for k in ("start", "n", "off",
                                                "total", "seg"))
    n_items = ga.fill_items(plan["items"], start, n, off,
                            plan.pop("seg_blocks"), cfg.block_tokens)
    slot = plan["seg_slot"]
    slot[:S] = [s[5] for s in segments]
    # the convolution's earlier inputs: a packed token of the same chunk,
    # the session's carried tail, or (a fresh session) the zero row
    taps = cfg.conv_kernel - 1
    zero_row = _zero_row(cfg, caps)
    tail0 = T + np.arange(S) * taps
    at = np.arange(n_tok) - off[seg]
    for back in range(1, taps + 1):
        plan["conv_src"][back - 1, :n_tok] = np.where(
            at >= back, np.arange(n_tok) - back,
            np.where(start[seg] == 0, zero_row,
                     tail0[seg] + taps + at - back))
    for j in range(taps):
        behind = n - taps + j           # the input's place in the chunk
        plan["tail_src"][:S, j] = np.where(
            behind >= 0, off + behind,
            np.where(start == 0, zero_row, tail0 + taps + behind))
    stats = ss.work_lists(
        plan["work"], off, n, slot[:S], start == 0,
        ss.recurrent_is_cheaper(
            n, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
            cfg.n_groups, cfg.chunk_size), cfg.chunk_size)
    stats.update(seq_tokens=n_tok,
                 gqa_pairs=int((n * start + n * (n + 1) // 2).sum()),
                 gqa_keys=int(total.sum()), gqa_items=n_items,
                 ssm_state_rows=S * cfg.count("mamba"),
                 moe_one_round_layers=cfg.count("moe") * rx.one_round(
                     T, cfg.num_experts_per_tok, expert_capacity(cfg, T)))
    return plan, stats, f["audit_rows"]


# -- the serving step ---------------------------------------------------------

def init_state(cfg: HybridConfig, n_tenants: int, dtype=None) -> dict:
    """The donated device state: the K/V pool, the two slot pools (slot 0
    is never allocated and takes the pads' writes; a state is held
    transposed, ``[state, heads * head_dim]``, as the scan's kernel works
    on it; a slot's convolution tail is one row) and each tenant's last
    hidden state."""
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    n_m, n_a = cfg.count("mamba"), cfg.count("attn")
    return {
        "pool": jnp.zeros((n_a, cfg.pool_blocks, cfg.block_tokens,
                           cfg.kv_row_width), dtype),
        "ssm": jnp.zeros((n_m, cfg.state_slots, cfg.ssm_state_size,
                          cfg.d_inner), dtype),
        "conv": jnp.zeros((n_m, cfg.state_slots,
                           (cfg.conv_kernel - 1) * cfg.conv_dim), dtype),
        "h_last": jnp.zeros((n_tenants + 1, cfg.hidden_size), dtype)}


def mamba_mixer(cfg: HybridConfig, lp: dict, u, plan: dict, ssm, conv,
                layer: int):
    """One Mamba-2 mixer over the packed tokens ``u`` ``[T, D]`` (normed):
    project, convolve causally over each chunk from its session's carried
    tail, run the recurrence from the carried states, gate, norm by
    groups, project out.  Returns ``(out [T, D], ssm, conv)``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T = u.shape[0]
    P, N, G = cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups
    di, C, taps = cfg.d_inner, cfg.conv_dim, cfg.conv_kernel - 1
    slot = plan["seg_slot"]

    def project(u):
        proj = jnp.dot(u, lp["w_in"], preferred_element_type=f32)
        return (proj[:, :di].astype(u.dtype),
                proj[:, di:di + C].astype(u.dtype),
                jax.nn.softplus(proj[:, di + C:] + lp["dt_bias"]))

    def convolve(xbc, conv):
        src = jnp.concatenate([xbc, conv[layer, slot].reshape(-1, C),
                               jnp.zeros((1, C), u.dtype)])
        w = lp["conv_w"].astype(f32)
        acc = lp["conv_b"] + xbc.astype(f32) * w[:, taps]
        for back in range(1, taps + 1):
            acc = acc + src[plan["conv_src"][back - 1]].astype(f32) \
                * w[:, taps - back]
        conv = seqcommon.write_rows(
            conv, layer * conv.shape[1] + slot,
            src[plan["tail_src"]].reshape(-1, taps * C))
        return jax.nn.silu(acc).astype(u.dtype), conv

    def gate_out(y, x, z):
        y = y.astype(f32) + jnp.repeat(lp["d"], P) * x.astype(f32)
        y = (y * jax.nn.silu(z.astype(f32))).reshape(T, G, di // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon)
        y = (y.reshape(T, di) * lp["gate_norm"]).astype(u.dtype)
        return jnp.dot(y, lp["w_out"], preferred_element_type=f32)

    z, xbc, dt = jax.named_call(project, name=PROJ_SCOPE)(u)
    xbc, conv = jax.named_call(convolve, name=CONV_SCOPE)(xbc, conv)
    x = xbc[:, :di]
    # a NAMED CALL: its name reaches the device ops' metadata, which is
    # how a trace reduction finds the recurrence; and ONE jitted function
    # for every layer (the layer's pool row is an argument), so that a
    # step's program traces and lowers the kernel once, not a layer
    y, ssm = jax.named_call(
        jax.jit(ss.ssm_scan, static_argnames="chunk"), name=ss.SCOPE)(
            x, xbc[:, di:di + G * N], xbc[:, di + G * N:], dt,
            -jnp.exp(lp["a_log"]), ssm, jnp.int32(layer), plan["work"],
            chunk=cfg.chunk_size)
    return jax.named_call(gate_out, name=PROJ_SCOPE)(y, x, z), ssm, conv


def attention_mixer(cfg: HybridConfig, lp: dict, u, plan: dict, pool,
                    layer: int):
    """One grouped-query attention mixer: project, write the new keys and
    values into ``pool`` (``[layers * blocks, block, row]``, this layer's
    rows at ``layer * blocks``), attend, project out.  Returns ``(out [T,
    D], pool)``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    T = u.shape[0]
    dot = lambda a, b, spec: jnp.einsum(spec, a, b,
                                        preferred_element_type=f32)
    rows = layer * cfg.pool_blocks

    def project(u, slot, pool):
        q = dot(u, lp["w_q"], "td,dhk->thk").astype(u.dtype)
        row = jnp.concatenate([
            dot(u, lp[k], "td,dgk->tgk").astype(u.dtype).reshape(T, -1)
            for k in ("w_k", "w_v")], axis=1)
        return q, seqcommon.write_rows(
            pool, rows * cfg.block_tokens + slot, row)

    q, pool = jax.named_call(project, name=PROJ_SCOPE)(
        u, plan["tok_slot"], pool)
    # a NAMED CALL, and one jitted function for every attention layer:
    # as the scan's
    o = jax.named_call(
        jax.jit(ga.append_attention, static_argnums=(4, 5, 6)),
        name=ga.SCOPE)(q, pool, plan["items"], jnp.int32(rows),
                       cfg.num_key_value_heads, cfg.head_dim ** -0.5,
                       cfg.block_tokens)
    out = jax.named_call(lambda o: dot(o, lp["w_o"], "thk,hkd->td"),
                         name=PROJ_SCOPE)(o)
    return out, pool


def relu2_mlp(x, w_1, w_2):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mlp(x, w_1, w_2):
        mid = jnp.square(jnp.maximum(
            jnp.dot(x, w_1, preferred_element_type=f32), 0.0)
        ).astype(x.dtype)
        return jnp.dot(mid, w_2, preferred_element_type=f32)

    return jax.named_call(mlp, name=MLP_SCOPE)(x, w_1, w_2)


def expert_capacity(cfg: HybridConfig, tokens: int) -> int:
    """Rows a round of the routed experts takes in a step of ``tokens``
    packed tokens."""
    del cfg
    return max(tokens, 8)


def moe_parts(cfg: HybridConfig, lp: dict, h, valid, capacity: int):
    """``(routed part of the held experts, shared expert's part, tokens
    per held expert)`` for ``h`` ``[T, D]``, both parts float32 on the
    full width: the router scores ``h``, the held experts work on its
    latent and their weighted sum goes back up once."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    # the latent's down and up projections: this mixer's dense work
    # around its rounds
    down = lambda h, w: jnp.dot(h, w, preferred_element_type=f32
                                ).astype(h.dtype)
    up = lambda r, w: jnp.dot(r.astype(h.dtype), w,
                              preferred_element_type=f32)
    experts, weights = rx.route(
        h, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    latent = jax.named_call(down, name=PROJ_SCOPE)(h, lp["w_dn"])
    routed, counts = rx.held_expert_sum(
        latent, experts, weights, valid, rx.relu2, (lp["e_1"], lp["e_2"]),
        cfg.experts_lo, capacity)
    routed = jax.named_call(up, name=PROJ_SCOPE)(routed, lp["w_up"])
    return routed, relu2_mlp(h, lp["s_1"], lp["s_2"]), counts


def append_step(cfg: HybridConfig, params: dict, state: dict, plan: dict):
    """One forward over the packed appended chunks of a step, a mixer a
    layer by the pattern.

    ``state`` (:func:`init_state`) is updated in place where the caller
    donates it.  Returns ``(state, surprisal [T] float32, audit logits [A,
    vocab_held] float32, tokens per held expert [expert layers,
    experts_held] int32)``.  A token's surprisal is ``-log p(token | its
    session so far)`` over the vocabulary slice; a session's first token
    has no context and reads ``log(vocab_held)``."""
    import jax
    import jax.numpy as jnp
    T = plan["tok_id"].shape[0]
    eps = cfg.layer_norm_epsilon
    pool_shape = state["pool"].shape
    pool = state["pool"].reshape((-1,) + pool_shape[2:])
    ssm, conv = state["ssm"], state["conv"]
    valid = plan["tok_seg"] >= 0
    x = params["embed"][plan["tok_id"]]
    at = dict.fromkeys(MIXERS.values(), 0)     # a layer's row of its cache
    counts = []
    for layer, c in enumerate(cfg.pattern):
        kind = MIXERS[c]
        i = at[kind]
        at[kind] += 1
        lp = params[layer_name(layer)]
        u = rmsnorm(x, lp["norm"], eps)
        if kind == "mamba":
            y, ssm, conv = mamba_mixer(cfg, lp, u, plan, ssm, conv, i)
        elif kind == "attn":
            y, pool = attention_mixer(cfg, lp, u, plan, pool, i)
        else:
            routed, shared, n = moe_parts(cfg, lp, u, valid,
                                          expert_capacity(cfg, T))
            y = routed + shared
            counts.append(n)
        x = x + y.astype(x.dtype)
    h_last, surprisal, audit = seqcommon.score_step(
        x, params, state["h_last"], plan, eps, cfg.vocab_held)
    counts = jnp.stack(counts) if counts else jnp.zeros(
        (0, cfg.experts_held), jnp.int32)
    return ({"pool": pool.reshape(pool_shape), "ssm": ssm, "conv": conv,
             "h_last": h_last}, surprisal, audit, counts)
