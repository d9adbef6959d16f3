"""Plain reference of the hybrid state-space, attention and latent-expert
decoder (the ``nemotron_h`` family's layers) and of the serve plane's
session-and-slot policy.  Imports nothing from ``anomod``: the equations
are written again here from the public configuration's keys, in float32
``jax.numpy`` with ``jax.default_matmul_precision("highest")``: the
recurrence TOKEN BY TOKEN (``lax.scan`` over positions; no chunked form),
dense causal attention a head at a time, every held expert over every
token by a mask; no cache, no paging, no batching, one whole session at a
time.  The tokeniser, the integer hash, the digests and the comparison
are ``latent_moe_decoder``'s (the plane's, whatever the model).

One mixer a layer by the first ``num_hidden_layers`` characters of
``hybrid_override_pattern``, ``h <- h + mixer(RMSNorm(h))``:

- ``M``: ``[z | xBC | dt] = W_in u``; ``xBC <- silu(conv(xBC))``
  (depthwise, causal, kernel ``conv_kernel``, with bias); ``x`` ``[heads,
  head_dim]``, ``B``, ``C`` ``[groups, state]``, head ``j`` reads group
  ``j // (heads / groups)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(a_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  S_t C_t + D x_t``; ``y <- RMSNorm_grouped(y * silu(z))`` (the gate
  first, then the norm over each group's channels, with weight); ``W_out
  y``.
- ``*``: grouped-query causal softmax attention, no positional encoding
  (the family's attention carries none; the Mamba layers carry order).
- ``E``: sigmoid router over all experts on the full width, top-k of
  ``score + bias``, the chosen scores normalised and scaled; the experts
  ``W2 relu(W1 l)^2`` on the latent ``l = W_dn u``, their weighted sum
  through ``W_up``; a shared expert ``V2 relu(V1 u)^2`` on the full width.

The share is the program's: the layer adds the part of the experts
``[experts_lo, experts_lo + experts_held)`` only; logits are over
``vocab_held`` rows.

The control: where ``rounded`` is true the reference runs in the served
log's segments (``bounds``: the positions at which a segment of the
session starts) with everything a cache would carry from one segment to
the next rounded to ``CONTROL_DTYPE`` (the nearest precision below the
configuration's bfloat16): the Mamba state and the convolution tail at
every such boundary, every key and value, and the last hidden state of a
segment, which is the context its successor's first token is scored
from.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference import latent_moe_decoder as base
from benchmark.reference.latent_moe_decoder import (  # noqa: F401
    CONTROL_DTYPE, _norm, digests, tokenise)

MIXERS = {"M": "mamba", "*": "attn", "E": "moe"}


def _held(x, rounded):
    """``x`` as a cache of ``CONTROL_DTYPE`` (float8 e4m3fn: three
    mantissa bits, normal from 2**-6, subnormal steps of 2**-9, saturating
    at 448) would hold it where ``rounded`` (the control), else ``x``.
    Written out in float32 arithmetic (round half to even on the value's
    own step) and not as a pair of dtype conversions, which a compiler
    that is allowed excess precision may remove: on the chip the
    conversions left the carried state and the keys as they were (my
    chip run, PR 34)."""
    import jax.numpy as jnp
    a = jnp.minimum(jnp.abs(x), 448.0)
    _, e = jnp.frexp(a)                                   # a = m 2**e
    step = jnp.exp2(jnp.maximum(e - 4, -9).astype(jnp.float32))
    return jnp.where(rounded, jnp.sign(x) * jnp.round(a / step) * step, x)


def pattern(c: dict) -> str:
    return c["hybrid_override_pattern"][:c["num_hidden_layers"]]


# -- the session-and-slot policy ----------------------------------------------

class SessionPolicy:
    """The bounded-memory policy replayed from a served log, by counts
    alone.  A session holds its blocks and one state slot.  A step appends
    ``(tenant, n)`` chunks in ascending tenant order.  A session that
    reaches ``context`` tokens ends and the next token starts an empty
    one, in a slot of its own; the ended one's blocks and slot are free
    again after the step.  Before a step is placed, while its blocks or
    its slots are not free, the session appended least recently (ties:
    the lower tenant id; tenants of this step count as appended now) is
    ended and frees both; a tenant of the step whose session is ended so
    starts an empty one.  A tick whose chunks touch more sessions than
    there are slots is placed in further steps, in ascending tenant order
    (:meth:`tick`)."""

    def __init__(self, usable_blocks: int, context: int, block: int,
                 usable_slots: int):
        self.free, self.context, self.block = usable_blocks, context, block
        self.usable, self.usable_slots = usable_blocks, usable_slots
        self.free_slots = usable_slots
        self.live = {}                 # tenant -> [length, blocks, number]
        self.stamp = {}                # tenant -> step of its last append
        self.begun = {}
        self.steps = self.rolled = self.evicted = 0
        self.evicted_by_slots = self.steps_split = 0

    def _walk(self, tenant: int, n: int):
        """``(start, take, blocks to add, a session begins)`` of each
        stretch ``n`` more tokens of ``tenant`` make, changing nothing."""
        length, held = self.live.get(tenant, (0, 0))[:2]
        begins = tenant not in self.live
        while n > 0:
            take = min(n, self.context - length)
            add = -(-(length + take) // self.block) - held
            yield length, take, add, begins
            n -= take
            length, held, begins = length + take, held + add, False
            if length == self.context:
                length, held, begins = 0, 0, True

    def _needs(self, tenant: int, n: int) -> tuple:
        walk = list(self._walk(tenant, n))
        return sum(w[2] for w in walk), sum(w[3] for w in walk)

    def tick(self, chunks: list) -> list:
        """``[(tenant, session number, start, n)]`` of a tick's chunks."""
        out, cur, demand = [], [], 0
        for tenant, n in sorted(chunks):
            touched = lambda: self._needs(tenant, n)[1] \
                + (tenant in self.live)
            d = touched()
            if cur and demand + d > self.usable_slots:
                out += self.step(cur)
                self.steps_split += 1
                cur, demand, d = [], 0, touched()
            cur.append((tenant, n))
            demand += d
        return out + self.step(cur)

    def step(self, chunks: list) -> list:
        self.steps += 1
        for tenant, _ in chunks:
            self.stamp[tenant] = self.steps
        sizes = dict(chunks)
        need = {t: self._needs(t, n) for t, n in chunks}
        short = lambda: (sum(b for b, _ in need.values()) - self.free,
                         sum(s for _, s in need.values()) - self.free_slots)
        while max(short()) > 0:
            victim = min(self.live, key=lambda t: (self.stamp[t], t))
            self.evicted += 1
            self.evicted_by_slots += short()[1] > 0
            self.free += self.live.pop(victim)[1]
            self.free_slots += 1
            if victim in need:
                need[victim] = self._needs(victim, sizes[victim])
        out, blocks_back, slots_back = [], 0, 0
        for tenant, n in chunks:
            for start, take, add, begins in list(self._walk(tenant, n)):
                if begins:
                    number = self.begun.get(tenant, 0)
                    self.begun[tenant] = number + 1
                    self.live[tenant] = [0, 0, number]
                    self.free_slots -= 1
                s = self.live[tenant]
                self.free -= add
                s[0], s[1] = start + take, s[1] + add
                out.append((tenant, s[2], start, take))
                if s[0] == self.context:
                    blocks_back += self.live.pop(tenant)[1]
                    slots_back += 1
                    self.rolled += 1
        self.free += blocks_back
        self.free_slots += slots_back
        return out

    @property
    def blocks_held(self) -> int:
        return self.usable - self.free

    @property
    def slots_held(self) -> int:
        return self.usable_slots - self.free_slots


# -- the weights --------------------------------------------------------------

#: leaves that stay float32 (the router, the norms, the convolution's bias
#: and the recurrence's own three); every other leaf is rounded to bfloat16
F32 = ("router", "router_bias", "norm", "gate_norm", "final_norm", "conv_b",
       "dt_bias", "a_log", "d")


def leaf_table(c: dict) -> list:
    """``[(path, shape, scale)]`` in the order that numbers the leaves:
    ``scale`` a matrix's fan-in, ``"norm"`` (``1 + 0.1 u``), ``"bias"``
    (``0.1 u``), ``"a_log"`` or ``"dt_bias"``.  Stacks carry their layer
    axis first, a kind of mixer a stack, experts their expert axis
    second."""
    D, V = c["hidden_size"], c["vocab_held"]
    H, P, N = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    di = H * P
    C, K = di + 2 * c["n_groups"] * N, c["conv_kernel"]
    Hq, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    L, F = c["moe_latent_size"], c["moe_intermediate_size"]
    Fs = c["moe_shared_expert_intermediate_size"]
    E, R = c["experts_held"], c["n_routed_experts"]
    kinds = {
        "mamba": [
            ("norm", (D,), "norm"), ("w_in", (D, di + C + H), D),
            ("conv_w", (C, K), K), ("conv_b", (C,), "bias"),
            ("dt_bias", (H,), "dt_bias"), ("a_log", (H,), "a_log"),
            ("d", (H,), "norm"), ("gate_norm", (di,), "norm"),
            ("w_out", (di, D), di)],
        "attn": [
            ("norm", (D,), "norm"), ("w_q", (D, Hq, hd), D),
            ("w_k", (D, kv, hd), D), ("w_v", (D, kv, hd), D),
            ("w_o", (Hq, hd, D), Hq * hd)],
        "moe": [
            ("norm", (D,), "norm"), ("router", (D, R), D),
            ("router_bias", (R,), "bias"), ("w_dn", (D, L), D),
            ("w_up", (L, D), L), ("e_1", (E, L, F), L),
            ("e_2", (E, F, L), F), ("s_1", (D, Fs), D),
            ("s_2", (Fs, D), Fs)]}
    table = [(("embed",), (V, D), 1), (("head",), (D, V), D),
             (("final_norm",), (D,), "norm")]
    for i, ch in enumerate(pattern(c)):
        table += [((f"layer{i:02d}", k), s, f) for k, s, f in
                  kinds[MIXERS[ch]]]
    return table


def draw_leaf(c: dict, seed: int, number: int, shape, scale, f32: bool):
    """Leaf ``number`` of the draw by the plane's rule
    (``latent_moe_decoder.draw_leaf``).  The recurrence's two leaves go
    through the family's initial ranges from the same uniform ``u01 = u /
    sqrt(12) + 0.5``: ``a_log = log(1 + 15 u01)`` (``-A`` uniform on [1,
    16)); ``dt_bias`` the softplus's inverse of a time step log-uniform
    between ``time_step_min`` and ``time_step_max``, no less than
    ``time_step_floor``."""
    import jax
    import jax.numpy as jnp
    if scale not in ("a_log", "dt_bias"):
        return base.draw_leaf(seed, number, shape, scale, f32)
    seed = int(seed)
    key = (((seed ^ (seed >> 32)) & 0xFFFFFFFF) + number * 0x9E3779B9) \
        & 0xFFFFFFFF
    h = base._hash32(base._hash32(jax.lax.iota(
        jnp.uint32, int(np.prod(shape)))) ^ jnp.uint32(key))
    u = ((h >> jnp.uint32(8)).astype(jnp.float32) * 2.0 ** -24 - 0.5) \
        * 12.0 ** 0.5
    u01 = (u / 12.0 ** 0.5 + 0.5).reshape(shape)
    if scale == "a_log":
        return jnp.log(1.0 + 15.0 * u01)
    lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * u01), c["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def draw_params(c: dict, seed: int) -> dict:
    """The reference's own weights from ``seed``, made on the default
    device in one program."""
    import jax

    def make():
        out = {}
        for number, (path, shape, scale) in enumerate(leaf_table(c), 1):
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = draw_leaf(c, seed, number, shape, scale,
                                       path[-1] in F32)
        return out

    return jax.jit(make)()


# -- the decoder --------------------------------------------------------------

def mamba(c: dict, w, u, rounded, starts):
    """``w(name)`` gives a layer's leaf in float32; ``starts`` ``[L]``
    marks the positions at which a served segment of the session starts
    (where the control rounds what is carried)."""
    import jax
    import jax.numpy as jnp
    H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    di, K = H * P, c["conv_kernel"]
    C = di + 2 * G * N
    proj = u @ w("w_in")
    z, xbc, dt = proj[:, :di], proj[:, di:di + C], proj[:, di + C:]
    dt = jax.nn.softplus(dt + w("dt_bias"))
    A, D = -jnp.exp(w("a_log")), w("d")
    conv_w, conv_b = w("conv_w"), w("conv_b")

    def token(carry, inputs):
        S, tail = carry
        row, dt_t, start = inputs
        S = jnp.where(rounded & start, _held(S, True), S)
        tail = jnp.where(rounded & start, _held(tail, True), tail)
        window = jnp.concatenate([tail, row[None]])           # [K, C]
        act = jax.nn.silu((window * conv_w.T).sum(axis=0) + conv_b)
        x = act[:di].reshape(H, P)
        B = jnp.repeat(act[di:di + G * N].reshape(G, N), H // G, axis=0)
        Cm = jnp.repeat(act[di + G * N:].reshape(G, N), H // G, axis=0)
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x)[:, :, None] * B[:, None, :]
        y = (S * Cm[:, None, :]).sum(axis=-1) + D[:, None] * x
        return (S, window[1:]), y.reshape(di)

    _, y = jax.lax.scan(token, (jnp.zeros((H, P, N), jnp.float32),
                                jnp.zeros((K - 1, C), jnp.float32)),
                        (xbc, dt, starts))
    y = (y * jax.nn.silu(z)).reshape(-1, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + c["layer_norm_epsilon"])
    return (y.reshape(-1, di) * w("gate_norm")) @ w("w_out")


def attention(c: dict, w, u, pos, rounded):
    import jax
    import jax.numpy as jnp
    per = c["num_attention_heads"] // c["num_key_value_heads"]
    q = jnp.einsum("td,dhk->htk", u, w("w_q"))
    k = _held(jnp.einsum("td,dgk->gtk", u, w("w_k")), rounded)
    v = _held(jnp.einsum("td,dgk->gtk", u, w("w_v")), rounded)
    causal = pos[:, None] >= pos[None, :]

    def head(args):
        qh, h = args
        s = (qh @ k[h // per].T) * c["head_dim"] ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return p @ v[h // per]

    o = jax.lax.map(head, (q, jnp.arange(q.shape[0])))
    return jnp.einsum("htk,hkd->td", o, w("w_o"))


def moe_parts(c: dict, w, u):
    """``(the held experts' routed part, the shared expert's part)``, both
    on the full width."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(u @ w("router"))
    _, choice = jax.lax.top_k(s + w("router_bias"),
                              c["num_experts_per_tok"])
    wt = jnp.take_along_axis(s, choice, axis=1)
    if c["norm_topk_prob"]:
        wt = wt / (wt.sum(axis=1, keepdims=True) + 1e-20)
    wt = wt * c["routed_scaling_factor"]
    latent = u @ w("w_dn")
    relu2 = lambda a: jnp.square(jnp.maximum(a, 0.0))

    def expert(e, total):
        w_e = jnp.where(choice == c.get("experts_lo", 0) + e, wt,
                        0.0).sum(axis=1)
        return total + w_e[:, None] * (relu2(latent @ w("e_1", e))
                                       @ w("e_2", e))

    routed = jax.lax.fori_loop(0, c["experts_held"], expert,
                               jnp.zeros_like(latent))
    return routed @ w("w_up"), relu2(u @ w("s_1")) @ w("s_2")


def layer(c: dict, kind: str, lp: dict, x, pos, rounded=False,
          starts=None):
    """One layer of ``kind`` (``mamba``, ``attn`` or ``moe``; its leaves
    ``lp`` in any float dtype) over one whole session ``x`` ``[L, D]``
    float32."""
    import jax
    import jax.numpy as jnp

    def w(name, e=None):
        leaf = lp[name]
        if e is not None:
            leaf = jax.lax.dynamic_index_in_dim(leaf, e, keepdims=False)
        return leaf.astype(jnp.float32)

    if starts is None:
        starts = jnp.zeros(x.shape[:1], bool)
    with jax.default_matmul_precision("highest"):
        u = _norm(x, w("norm"), c["layer_norm_epsilon"])
        if kind == "mamba":
            y = mamba(c, w, u, rounded, starts)
        elif kind == "attn":
            y = attention(c, w, u, pos, rounded)
        else:
            y = sum(moe_parts(c, w, u))
        return x + y


def head_scores(c: dict, params: dict, x, tokens, rows, rounded=False,
                starts=None):
    """``(surprisal [L], logits rows [len(rows), vocab_held])`` of a
    session from its last hidden states ``x``: position ``p``'s logits
    predict token ``p + 1``; the first token reads ``log(vocab_held)``.
    The control scores a segment's first token from its predecessor's
    last hidden state as a cache would hold it; the kept rows are read
    before any cache."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        hn = _norm(x, params["final_norm"].astype(f32),
                   c["layer_norm_epsilon"])
        head = params["head"].astype(f32)
        ctx = hn[:-1]
        if starts is not None:
            ctx = jnp.where((rounded & starts[1:])[:, None],
                            _held(ctx, True), ctx)
        logp = jax.nn.log_softmax(ctx @ head, axis=-1)
        kept = hn[rows] @ head
    rest = -jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]
    return jnp.concatenate([jnp.full((1,), math.log(c["vocab_held"]), f32),
                            rest]), kept


def compare(program: dict, reference: dict, least: int = 64) -> dict:
    """``latent_moe_decoder.compare``'s numbers and the median gap
    (``surprisal_gap_p50``): where two experts' scores nearly tie a
    token's gap is an expert's whole part, which moves the mean and not
    the median."""
    out = base.compare(program, reference, least)
    gaps = [np.abs(np.asarray(program[k][0], np.float64)
                   - np.asarray(ref_s, np.float64))
            for k, (ref_s, _) in reference.items()]
    out["surprisal_gap_p50"] = float(np.median(np.concatenate(gaps))) \
        if gaps else 0.0
    return out


class SessionRunner:
    """Runs whole sessions through the reference on the default device,
    padded to one of ``lengths`` (pads follow the session, so causality
    keeps them out of it)."""

    def __init__(self, c: dict, params: dict, lengths=(1024, 8192),
                 max_rows: int = 64):
        import jax
        self.c, self.params = c, params
        self.lengths = tuple(sorted(lengths))
        self.max_rows = max_rows
        self._layer = {
            kind: jax.jit(
                lambda lp, x, pos, rounded, starts, kind=kind: layer(
                    c, kind, lp, x, pos, rounded, starts))
            for kind in MIXERS.values()}
        self._head = jax.jit(
            lambda params, x, tokens, rows, rounded, starts: head_scores(
                c, params, x, tokens, rows, rounded, starts))

    def run(self, tokens: np.ndarray, rows=(), control: bool = False,
            bounds=()):
        """``(surprisal [L] float32, logits rows)`` of one session;
        ``control``: in the segments that start at ``bounds``, with what
        a cache would carry between them rounded."""
        import jax.numpy as jnp
        L = len(tokens)
        size = next(n for n in self.lengths if n >= L)
        padded = np.zeros((size,), np.int32)
        padded[:L] = tokens
        idx = np.zeros((self.max_rows,), np.int32)
        idx[:len(rows)] = rows
        starts = np.zeros((size,), bool)
        starts[[b for b in bounds if 0 < b < size]] = True
        tok = jnp.asarray(padded)
        pos = jnp.arange(size, dtype=jnp.int32)
        x = self.params["embed"][tok].astype(jnp.float32)
        for i, ch in enumerate(pattern(self.c)):
            x = self._layer[MIXERS[ch]](
                self.params[f"layer{i:02d}"], x, pos, np.bool_(control),
                jnp.asarray(starts))
        s, logits = self._head(self.params, x, tok, jnp.asarray(idx),
                               np.bool_(control), jnp.asarray(starts))
        return np.asarray(s)[:L], np.asarray(logits)[:len(rows)]
