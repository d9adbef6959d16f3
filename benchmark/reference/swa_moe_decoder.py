"""Plain reference of the window-and-full attention decoder with per-layer
head counts and small routed experts (the ``laguna`` family's layers) and
of the serve plane's session policy over two kinds of block.  Imports
nothing from ``anomod``: the equations are written again here from the
public configuration's keys, in float32 ``jax.numpy`` with
``jax.default_matmul_precision("highest")``: dense attention a head at a
time with the window as a mask over positions, every held expert over
every token by a mask; no cache, no paging, no pair list, no batching, one
whole session at a time.  The tokeniser, the integer hash, the digests and
the comparison are ``latent_moe_decoder``'s (the plane's, whatever the
model).

Layer ``i`` of kind ``t = layer_types[i]`` with ``H = num_attention_heads_
per_layer[i]`` query heads over ``num_key_value_heads`` key-value heads
(head ``j`` reads key head ``j // (H / kv)``):

- ``a = RMSNorm(h)``; ``q = a W_q``, ``k = a W_k``, ``v = a W_v``; ``q, k
  <- rope_t(q, k, position)``; scores ``q k / sqrt(head_dim)`` over keys
  ``j <= i`` and, where ``t`` is ``sliding_attention``, ``i - j <
  sliding_window`` (``sliding_window`` keys, the token's own among them);
  softmax; ``o = p v``; ``g = sigmoid(a W_g)``, one number a head, ``o_h <-
  g_h o_h``; ``h <- h + concat(o) W_o``.
- ``rope_t``: the first ``partial_rotary_factor * head_dim`` dims of a head
  rotate (rotate-half: dim ``i`` pairs with dim ``i + half``), the rest
  pass through.  ``default``: ``inv_freq_i = theta ** (-2 i / dim)``.
  ``yarn``: ``inv_freq_i = (extrap_i / factor) ramp_i + extrap_i (1 -
  ramp_i)`` with ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``low =
  floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, ``c(r) = dim ln(
  original / (2 pi r)) / (2 ln theta)``, both clipped to ``[0, dim - 1]``;
  cosine and sine times ``attention_factor``.
- ``b = RMSNorm(h)``; ``dense``: ``h <- h + (silu(b W_gate) * b W_up)
  W_down``; ``sparse``: ``p = softmax(b W_r)`` over all experts, top-k,
  ``w = scaling * p_top / sum(p_top)``, ``h <- h + sum_e w_e SwiGLU_e(b) +
  SwiGLU_shared(b)``.

The share is the program's: the layer adds the part of the experts
``[experts_lo, experts_lo + experts_held)`` only; logits are over
``vocab_held`` rows.

The control: where ``rounded`` is true everything a cache would carry is
rounded to ``CONTROL_DTYPE`` (the nearest precision below the
configuration's bfloat16): every key (after its rotation) and value of
both kinds of layer, and the last hidden state of a served segment, which
is the context its successor's first token is scored from (``bounds``:
the positions at which a segment of the session starts).

Two readings beside it say where the served program's own distance comes
from (``SessionRunner.run(acts=, forced=)``): ``acts`` rounds what the
program holds in bfloat16 between its matrix products (the residual
stream, a layer's two normed inputs, queries, keys, values, attention's
output, an MLP's inner product and its output) with
``jax.lax.reduce_precision``, which no compiler folds away; ``forced``
gives every sparse layer the experts that another run of the session
chose, so that two runs differ by arithmetic and not by a near-tie of two
router scores.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference import latent_moe_decoder as base
from benchmark.reference.hybrid_ssm_moe_decoder import (  # noqa: F401
    _held, compare)
from benchmark.reference.latent_moe_decoder import (  # noqa: F401
    CONTROL_DTYPE, _norm, digests, tokenise)

FULL, SWA = "full_attention", "sliding_attention"


def _as_bf16(x, on):
    """``x`` as bfloat16 holds it (8 exponent bits, 7 of mantissa) where
    ``on``."""
    import jax
    import jax.numpy as jnp
    return jnp.where(on, jax.lax.reduce_precision(x, 8, 7), x)


def kinds(c: dict) -> list:
    """``[(attention kind, query heads, mlp kind)]`` of the layers here:
    the published lists' first ``num_hidden_layers`` entries."""
    L = c["num_hidden_layers"]
    return list(zip(c["layer_types"][:L],
                    c["num_attention_heads_per_layer"][:L],
                    c["mlp_layer_types"][:L]))


# -- the session policy over two kinds of block -------------------------------

def ring_blocks(length: int, window: int, block: int) -> int:
    """Window blocks a session of ``length`` tokens holds between steps:
    those from the block of position ``length - window + 1`` (the oldest
    key its next token sees) to the block of its last token."""
    return -(-length // block) - max(length - window + 1, 0) // block


class SessionPolicy:
    """The bounded-memory policy replayed from a served log, by counts
    alone.  A step appends ``(tenant, n)`` chunks in ascending tenant
    order; its tokens take a block of the full pool and a block of the
    window pool wherever they begin one.  A session that reaches
    ``context`` tokens ends and the next token starts an empty one; the
    ended one's blocks of both kinds are free again after the step, and so
    are the window blocks that a session's chunk has passed: after a step
    a session holds :func:`ring_blocks` of them.  Before a step is placed,
    while EITHER pool is short of the step's blocks, the session appended
    least recently (ties: the lower tenant id; tenants of this step count
    as appended now) is ended and frees both kinds; a tenant of the step
    whose session is ended so starts an empty one.  A tick is one step
    unless its chunks, counted as the blocks their tokens would begin in
    empty sessions, outnumber the smaller pool: then it is as many steps
    as that takes, each filled to the last block (:meth:`tick`)."""

    def __init__(self, usable_blocks: int, usable_window_blocks: int,
                 context: int, block: int, window: int):
        self.free, self.free_win = usable_blocks, usable_window_blocks
        self.usable, self.usable_win = usable_blocks, usable_window_blocks
        self.context, self.block, self.window = context, block, window
        self.live = {}      # tenant -> [length, blocks, number, ring]
        self.stamp = {}     # tenant -> step of its last append
        self.begun = {}
        self.steps = self.rolled = self.evicted = 0
        self.evicted_by_window = self.win_freed = self.steps_split = 0

    def tick(self, chunks: list) -> list:
        """``[(tenant, session number, start, n)]`` of a tick's chunks."""
        room = min(self.usable, self.usable_win) * self.block   # in tokens
        out, cur, used = [], [], 0
        for tenant, n in sorted(chunks):
            while n:
                # a chunk's tokens round up to whole blocks; what is over
                # the step's room goes on in the next step
                take = min(n, room - used)
                if take:
                    cur.append((tenant, take))
                    used += -(-take // self.block) * self.block
                    n -= take
                if n:
                    out += self.step(cur)
                    self.steps_split += 1
                    cur, used = [], 0
        return out + self.step(cur)

    def _walk(self, tenant: int, n: int):
        """``(start, take, blocks to add)`` of each stretch ``n`` more
        tokens of ``tenant`` make, changing nothing."""
        length = self.live.get(tenant, (0,))[0]
        while n > 0:
            take = min(n, self.context - length)
            yield length, take, -(-(length + take) // self.block) \
                - -(-length // self.block)
            n -= take
            length = (length + take) % self.context

    def _needs(self, tenant: int, n: int) -> int:
        return sum(add for _, _, add in self._walk(tenant, n))

    def step(self, chunks: list) -> list:
        self.steps += 1
        chunks = sorted(chunks)
        for tenant, _ in chunks:
            self.stamp[tenant] = self.steps
        sizes = dict(chunks)
        need = {t: self._needs(t, n) for t, n in chunks}
        while sum(need.values()) > min(self.free, self.free_win):
            victim = min(self.live, key=lambda t: (self.stamp[t], t))
            self.evicted += 1
            self.evicted_by_window += sum(need.values()) > self.free_win
            _, blocks, _, ring = self.live.pop(victim)
            self.free += blocks
            self.free_win += ring
            self.win_freed += ring
            if victim in need:
                need[victim] = self._needs(victim, sizes[victim])
        out, blocks_back, ring_back = [], 0, 0
        for tenant, n in chunks:
            for start, take, add in list(self._walk(tenant, n)):
                if tenant not in self.live:
                    number = self.begun.get(tenant, 0)
                    self.begun[tenant] = number + 1
                    self.live[tenant] = [0, 0, number, 0]
                s = self.live[tenant]
                self.free -= add
                self.free_win -= add
                s[0], s[1], s[3] = start + take, s[1] + add, s[3] + add
                out.append((tenant, s[2], start, take))
                if s[0] == self.context:
                    blocks_back += s[1]
                    ring_back += s[3]
                    del self.live[tenant]
                    self.rolled += 1
        for tenant, _ in chunks:          # the blocks the chunks passed
            s = self.live.get(tenant)
            if s is not None:
                keep = ring_blocks(s[0], self.window, self.block)
                ring_back += s[3] - keep
                s[3] = keep
        self.free += blocks_back
        self.free_win += ring_back
        self.win_freed += ring_back
        return out

    @property
    def blocks_held(self) -> int:
        return self.usable - self.free

    @property
    def win_blocks_held(self) -> int:
        return self.usable_win - self.free_win


# -- the weights --------------------------------------------------------------

#: leaves that stay float32 (the router and the norms); every other leaf
#: is rounded to bfloat16
F32 = ("router", "norm", "mlp_norm", "final_norm")


def leaf_table(c: dict) -> list:
    """``[(path, shape, scale)]`` in the order that numbers the leaves:
    ``scale`` a matrix's fan-in or ``"norm"`` (``1 + 0.1 u``).  A group of
    leaves a layer, its attention's by the layer's own head count and then
    its MLP's; experts carry their expert axis first."""
    D, V = c["hidden_size"], c["vocab_held"]
    kv, hd = c["num_key_value_heads"], c["head_dim"]
    I, F = c["intermediate_size"], c["moe_intermediate_size"]
    Fs, E = c["shared_expert_intermediate_size"], c["experts_held"]
    mlps = {
        "dense": [("w_gate", (D, I), D), ("w_up", (D, I), D),
                  ("w_down", (I, D), I)],
        "sparse": [("router", (D, c["num_experts"]), D),
                   ("e_gate", (E, D, F), D), ("e_up", (E, D, F), D),
                   ("e_down", (E, F, D), F), ("s_gate", (D, Fs), D),
                   ("s_up", (D, Fs), D), ("s_down", (Fs, D), Fs)]}
    table = [(("embed",), (V, D), 1), (("head",), (D, V), D),
             (("final_norm",), (D,), "norm")]
    for i, (_, H, mlp) in enumerate(kinds(c)):
        table += [((f"layer{i:02d}", k), s, f) for k, s, f in [
            ("norm", (D,), "norm"), ("w_q", (D, H, hd), D),
            ("w_k", (D, kv, hd), D), ("w_v", (D, kv, hd), D),
            ("w_g", (D, H), D), ("w_o", (H, hd, D), H * hd),
            ("mlp_norm", (D,), "norm")] + mlps[mlp]]
    return table


def draw_params(c: dict, seed: int) -> dict:
    """The reference's own weights from ``seed`` by the plane's rule
    (``latent_moe_decoder.draw_leaf``), made on the default device in one
    program."""
    import jax

    def make():
        out = {}
        for number, (path, shape, scale) in enumerate(leaf_table(c), 1):
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = base.draw_leaf(seed, number, shape, scale,
                                            path[-1] in F32)
        return out

    return jax.jit(make)()


# -- the decoder --------------------------------------------------------------

def inv_freq(rope: dict, head_dim: int) -> tuple:
    """``(inverse frequencies of the rotated pairs, amplitude)`` of one
    kind's rotary parameters, written out from the equations above."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    extrap = np.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)])
    if rope["rope_type"] == "default":
        return extrap.astype(np.float32), 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    orig = rope["original_max_position_embeddings"]

    def c(turns):
        return dim * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(c(rope["beta_fast"])), 0), dim - 1)
    high = min(max(math.ceil(c(rope["beta_slow"])), 0), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    freq = extrap / rope["factor"] * ramp + extrap * (1.0 - ramp)
    amp = rope.get("attention_factor")
    if amp is None:
        amp = 0.1 * math.log(rope["factor"]) + 1.0
    return freq.astype(np.float32), float(amp)


def _rope(x, pos, rope: dict, head_dim: int):
    """``x`` ``[heads, L, head_dim]`` rotated at ``pos`` ``[L]``."""
    import jax.numpy as jnp
    freq, amp = inv_freq(rope, head_dim)
    half = len(freq)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def attention(c: dict, kind: str, w, a, pos, rounded, acts=False):
    """``w(name)`` gives a layer's leaf in float32; ``a`` ``[L, D]`` the
    layer's normed input."""
    import jax
    import jax.numpy as jnp
    rope, hd = c["rope_parameters"][kind], c["head_dim"]
    q = _as_bf16(_rope(jnp.einsum("td,dhk->htk", a, w("w_q")), pos, rope,
                       hd), acts)
    k = _held(_as_bf16(_rope(jnp.einsum("td,dgk->gtk", a, w("w_k")), pos,
                             rope, hd), acts), rounded)
    v = _held(_as_bf16(jnp.einsum("td,dgk->gtk", a, w("w_v")), acts),
              rounded)
    per = q.shape[0] // k.shape[0]
    see = pos[:, None] >= pos[None, :]
    if kind == SWA:
        see = see & (pos[:, None] - pos[None, :] < c["sliding_window"])
    gate = jax.nn.sigmoid(a @ w("w_g"))                      # [L, H]

    def head(args):
        qh, h = args
        s = (qh @ k[h // per].T) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return (p @ v[h // per]) * gate[:, h, None]

    o = _as_bf16(jax.lax.map(head, (q, jnp.arange(q.shape[0]))), acts)
    return jnp.einsum("htk,hkd->td", o, w("w_o"))


def _swiglu(x, w_gate, w_up, w_down, acts=False):
    import jax
    return _as_bf16(jax.nn.silu(x @ w_gate) * (x @ w_up), acts) @ w_down


def moe_parts(c: dict, w, b, acts=False, forced=None, chosen=False):
    """``(the held experts' routed part, the shared expert's part)``, and
    with ``chosen`` the experts each token took ``[L, k]``; ``forced``
    ``(experts [L, k], whether to take them)`` in place of the layer's
    own choice."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(b @ w("router"), axis=-1)
    top, choice = jax.lax.top_k(p, c["num_experts_per_tok"])
    if forced is not None:
        choice = jnp.where(forced[1], forced[0], choice)
        top = jnp.take_along_axis(p, choice, axis=1)
    wt = c["moe_routed_scaling_factor"] * top / top.sum(axis=1,
                                                         keepdims=True)

    def expert(e, total):
        w_e = jnp.where(choice == c.get("experts_lo", 0) + e, wt,
                        0.0).sum(axis=1)
        return total + w_e[:, None] * _swiglu(
            b, w("e_gate", e), w("e_up", e), w("e_down", e), acts)

    routed = jax.lax.fori_loop(0, c["experts_held"], expert,
                               jnp.zeros_like(b))
    parts = routed, _swiglu(b, w("s_gate"), w("s_up"), w("s_down"), acts)
    return parts + (choice,) if chosen else parts


def layer(c: dict, kind: str, mlp: str, lp: dict, x, pos, rounded=False,
          acts=False, forced=None):
    """One layer (attention of ``kind``, then the MLP ``mlp``; its leaves
    ``lp`` in any float dtype) over one whole session ``x`` ``[L, D]``
    float32.  With ``forced`` (see :func:`moe_parts`) returns ``(x, the
    experts chosen [L, k])``, zeros for a dense layer."""
    import jax
    import jax.numpy as jnp

    def w(name, e=None):
        leaf = lp[name]
        if e is not None:
            leaf = jax.lax.dynamic_index_in_dim(leaf, e, keepdims=False)
        return leaf.astype(jnp.float32)

    eps = c["rms_norm_eps"]
    bf = lambda y: _as_bf16(y, acts)
    with jax.default_matmul_precision("highest"):
        x = bf(x + bf(attention(c, kind, w, bf(_norm(x, w("norm"), eps)),
                                pos, rounded, acts)))
        b = bf(_norm(x, w("mlp_norm"), eps))
        if mlp == "dense":
            out = _swiglu(b, w("w_gate"), w("w_up"), w("w_down"), acts)
            chosen = jnp.zeros((x.shape[0], c["num_experts_per_tok"]),
                               jnp.int32)
        else:
            routed, shared, chosen = moe_parts(c, w, b, acts, forced, True)
            out = routed + shared
        x = bf(x + bf(out))
    return x if forced is None else (x, chosen)


def head_scores(c: dict, params: dict, x, tokens, rows, rounded=False,
                starts=None):
    """``(surprisal [L], logits rows [len(rows), vocab_held])`` of a
    session from its last hidden states ``x``: position ``p``'s logits
    predict token ``p + 1``; the first token reads ``log(vocab_held)``.
    The control scores a segment's first token from its predecessor's last
    hidden state as a cache would hold it; the kept rows are read before
    any cache.  The logits are taken a block of positions at a time."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    L = x.shape[0]
    chunk = min(L, 1024)
    with jax.default_matmul_precision("highest"):
        hn = _norm(x, params["final_norm"].astype(f32), c["rms_norm_eps"])
        head = params["head"].astype(f32)
        ctx = jnp.concatenate([hn[:-1], jnp.zeros_like(hn[:1])])
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        if starts is not None:
            at_start = jnp.concatenate([starts[1:], starts[:1]])
            ctx = jnp.where((rounded & at_start)[:, None], _held(ctx, True),
                            ctx)

        def score(args):
            ctx_b, tok_b = args
            logp = jax.nn.log_softmax(ctx_b @ head, axis=-1)
            return -jnp.take_along_axis(logp, tok_b[:, None], axis=1)[:, 0]

        rest = jax.lax.map(score, (ctx.reshape(L // chunk, chunk, -1),
                                   nxt.reshape(L // chunk, chunk)))
        kept = hn[rows] @ head
    return jnp.concatenate([jnp.full((1,), math.log(c["vocab_held"]), f32),
                            rest.reshape(L)[:-1]]), kept


class SessionRunner:
    """Runs whole sessions through the reference on the default device, a
    layer at a time (a layer's leaves are upcast as it runs), padded to
    one of ``lengths`` (pads follow the session, so causality keeps them
    out of it)."""

    def __init__(self, c: dict, params: dict, lengths=(1024, 8192),
                 max_rows: int = 64):
        import jax
        self.c, self.params = c, params
        self.lengths = tuple(sorted(lengths))
        self.max_rows = max_rows
        self._layer = {
            (kind, mlp): jax.jit(
                lambda lp, x, pos, rounded, acts, forced, kind=kind,
                mlp=mlp: layer(c, kind, mlp, lp, x, pos, rounded, acts,
                               forced))
            for kind, _, mlp in kinds(c)}
        self._head = jax.jit(
            lambda params, x, tokens, rows, rounded, starts: head_scores(
                c, params, x, tokens, rows, rounded, starts))

    def run(self, tokens: np.ndarray, rows=(), control: bool = False,
            bounds=(), acts: bool = False, forced=None,
            chosen: bool = False):
        """``(surprisal [L] float32, logits rows)`` of one session;
        ``control``: with what a cache would carry rounded, the last
        hidden state at the segments that start at ``bounds``; ``acts``:
        with what the program holds in bfloat16 rounded so; ``forced``:
        the experts ``[layers, L, k]`` every layer takes (an earlier
        run's third result, which ``chosen`` asks for)."""
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
        k = self.c["num_experts_per_tok"]
        took = []
        for i, (kind, _, mlp) in enumerate(kinds(self.c)):
            given = np.zeros((size, k), np.int32)
            if forced is not None:
                given[:L] = forced[i]
            # heads differ by layer: the jitted layer retraces by shape
            x, experts = self._layer[kind, mlp](
                self.params[f"layer{i:02d}"], x, pos, np.bool_(control),
                np.bool_(acts), (given, np.bool_(forced is not None)))
            if chosen:
                took.append(np.asarray(experts)[:L])
        s, logits = self._head(self.params, x, tok, jnp.asarray(idx),
                               np.bool_(control), jnp.asarray(starts))
        out = np.asarray(s)[:L], np.asarray(logits)[:len(rows)]
        return out + (np.stack(took),) if chosen else out
