"""Plain reference of the latent-attention, routed-expert decoder and of
the serve plane's tokeniser and session policy.  Imports nothing from
``anomod``: the equations are written again here from the public
configuration's keys, in float32 ``jax.numpy`` with
``jax.default_matmul_precision("highest")``: no cache, no paging, no
grouped matmul (a loop over the held experts, each over every token), one
whole session at a time.

The share is the program's: the layer routes over all ``n_routed_experts``
and adds the part of the experts ``[experts_lo, experts_lo +
experts_held)`` only; logits are over ``vocab_held`` rows.

The control: where ``rounded`` is true the latents ``c_kv`` and ``k_pe``
are rounded to ``CONTROL_DTYPE`` where a cache would hold them (the
nearest precision below the configuration's bfloat16).  ``rounded`` is an
argument of the compiled layers, not a second set of them.

The weights are the reference's own: :func:`draw_params` makes them from
``--seed`` by the rule the configuration's ``precision`` and the program's
documentation state (each element an integer hash of its index and its
leaf's number), written again here; :func:`digests` compares them leaf by
leaf with whatever weights the program served with.

At published widths a session runs layer by layer through jitted layer
functions that take the stacked bfloat16 weights and a layer index and
upcast inside (:class:`SessionRunner`); tests at a tiny size call the
same functions.
"""

from __future__ import annotations

import math

import numpy as np

N_STATUS, N_KIND = 4, 3


# -- tokeniser and session policy ---------------------------------------------

def tokenise(service, duration_us, status, kind, n_hist: int) -> np.ndarray:
    """``((service * n_hist + latency bucket) * 4 + status class) * 3 +
    kind``; the bucket is ``int(log1p(duration_us))`` in float32, clipped
    (the sketch histogram's); the class 0: 2xx/3xx, 1: 4xx, 2: 5xx, 3:
    none."""
    bucket = np.clip(np.log1p(np.asarray(duration_us, np.float32))
                     .astype(np.int32), 0, n_hist - 1)
    status = np.asarray(status, np.int32)
    cls = np.full(status.shape, 3, np.int32)
    cls[(status >= 200) & (status < 400)] = 0
    cls[(status >= 400) & (status < 500)] = 1
    cls[status >= 500] = 2
    kind = np.clip(np.asarray(kind, np.int32), 0, N_KIND - 1)
    return ((np.asarray(service, np.int32) * n_hist + bucket) * N_STATUS
            + cls) * N_KIND + kind


class SessionPolicy:
    """The bounded-memory policy replayed from a served log, by counts
    alone.  A step appends ``(tenant, n)`` chunks in ascending tenant
    order.  A session that reaches ``context`` tokens ends and the next
    token starts an empty one; its blocks are free again after the step.
    Before a step is placed, while its blocks are not free, the session
    appended least recently (ties: the lower tenant id; tenants of this
    step count as appended now) is ended; a tenant of the step whose
    session is ended so starts an empty one."""

    def __init__(self, usable_blocks: int, context: int, block: int):
        self.free, self.context, self.block = usable_blocks, context, block
        self.usable = usable_blocks
        self.live = {}                 # tenant -> [length, blocks, number]
        self.stamp = {}                # tenant -> step of its last append
        self.begun = {}
        self.steps = self.rolled = self.evicted = 0

    def _walk(self, tenant: int, n: int):
        """``(start, take, blocks to add)`` of each stretch ``n`` more
        tokens of ``tenant`` make, without changing anything."""
        length, held = self.live.get(tenant, (0, 0))[:2]
        while n > 0:
            take = min(n, self.context - length)
            add = -(-(length + take) // self.block) - held
            yield length, take, add
            n -= take
            length, held = (length + take, held + add)
            if length == self.context:
                length = held = 0

    def step(self, chunks: list) -> list:
        """``[(tenant, session number, start, n)]`` of this step."""
        self.steps += 1
        chunks = sorted(chunks)
        for tenant, _ in chunks:
            self.stamp[tenant] = self.steps
        need = sum(add for t, n in chunks for _, _, add in self._walk(t, n))
        while need > self.free:
            victim = min(self.live, key=lambda t: (self.stamp[t], t))
            n = dict(chunks).get(victim)
            if n is not None:
                need -= sum(add for _, _, add in self._walk(victim, n))
            self.free += self.live.pop(victim)[1]
            self.evicted += 1
            if n is not None:
                need += sum(add for _, _, add in self._walk(victim, n))
        out, returned = [], 0
        for tenant, n in chunks:
            for start, take, add in list(self._walk(tenant, n)):
                s = self.live.get(tenant)
                if s is None:
                    number = self.begun.get(tenant, 0)
                    self.begun[tenant] = number + 1
                    s = self.live[tenant] = [0, 0, number]
                self.free -= add
                s[0], s[1] = start + take, s[1] + add
                out.append((tenant, s[2], start, take))
                if s[0] == self.context:
                    returned += self.live.pop(tenant)[1]
                    self.rolled += 1
        self.free += returned
        return out

    @property
    def blocks_held(self) -> int:
        return self.usable - self.free


# -- the weights --------------------------------------------------------------

#: leaves that stay float32 (the router and the norms); every other leaf
#: is rounded to bfloat16, which is what "the same weights" means
F32 = ("router", "router_bias", "attn_norm", "ffn_norm", "q_norm",
       "kv_norm", "final_norm")


def leaf_table(c: dict) -> list:
    """``[(path, shape, scale)]`` in the order that numbers the leaves:
    ``scale`` a matrix's fan-in, ``"norm"`` (``1 + 0.1 u``) or ``"bias"``
    (``0.1 u``).  Stacks carry their layer axis first, experts their
    expert axis second."""
    D, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_held"]
    Q, R, rope = c["q_lora_rank"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    n_dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    n_moe = c["num_hidden_layers"] - n_dense

    def attention(n):
        return [("attn_norm", (n, D), "norm"), ("ffn_norm", (n, D), "norm"),
                ("w_qa", (n, D, Q), D), ("q_norm", (n, Q), "norm"),
                ("w_qb", (n, Q, H, nope + rope), Q),
                ("w_kva", (n, D, R + rope), D), ("kv_norm", (n, R), "norm"),
                ("w_kvb", (n, R, H, nope + v), R),
                ("w_o", (n, H, v, D), H * v)]

    table = [(("embed",), (V, D), 1), (("head",), (D, V), D),
             (("final_norm",), (D,), "norm")]
    if n_dense:
        I = c["intermediate_size"]
        table += [(("dense", k), s, f) for k, s, f in attention(n_dense) + [
            ("w_gate", (n_dense, D, I), D), ("w_up", (n_dense, D, I), D),
            ("w_down", (n_dense, I, D), I)]]
    if n_moe:
        E, N = c["experts_held"], c["n_routed_experts"]
        F = c["moe_intermediate_size"]
        Fs = F * c["n_shared_experts"]
        table += [(("moe", k), s, f) for k, s, f in attention(n_moe) + [
            ("router", (n_moe, D, N), D), ("router_bias", (n_moe, N), "bias"),
            ("e_gate", (n_moe, E, D, F), D), ("e_up", (n_moe, E, D, F), D),
            ("e_down", (n_moe, E, F, D), F), ("s_gate", (n_moe, D, Fs), D),
            ("s_up", (n_moe, D, Fs), D), ("s_down", (n_moe, Fs, D), Fs)]]
    return table


def _hash32(x):
    """The ``lowbias32`` integer hash (uint32 in, uint32 out)."""
    import jax.numpy as jnp
    u = jnp.uint32
    x = (x ^ (x >> u(16))) * u(0x7FEB352D)
    x = (x ^ (x >> u(15))) * u(0x846CA68B)
    return x ^ (x >> u(16))


def draw_leaf(seed: int, number: int, shape, scale, f32: bool):
    """Leaf ``number`` (from 1) of the draw: element ``i`` is ``u_i =
    (top 24 bits of hash(hash(i) ^ key) / 2**24 - 0.5) * sqrt(12)``, a
    uniform of unit variance, ``key = (seed folded to 32 bits) + number *
    0x9E3779B9``; a matrix is ``u / sqrt(fan-in)``."""
    import jax
    import jax.numpy as jnp
    seed = int(seed)
    key = (((seed ^ (seed >> 32)) & 0xFFFFFFFF) + number * 0x9E3779B9) \
        & 0xFFFFFFFF
    size = int(np.prod(shape))
    h = _hash32(_hash32(jax.lax.iota(jnp.uint32, size)) ^ jnp.uint32(key))
    u = ((h >> jnp.uint32(8)).astype(jnp.float32) * 2.0 ** -24 - 0.5) \
        * 12.0 ** 0.5
    if scale == "norm":
        w = 1.0 + 0.1 * u
    elif scale == "bias":
        w = 0.1 * u
    else:
        w = (u * scale ** -0.5).astype(jnp.float32 if f32
                                       else jnp.bfloat16)
    return w.reshape(shape)


def draw_params(c: dict, seed: int) -> dict:
    """The reference's own weights from ``seed``, made on the default
    device in one program (``{"dense": {}, "moe": {}}`` always there)."""
    import jax

    def make():
        out = {"dense": {}, "moe": {}}
        for number, (path, shape, scale) in enumerate(leaf_table(c), 1):
            node = out
            for k in path[:-1]:
                node = node[k]
            node[path[-1]] = draw_leaf(seed, number, shape, scale,
                                       path[-1] in F32)
        return out

    return jax.jit(make)()


def _digest(a):
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(
        a, jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32)
    i = jax.lax.iota(jnp.uint32, a.size)
    return jnp.sum((bits.reshape(-1).astype(jnp.uint32) + jnp.uint32(1))
                   * (i * jnp.uint32(0x9E3779B1) | jnp.uint32(1)),
                   dtype=jnp.uint32)


def _digest_tree(params):
    import jax
    return jax.tree_util.tree_map(_digest, params)


def digests(params: dict) -> dict:
    """``path -> (dtype, shape, a 32-bit digest of the leaf's bits that
    depends on every element's place)``: two draws are the same weights
    when these are equal."""
    import jax
    sums = jax.jit(_digest_tree)(params)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {jax.tree_util.keystr(path): (str(a.dtype), tuple(a.shape),
                                         int(d))
            for (path, a), d in zip(flat, jax.tree_util.tree_leaves(sums))}


# -- the decoder --------------------------------------------------------------

def _mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def rope_tables(c: dict):
    """``(inverse frequencies [rope / 2], amplitude, softmax scale)``."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (c["qk_nope_head_dim"] + dim) ** -0.5
    sc = c.get("rope_scaling")
    if not sc:
        return freq.astype(np.float32), 1.0, scale
    orig, factor = sc["original_max_position_embeddings"], sc["factor"]

    def turn_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turn_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(turn_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freq = freq / factor * ramp + freq * (1 - ramp)
    all_dim = _mscale(factor, sc.get("mscale_all_dim", 0))
    return (freq.astype(np.float32), _mscale(factor, sc.get("mscale", 1))
            / all_dim, scale * all_dim ** 2)


def _rope(x, pos, freq, amp):
    import jax.numpy as jnp
    a, b = x[..., 0::2], x[..., 1::2]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _norm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


CONTROL_DTYPE = "float8_e4m3fn"


def _held(x, rounded):
    """``x`` as a cache of ``CONTROL_DTYPE`` would hold it where
    ``rounded`` (the control), else ``x``."""
    import jax.numpy as jnp
    return jnp.where(rounded, x.astype(CONTROL_DTYPE).astype(jnp.float32), x)


def attention(c: dict, w, h, pos, rounded=False):
    """``w(name)`` gives a layer's leaf in float32."""
    import jax
    import jax.numpy as jnp
    R, nope = c["kv_lora_rank"], c["qk_nope_head_dim"]
    eps = c["rms_norm_eps"]
    freq, amp, scale = rope_tables(c)
    c_q = _norm(h @ w("w_qa"), w("q_norm"), eps)
    q = jnp.einsum("tr,rhk->htk", c_q, w("w_qb"))
    kva = h @ w("w_kva")
    c_kv = _held(_norm(kva[:, :R], w("kv_norm"), eps), rounded)
    k_pe = _held(_rope(kva[:, R:], pos, freq, amp), rounded)
    kv = jnp.einsum("tc,chd->htd", c_kv, w("w_kvb"))
    causal = pos[:, None] >= pos[None, :]

    def head(args):
        qh, kvh = args
        s = (qh[:, :nope] @ kvh[:, :nope].T
             + _rope(qh[:, nope:], pos, freq, amp) @ k_pe.T) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return p @ kvh[:, nope:]

    return jnp.einsum("htv,hvd->td", jax.lax.map(head, (q, kv)), w("w_o"))


def moe_parts(c: dict, w, h):
    """``(the held experts' routed part, the shared experts' part)``."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(h @ w("router"))
    _, choice = jax.lax.top_k(s + w("router_bias"),
                              c["num_experts_per_tok"])
    wt = jnp.take_along_axis(s, choice, axis=1)
    if c["norm_topk_prob"]:
        wt = wt / (wt.sum(axis=1, keepdims=True) + 1e-20)
    wt = wt * c["routed_scaling_factor"]
    routed = jnp.zeros_like(h)
    for e in range(c["experts_held"]):
        w_e = jnp.where(choice == c.get("experts_lo", 0) + e, wt,
                        0.0).sum(axis=1)
        y = (jax.nn.silu(h @ w("e_gate", e)) * (h @ w("e_up", e))) \
            @ w("e_down", e)
        routed = routed + w_e[:, None] * y
    shared = (jax.nn.silu(h @ w("s_gate")) * (h @ w("s_up"))) @ w("s_down")
    return routed, shared


def layer(c: dict, kind: str, stack: dict, i, x, pos, rounded=False):
    """Layer ``i`` of the ``kind`` stack (``dense`` or ``moe``; leaves
    ``[layers, ...]`` in any float dtype) over one whole session ``x``
    ``[L, D]`` float32."""
    import jax
    import jax.numpy as jnp

    def w(name, e=None):
        leaf = jax.lax.dynamic_index_in_dim(stack[name], i, keepdims=False)
        return (leaf if e is None else leaf[e]).astype(jnp.float32)

    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = x + attention(c, w, _norm(x, w("attn_norm"), eps), pos,
                          rounded)
        h = _norm(x, w("ffn_norm"), eps)
        if kind == "dense":
            y = (jax.nn.silu(h @ w("w_gate")) * (h @ w("w_up"))) \
                @ w("w_down")
        else:
            y = sum(moe_parts(c, w, h))
        return x + y


def head_scores(c: dict, params: dict, x, tokens, rows):
    """``(surprisal [L], logits rows [len(rows), vocab_held])`` of a
    session from its last hidden states ``x``: position ``p``'s logits
    predict token ``p + 1``; the first token reads ``log(vocab_held)``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        logits = _norm(x, params["final_norm"].astype(f32),
                       c["rms_norm_eps"]) @ params["head"].astype(f32)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    rest = -jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]
    return jnp.concatenate([jnp.full((1,), math.log(c["vocab_held"]), f32),
                            rest]), logits[rows]


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
        self.n = {"dense": min(c["first_k_dense_replace"],
                               c["num_hidden_layers"])}
        self.n["moe"] = c["num_hidden_layers"] - self.n["dense"]
        self._layer = {
            kind: jax.jit(lambda stack, i, x, pos, rounded, kind=kind: layer(
                c, kind, stack, i, x, pos, rounded))
            for kind in ("dense", "moe")}
        self._head = jax.jit(lambda params, x, tokens, rows: head_scores(
            c, params, x, tokens, rows))

    def run(self, tokens: np.ndarray, rows=(), control: bool = False):
        """``(surprisal [L] float32, logits rows)`` of one session;
        ``control``: with the latents rounded."""
        import jax.numpy as jnp
        L = len(tokens)
        size = next(n for n in self.lengths if n >= L)
        padded = np.zeros((size,), np.int32)
        padded[:L] = tokens
        idx = np.zeros((self.max_rows,), np.int32)
        idx[:len(rows)] = rows
        tok = jnp.asarray(padded)
        pos = jnp.arange(size, dtype=jnp.int32)
        x = self.params["embed"][tok].astype(jnp.float32)
        for kind in ("dense", "moe"):
            for i in range(self.n[kind]):
                x = self._layer[kind](self.params[kind], np.int32(i), x, pos,
                                      np.bool_(control))
        s, logits = self._head(self.params, x, tok, jnp.asarray(idx))
        return np.asarray(s)[:L], np.asarray(logits)[:len(rows)]


def compare(program: dict, reference: dict, least: int = 64) -> dict:
    """Numbers of the comparison over sessions: ``program`` and
    ``reference`` map a session key ``(tenant, number)`` to ``(surprisal
    [L], {position: logits row})``.  ``surprisal_gap_mean`` / ``_p99`` /
    ``_max``: the absolute gaps over every span;
    ``surprisal_gap_group_max``: the widest mean gap of any one session's
    or any one tenant's spans, of those with at least ``least`` spans (a
    fault in one slot drowns in the window's mean and not in its own
    session's, nor, where a small tenant's sessions are short, in its
    tenant's; under ``least`` spans one near-tie's gap is the mean);
    ``logit_gap``: the mean, over the kept rows, of the gap's norm over
    the reference row's norm (the row centred: a shift of all logits
    moves no probability), ``_max`` the widest.  Means, not the widest
    gap of one span, are what is held to a limit: where two experts'
    scores nearly tie, bfloat16 activations choose the other one for a
    token now and then, and that token's row moves by an expert's whole
    part."""
    gaps, rows, of_tenant = [], [], {}
    for key, (ref_s, ref_rows) in reference.items():
        got_s, got_rows = program[key]
        gaps.append(np.abs(np.asarray(got_s, np.float64)
                           - np.asarray(ref_s, np.float64)))
        of_tenant.setdefault(key[0], []).append(gaps[-1])
        for p, ref_row in ref_rows.items():
            r = np.asarray(ref_row, np.float64)
            g = np.asarray(got_rows[p], np.float64)
            r, g = r - r.mean(), g - g.mean()
            rows.append(np.linalg.norm(g - r) / np.linalg.norm(r))
    own = [g.mean() for g in gaps + [np.concatenate(g)
                                     for g in of_tenant.values()]
           if len(g) >= least]
    gaps = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"surprisal_gap_mean": float(gaps.mean()),
            "surprisal_gap_group_max": float(max(own, default=0.0)),
            "groups_with_a_mean": len(own),
            "surprisal_gap_p99": float(np.percentile(gaps, 99)),
            "surprisal_gap_max": float(gaps.max()),
            "logit_gap": float(np.mean(rows)) if rows else float("nan"),
            "logit_gap_max": float(max(rows)) if rows else float("nan"),
            "spans_compared": int(len(gaps)), "rows_compared": len(rows)}
