"""What the sequence models of the serve plane share: the norm, the seeded
integer-hash weight draw, the token part of a step's plan and the scoring
tail of a step (final norm, each token's context, surprisal, the audit
rows, the sessions' last hidden states).

A model of the plane (:mod:`anomod.models.latent_moe`,
:mod:`anomod.models.hybrid_ssm_moe`) adds its layers, its caches and the
work lists its kernels walk.
"""

from __future__ import annotations

import math

import numpy as np


#: call names the models' steps run their parts under, beside the kernels'
#: own (``latent_moe.ATTENTION_SCOPE``, ``ssm_scan.SCOPE``, ``gqa_attention
#: .SCOPE`` / ``.SWA_SCOPE``) and the experts' (``routed_experts.ROUTE_SCOPE``
#: / ``.ROUNDS_SCOPE``): every device op of a part carries its name in the
#: trace's op metadata, which is how a reduction prices the step by part.
#: No part encloses another.  A mixer's dense work around its kernel:
PROJ_SCOPE = "anomod_seq_proj"
#: the dense MLP and the shared expert
MLP_SCOPE = "anomod_seq_mlp"
#: :func:`score_step`
HEAD_SCOPE = "anomod_seq_head"


def flat_spec(d: dict) -> dict:
    """A configuration file's object flattened for a model's dataclass:
    the public keys at the top level, the sizes this repo set under
    ``assumed`` beside them, the share defaulting to the whole (the
    experts under either of the two names the public configurations
    give their count)."""
    flat = dict(d)
    flat.update({k: v for k, v in d.get("assumed", {}).items()
                 if not isinstance(v, (dict, list, str))})
    flat.setdefault("vocab_held", flat["vocab_size"])
    flat.setdefault("experts_held", flat.get("n_routed_experts",
                                             flat.get("num_experts")))
    return flat


def rmsnorm(x, w, eps: float):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def write_rows(pool, rows, values):
    """``pool`` ``[..., width]`` with ``values`` ``[n, width]`` written at
    ``rows`` of its leading dimensions flattened (in place where the pool
    is donated).  The TPU compiler rewrites a row write on two indices
    into this scatter on one, and the rewritten op carries no metadata;
    written so here, the write's device op keeps the name of the call it
    runs under."""
    flat = pool.reshape((-1,) + pool.shape[-1:])
    return flat.at[rows].set(values).reshape(pool.shape)


# -- the seeded draw ----------------------------------------------------------

def lowbias32(x):
    import jax.numpy as jnp
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def flat_leaves(shapes: dict) -> list:
    """``[(path, (shape, rule))]`` of a one- or two-level table of leaves,
    in the order that numbers them."""
    flat = []
    for name, spec in shapes.items():
        flat += ([((name, k), s) for k, s in spec.items()]
                 if isinstance(spec, dict) else [((name,), spec)])
    return flat


def draw_params(shapes: dict, seed: int, dtype, f32_leaves,
                groups=()) -> dict:
    """Seeded weights made on the device in one program, each element an
    integer hash of its index and the leaf's number: ``u`` uniform of unit
    variance; a leaf's rule is its fan-in (a matrix, ``u / sqrt(fan)``),
    ``None`` (a norm weight, ``1 + 0.1 u``), ``"bias"`` (``0.1 u``) or a
    function of ``u`` (float32).  A matrix whose name is in ``f32_leaves``
    stays float32, every other is ``dtype``.  The same seed gives the same
    weights on every backend.  ``groups``: sub-tables that are there even
    when empty."""
    import jax
    import jax.numpy as jnp
    from anomod.replay import named_jit
    seed = int(seed)
    base = (seed ^ (seed >> 32)) & 0xFFFFFFFF
    flat = flat_leaves(shapes)

    def make():
        out = {}
        for n, (path, (shape, rule)) in enumerate(flat):
            size = int(np.prod(shape))
            key = jnp.uint32((base + (n + 1) * 0x9E3779B9) & 0xFFFFFFFF)
            h = lowbias32(lowbias32(jax.lax.iota(jnp.uint32, size)) ^ key)
            u = ((h >> jnp.uint32(8)).astype(jnp.float32) * 2.0 ** -24
                 - 0.5) * 12.0 ** 0.5
            if rule is None:
                w = 1.0 + 0.1 * u
            elif rule == "bias":
                w = 0.1 * u
            elif callable(rule):
                w = rule(u)
            else:
                w = (u * rule ** -0.5).astype(
                    jnp.float32 if path[-1] in f32_leaves else dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = w.reshape(shape)
        for g in groups:
            out.setdefault(g, {})
        return out

    return named_jit("anomod_seq_init", make)()


def param_count(shapes: dict) -> int:
    return sum(int(np.prod(shape)) for _, (shape, _) in flat_leaves(shapes))


# -- the token part of a step's plan ------------------------------------------

def empty_token_plan(tokens: int, segments: int, session_blocks: int,
                     audit: int, trash_row: int) -> dict:
    """The token and segment rows of a plan of no work (numpy, int32):
    every token a pad that writes the never-read cache slot 0 and reads
    nothing; every segment leaves its hidden state in ``trash_row`` of
    ``h_last``."""
    T, S = tokens, segments
    z = lambda n: np.zeros((n,), np.int32)
    return {
        "tok_id": z(T), "tok_pos": z(T),
        "tok_seg": np.full((T,), -1, np.int32),
        "tok_slot": z(T), "tok_ctx": np.full((T,), -1, np.int32),
        "seg_blocks": np.zeros((S + 1, session_blocks), np.int32),
        "last_src": z(S), "last_row": np.full((S,), trash_row, np.int32),
        "audit": z(audit),
    }


def fill_token_plan(plan: dict, caps: dict, block_tokens: int,
                    segments: list, tokens: np.ndarray,
                    tenant_ids: np.ndarray, audit: frozenset) -> dict:
    """Fill :func:`empty_token_plan`'s rows for ``segments`` ``(tenant,
    session number, start, n, blocks, ...)`` whose tokens are packed in
    order in ``tokens`` (``tenant_ids``: the sorted ids whose ranks are
    the rows of ``h_last``).  Returns what a model's own plan goes on
    from: ``tenant``, ``start``, ``n``, ``off`` (a segment's first packed
    token), ``total`` (the session's length after the step), ``seg``
    (each token's segment), ``last`` (the segment is its tenant's last of
    the step) and ``audit_rows``, the segment behind each filled row of
    ``plan["audit"]``."""
    S, n_tok = len(segments), len(tokens)
    tenant, number, start, n, blocks = list(zip(*segments))[:5]
    tenant, start, n = (np.asarray(a, np.int64) for a in (tenant, start, n))
    off = np.concatenate([[0], np.cumsum(n)[:-1]])
    row = np.searchsorted(tenant_ids, tenant)
    for s, b in enumerate(blocks):
        plan["seg_blocks"][s, :len(b)] = b
    seg = np.repeat(np.arange(S), n)
    pos = start[seg] + np.arange(n_tok) - off[seg]
    first = np.arange(n_tok) == off[seg]
    plan["tok_id"][:n_tok] = tokens
    plan["tok_pos"][:n_tok] = pos
    plan["tok_seg"][:n_tok] = seg
    plan["tok_slot"][:n_tok] = plan["seg_blocks"][
        seg, pos // block_tokens] * block_tokens + pos % block_tokens
    plan["tok_ctx"][:n_tok] = np.where(
        first, np.where(pos > 0, caps["tokens"] + row[seg], -1),
        np.arange(n_tok) - 1)
    # the last segment of a tenant in this step leaves its hidden state
    last = np.ones(S, bool)
    last[:-1] = tenant[1:] != tenant[:-1]
    plan["last_src"][:S] = np.where(last, off + n - 1, 0)
    plan["last_row"][:S] = np.where(last, row, len(tenant_ids))
    total = start + n
    rows = [s for s in range(S) if last[s] and int(tenant[s]) in audit][
        :caps["audit"]]
    for i, s in enumerate(rows):
        plan["audit"][i] = off[s] + n[s] - 1
    return {"tenant": tenant, "number": number, "start": start, "n": n,
            "off": off, "total": total, "seg": seg, "last": last,
            "audit_rows": [(int(tenant[s]), number[s], int(total[s]) - 1)
                           for s in rows]}


# -- the scoring tail of a step -----------------------------------------------

def score_step(x, params: dict, h_last, plan: dict, eps: float,
               vocab_held: int):
    """From the last layer's output ``x`` ``[T, D]``: ``(h_last, surprisal
    [T] float32, audit logits [A, vocab_held] float32)``.  A token's
    context is the packed token before it, its session's last hidden state
    of an earlier step (``h_last``, final-normed), or nothing: a session's
    first token reads ``log(vocab_held)``."""
    import jax
    return jax.named_call(_score_step, name=HEAD_SCOPE)(
        x, params, h_last, plan, eps, vocab_held)


def _score_step(x, params: dict, h_last, plan: dict, eps: float,
                vocab_held: int):
    import jax
    import jax.numpy as jnp
    T = plan["tok_id"].shape[0]
    hn = rmsnorm(x, params["final_norm"], eps)
    ctx_i = plan["tok_ctx"]
    ctx = jnp.where((ctx_i >= T)[:, None], h_last[jnp.maximum(ctx_i - T, 0)],
                    hn[jnp.clip(ctx_i, 0, T - 1)])
    chunk = min(T, 1024)

    def score(args):
        c, tok = args
        logits = jnp.dot(c, params["head"],
                         preferred_element_type=jnp.float32)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tok[:, None], axis=1)[:, 0]

    surprisal = jax.lax.map(score, (
        ctx.reshape(T // chunk, chunk, -1),
        plan["tok_id"].reshape(T // chunk, chunk))).reshape(T)
    surprisal = jnp.where(ctx_i < 0, math.log(vocab_held), surprisal)
    audit = jnp.dot(hn[plan["audit"]], params["head"],
                    preferred_element_type=jnp.float32)
    h_last = h_last.at[plan["last_row"]].set(hn[plan["last_src"]])
    return h_last, surprisal, audit
