"""The sequence-model plane of the serve tick: every served span is one
event token of its tenant's session, scored by a decoder as its surprisal
``-log p(token | the tenant's session so far)``.  The decoder is picked
by the configuration's ``model_type`` (:data:`MODELS`): a
latent-attention, routed-expert one (:mod:`anomod.models.latent_moe`) or
a hybrid of state-space, attention and latent-expert layers
(:mod:`anomod.models.hybrid_ssm_moe`), or one of window-and-full
attention layers with their own head counts over two K/V pools and small
routed experts (:mod:`anomod.models.swa_moe`), or a block-diffusion one
that scores a token by a masked round over its block and caches a block
when it is whole (:mod:`anomod.models.block_diffusion_moe`: a step's
ROWS, not its tokens, fill the grid, and a session keeps the ids of its
open block between ticks).

A plane beside ``_rca_step``: it reads the tick's served batches and
writes nothing the sketch planes read, so states, alerts and shed
decisions are byte-identical with the plane on or off.

- **Tokeniser**: ``id = ((service * H + latency bucket) * 4 + status
  class) * 3 + kind``, the latency bucket that of the sketch's
  ``H``-bucket histogram (``int(log1p(duration_us))``, clipped).
- **State**: a block-paged latent pool on the device (``[layers, blocks,
  block, latent + rope]`` with the row held at a multiple of 128 columns,
  allocated once; block 0 is never allocated and takes the pads'
  writes), each tenant's last hidden state, and the per-tenant session
  table on the host (:class:`SessionTable`).  A session
  that reaches ``context_tokens`` restarts empty; when a step's blocks
  are not free the least recently appended sessions are ended before it
  is placed (ties by tenant id).  The policy is a pure function of the
  served log.
- **Step**: one forward a tick over the packed appended chunks of every
  tenant served in it (short chunks through the absorbed attention
  kernel, a grid step a group of ``GROUP`` tokens), padded to a fixed
  grid of token counts compiled in :meth:`SeqPlane.warm`; pool and
  hidden states are donated and updated in place; the tick does not go
  on until the scores are on the host.  More tokens than the grid's
  largest size take further steps.

Spans: ``serve.seq_stage`` (tokenise, session and block tables),
``serve.seq_model`` (issue to scores on the host), ``serve.seq_score``
(window roll-up).  Counters: :data:`COUNTERS`.
"""

from __future__ import annotations

import collections
import json
import zlib

import numpy as np

from anomod.models import latent_moe as lm
from anomod.models import seqcommon
from anomod.ops import latent_attention as la
from anomod.ops import routed_experts as rx
from anomod.utils.tracing import span_of

#: every plane counts all of these; a model's own read 0 under the other
COUNTERS = ("seq_tokens", "seq_pairs", "seq_absorbed_tokens",
            "seq_absorbed_pairs", "seq_absorbed_group_blocks",
            "seq_expanded_keys", "seq_keys", "seq_pad_tokens", "seq_steps",
            "expert_tokens_max", "expert_tokens_mean", "sessions_rolled",
            "sessions_evicted", "pool_blocks_held",
            "ssm_recurrent_tokens", "ssm_scan_tokens", "ssm_scan_blocks",
            "ssm_scan_pairs", "ssm_state_rows", "gqa_pairs", "gqa_keys",
            "gqa_items", "state_slots_held",
            "sessions_evicted_by_slots", "steps_split_by_slots",
            "full_pairs", "full_keys", "full_items", "swa_pairs", "swa_keys",
            "swa_items",
            "win_blocks_held", "win_blocks_freed",
            "sessions_evicted_by_window", "steps_split_by_window",
            "seq_plan_bytes", "seq_fetch_bytes",
            "bd_round_rows", "bd_commit_rows", "bd_blocks_committed",
            "bd_pending_tokens", "bd_prefix_pairs", "bd_prefix_keys",
            "bd_inblock_pairs", "bd_items", "moe_one_round_layers")
#: counters that hold the table's present count, not a sum over steps
GAUGES = ("sessions_rolled", "sessions_evicted", "pool_blocks_held",
          "state_slots_held", "sessions_evicted_by_slots",
          "steps_split_by_slots", "win_blocks_held", "win_blocks_freed",
          "sessions_evicted_by_window", "steps_split_by_window",
          "bd_pending_tokens")
N_STATUS, N_KIND = 4, 3
#: logits rows kept for each audit tenant, the newest
AUDIT_KEEP = 32


def vocab_needed(n_services: int, n_hist: int) -> int:
    return n_services * n_hist * N_STATUS * N_KIND


def tokenise(service, duration_us, status, kind, n_hist: int) -> np.ndarray:
    """Event-token ids of spans (columns of a ``SpanBatch``)."""
    bucket = np.clip(np.log1p(np.asarray(duration_us, np.float32))
                     .astype(np.int32), 0, n_hist - 1)
    status = np.asarray(status, np.int32)
    cls = np.where(status >= 500, 2, np.where(
        status >= 400, 1, np.where(status >= 200, 0, 3)))
    k = np.clip(np.asarray(kind, np.int32), 0, N_KIND - 1)
    return (((np.asarray(service, np.int32) * n_hist + bucket) * N_STATUS
             + cls) * N_KIND + k).astype(np.int32)


class Session:
    __slots__ = ("blocks", "length", "number", "slot", "ring", "ring_lo",
                 "pending")

    def __init__(self, number: int):
        self.blocks, self.length, self.number, self.slot = [], 0, number, 0
        #: the window blocks held, the oldest first, and the place in the
        #: session of the first of them (in blocks)
        self.ring, self.ring_lo = [], 0
        #: a block-diffusion model's open block: the ids of the tokens
        #: after the session's last whole diffusion block (they go with
        #: the session when it rolls or is ended)
        self.pending = np.zeros((0,), np.int32)


class SessionTable:
    """Sessions, their blocks, their state slots and the bounded-memory
    policy.  ``append`` takes one step's ``(tenant, n_tokens)`` chunks in
    ascending tenant order and returns its segments ``(tenant, session
    number, start position, n, blocks)``, with the session's slot as a
    sixth where the table has ``state_slots``; blocks and slot of a
    session that rolled inside the step are freed when the step has been
    planned (the step still reads them), so a session begun in a step
    takes a slot of its own.  ``place`` cuts a tick's chunks into steps
    the slots can hold.  Without ``state_slots`` the table knows blocks
    only.

    With ``window_blocks`` (and ``window``, in tokens) there is a second
    kind of block, of a pool of its own, for layers that read the newest
    ``window`` keys only: a step's tokens take a window block wherever
    they take a block, and when the step has been planned each session
    keeps only the trailing ring that its next token can still see (the
    blocks from position ``length - window + 1`` on) and the rest are free
    again (the step still reads them).  A segment then ends in ``(place of
    the first window block in the session, the window blocks)``.  One
    policy: sessions are ended while EITHER pool is short, and an ended
    session frees both.  ``place`` cuts a tick whose tokens begin more
    blocks than the smaller pool has into steps that each fit, so no tick
    is too wide for the table."""

    def __init__(self, n_blocks: int, context_tokens: int,
                 block_tokens: int, state_slots: int = None,
                 window_blocks: int = None, window: int = None):
        self.context, self.block = int(context_tokens), int(block_tokens)
        self.free = collections.deque(range(1, int(n_blocks)))
        self.usable = len(self.free)
        self.window = None if window_blocks is None else int(window)
        self.free_win = None if window_blocks is None \
            else collections.deque(range(1, int(window_blocks)))
        self.usable_win = 0 if window_blocks is None else len(self.free_win)
        self.evicted_by_window = self.win_freed = 0
        self.steps_split_by_window = 0
        if window_blocks is not None and self.context % self.block:
            raise ValueError("with window blocks a session is a whole "
                             "number of blocks")
        if window_blocks is not None and state_slots is not None:
            raise ValueError("a table of state slots AND window blocks: "
                             "`place` cuts a tick by one of them")
        self.free_slots = None if state_slots is None \
            else collections.deque(range(1, int(state_slots)))
        self.usable_slots = 0 if state_slots is None \
            else len(self.free_slots)
        self.sessions = collections.OrderedDict()   # tenant -> Session, LRU
        self.started = {}                           # tenant -> sessions begun
        self.rolled = self.evicted = 0
        self.evicted_by_slots = self.steps_split = 0

    @property
    def blocks_held(self) -> int:
        return self.usable - len(self.free)

    @property
    def slots_held(self) -> int:
        return self.usable_slots - len(self.free_slots or ())

    @property
    def win_blocks_held(self) -> int:
        return self.usable_win - len(self.free_win or ())

    def _begin(self, tenant: int) -> Session:
        n = self.started.get(tenant, 0)
        self.started[tenant] = n + 1
        s = self.sessions[tenant] = Session(n)
        if self.free_slots is not None:
            s.slot = self.free_slots.popleft()
        return s

    def _end(self, s: Session) -> None:
        self.free.extend(s.blocks)
        if self.free_slots is not None:
            self.free_slots.append(s.slot)
        if self.free_win is not None:
            self.free_win.extend(s.ring)
            self.win_freed += len(s.ring)

    def _needed(self, tenant: int, n: int) -> int:
        """Blocks ``n`` more tokens of ``tenant`` take now (a session
        that rolls inside the step keeps its blocks until its end)."""
        s = self.sessions.get(tenant)
        length, held = (s.length, len(s.blocks)) if s else (0, 0)
        need = 0
        while n > 0:
            take = min(n, self.context - length)
            need += -(-(length + take) // self.block) - held
            n -= take
            length, held = (length + take, held + need) \
                if length + take < self.context else (0, 0)
        return need

    def _begun(self, tenant: int, n: int) -> int:
        """Sessions ``n`` more tokens of ``tenant`` begin now, each of
        which takes a slot (a live one goes on in its own)."""
        s = self.sessions.get(tenant)
        if s is None:
            return -(-n // self.context)
        return -(-max(n - (self.context - s.length), 0) // self.context)

    def place(self, chunks: list) -> list:
        """A tick's chunks in ascending tenant order as the segments of
        one step or, where the sessions they touch outnumber the slots or
        their tokens' blocks outnumber a window pool's (or the pool's
        beside it), of further steps: what a step passed or ended is free
        before the next takes its own."""
        if self.free_win is not None:
            return self._place_by_blocks(chunks)
        if self.free_slots is None:
            return [self.append(chunks)]
        touched = lambda t, n: self._begun(t, n) + (t in self.sessions)
        steps, cur, demand = [], [], 0
        for t, n in chunks:
            d = touched(t, n)
            if cur and demand + d > self.usable_slots:
                steps.append(self.append(cur))
                self.steps_split += 1
                cur, demand, d = [], 0, touched(t, n)
            cur.append((t, n))
            demand += d
        return steps + [self.append(cur)]

    def _place_by_blocks(self, chunks: list) -> list:
        """Steps whose tokens begin no more blocks than the smaller pool
        has, counted as of EMPTY sessions (a step's own sessions may be
        ended to make its room, and an empty one takes no fewer), so a
        step is placed whatever the table holds; a chunk that alone takes
        more goes on in the next step."""
        room = min(self.usable, self.usable_win)
        steps, cur, left = [], [], room
        queue = collections.deque(chunks)
        while queue:
            t, n = queue.popleft()
            head = min(n, left * self.block)
            if head:
                cur.append((t, head))
                left -= -(-head // self.block)
            if head < n:
                queue.appendleft((t, n - head))
                steps.append(self.append(cur))
                self.steps_split_by_window += 1
                cur, left = [], room
        return steps + [self.append(cur)]

    def append(self, chunks: list) -> list:
        for t, _ in chunks:
            if t in self.sessions:
                self.sessions.move_to_end(t)
        # room first: end the least recently appended sessions (this
        # step's own count as appended now, in tenant order) until the
        # step's blocks and slots are free; nothing ends while it is placed
        need = {t: self._needed(t, n) for t, n in chunks}
        short = sum(need.values()) - len(self.free)
        slots = self.free_slots is not None
        # a step's tokens take as many window blocks as blocks (nothing
        # of the ring is freed before the step has been planned)
        wins = self.free_win is not None
        short_win = sum(need.values()) - len(self.free_win) if wins else 0
        begun = {t: self._begun(t, n) for t, n in chunks} if slots else {}
        short_slots = sum(begun.values()) - len(self.free_slots) \
            if slots else 0
        sizes = dict(chunks)
        while short > 0 or short_slots > 0 or short_win > 0:
            if not self.sessions:
                raise RuntimeError(
                    "the pools cannot hold one step's tokens")
            victim, s = next(iter(self.sessions.items()))
            del self.sessions[victim]
            self._end(s)
            self.evicted += 1
            self.evicted_by_slots += short_slots > 0
            self.evicted_by_window += short_win > 0
            short -= len(s.blocks)
            short_slots -= slots
            short_win -= len(s.ring)
            if victim in need:
                fresh = self._needed(victim, sizes[victim])
                short += fresh - need[victim]
                short_win += (fresh - need[victim]) * wins
                need[victim] = fresh
                if slots:
                    again = self._begun(victim, sizes[victim])
                    short_slots += again - begun[victim]
                    begun[victim] = again
        segments, ended, touched = [], [], []
        for tenant, n in chunks:
            while n > 0:
                s = self.sessions.get(tenant) or self._begin(tenant)
                take = min(n, self.context - s.length)
                for _ in range(-(-(s.length + take) // self.block)
                               - len(s.blocks)):
                    s.blocks.append(self.free.popleft())
                    if wins:
                        s.ring.append(self.free_win.popleft())
                segments.append((tenant, s.number, s.length, take, s.blocks)
                                + ((s.slot,) if slots else ())
                                + (((s.ring_lo, s.ring),) if wins else ()))
                s.length += take
                n -= take
                if s.length == self.context:
                    ended.append(self.sessions.pop(tenant))
                    self.rolled += 1
                elif wins:
                    touched.append(s)
        for s in ended:
            self._end(s)
        for s in touched:
            # what the session's next token can still see stays; a NEW
            # list, the step's segments read the old one
            passed = max(s.length - self.window + 1, 0) // self.block \
                - s.ring_lo
            self.free_win.extend(s.ring[:passed])
            self.win_freed += passed
            s.ring, s.ring_lo = s.ring[passed:], s.ring_lo + passed
        for t, _ in chunks:             # same-step sessions: by tenant id
            if t in self.sessions:
                self.sessions.move_to_end(t)
        return segments


def build_plan(cfg, caps: dict, segments: list, tokens: np.ndarray,
               tenant_ids: np.ndarray, audit: frozenset) -> tuple:
    """A step's plan (``latent_moe.empty_plan`` filled) for ``segments``
    whose tokens are packed in order in ``tokens`` (``tenant_ids``: the
    sorted ids whose ranks are the rows of ``h_last``).  Returns ``(plan,
    stats, audit_rows)``: ``stats`` holds the step's share of the work
    counters (tokens, visible (new, cached) pairs, those and the tokens of
    absorbed chunks, the cached blocks their groups walk, the keys expanded
    chunks materialise, the keys read);
    ``audit_rows`` names the segment behind each filled row of
    ``plan["audit"]``."""
    plan = lm.empty_plan(cfg, caps, len(tenant_ids))
    S, n_tok = len(segments), len(tokens)
    f = seqcommon.fill_token_plan(plan, caps, cfg.block_tokens, segments,
                                  tokens, tenant_ids, audit)
    start, n, off, total, seg = (f[k] for k in ("start", "n", "off",
                                                "total", "seg"))
    # the form of each segment, from sizes alone; the longest first into
    # the pair list, whatever it cannot hold stays absorbed
    dims = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
    expanded = np.zeros(S, bool)
    pairs = []
    wants = np.nonzero(~la.absorbed_is_cheaper(n, total, *dims,
                                               block=cfg.block_tokens))[0]
    for s in wants[np.argsort(-n[wants], kind="stable")]:
        runs = range(0, -(-int(total[s]) // cfg.block_tokens), la.KV_BLOCKS)
        if len(pairs) + len(runs) > caps["pairs"]:
            break
        expanded[s] = True
        tiles = -(-int(n[s]) // la.Q_TILE)
        pairs += [(s, off[s], tiles, blk0) for blk0 in runs]
    plan["tok_expanded"][:n_tok] = expanded[seg]
    if pairs:
        p = plan["pairs"]
        p["seg"][:len(pairs)], p["q0"][:len(pairs)], \
            p["n_tiles"][:len(pairs)], p["blk0"][:len(pairs)] = zip(*pairs)
        p["n_pairs"] = np.int32(len(pairs))
    a_seg = np.nonzero(~expanded)[0]
    if len(a_seg):
        per = -(-n[a_seg] // la.GROUP)
        g_seg = np.repeat(a_seg, per)
        g_i = np.arange(per.sum()) - np.repeat(
            np.concatenate([[0], np.cumsum(per)[:-1]]), per)
        g_tok0 = off[g_seg] + g_i * la.GROUP
        g_ntok = np.minimum(la.GROUP, n[g_seg] - g_i * la.GROUP)
        g_nblk = -(-(start[g_seg] + g_i * la.GROUP + g_ntok)
                   // cfg.block_tokens)
        order = np.argsort(-g_nblk, kind="stable")
        g = plan["groups"]
        G = len(order)
        g["tok0"][:G], g["ntok"][:G] = g_tok0[order], g_ntok[order]
        g["seg"][:G], g["nblk"][:G] = g_seg[order], g_nblk[order]
        g["n_groups"] = np.int32(G)
    pairs_of = n * start + n * (n + 1) // 2
    T = caps["tokens"]
    return plan, {"seq_tokens": n_tok, "seq_pairs": int(pairs_of.sum()),
                  "seq_absorbed_tokens": int(n[~expanded].sum()),
                  "seq_absorbed_pairs": int(pairs_of[~expanded].sum()),
                  "seq_absorbed_group_blocks":
                      int(plan["groups"]["nblk"].sum()),
                  "seq_expanded_keys": int(total[expanded].sum()),
                  "seq_keys": int(total.sum()),
                  "moe_one_round_layers": cfg.n_moe * rx.one_round(
                      T, cfg.num_experts_per_tok,
                      lm.expert_capacity(cfg, T))}, f["audit_rows"]


class LatentMoE:
    """What the plane asks of a model (configuration, weights, pools,
    plan, step), for :mod:`anomod.models.latent_moe`.  ``block_length``:
    a block-diffusion model's, whose segments carry their open block and
    whose rows its ``fit`` counts."""

    state_slots = window_blocks = window = block_length = None

    def __init__(self, spec: dict):
        self.cfg = lm.DecoderConfig.from_dict(spec)

    def init_params(self, seed: int) -> dict:
        return lm.init_params(self.cfg, seed)

    def init_state(self, n_tenants: int) -> dict:
        import jax.numpy as jnp
        cfg = self.cfg
        return {"pool": jnp.zeros((cfg.num_hidden_layers, cfg.pool_blocks,
                                   cfg.block_tokens, cfg.pool_row_width),
                                  jnp.bfloat16),
                "h_last": jnp.zeros((n_tenants + 1, cfg.hidden_size),
                                    jnp.bfloat16)}

    def caps(self, tokens: int, segments: int) -> dict:
        return lm.plan_caps(self.cfg, tokens, segments)

    def empty_plan(self, caps: dict, trash_row: int) -> dict:
        return lm.empty_plan(self.cfg, caps, trash_row)

    def build_plan(self, caps, segments, tokens, tenant_ids, audit):
        return build_plan(self.cfg, caps, segments, tokens, tenant_ids,
                          audit)

    def step(self, params: dict, state: dict, plan: dict):
        pool, h_last, *out = lm.append_step(
            self.cfg, params, state["pool"], state["h_last"], plan)
        return ({"pool": pool, "h_last": h_last},) + tuple(out)

    def fit(self, start: int, n: int, room: int) -> tuple:
        """``(tokens, rows)`` of the longest head of ``n`` tokens appended
        at ``start`` whose rows fit ``room``: a row a token."""
        del start
        return (min(n, room),) * 2


class ModuleModel:
    """The same for a model whose module has the plane's functions by
    their own names (``init_params``, ``init_state``, ``plan_caps``,
    ``empty_plan``, ``build_plan``, ``append_step``, and ``fit`` where its
    rows are not its tokens)."""

    state_slots = window_blocks = window = block_length = None

    def __init__(self, module, cfg):
        self.module, self.cfg = module, cfg

    def init_params(self, seed: int) -> dict:
        return self.module.init_params(self.cfg, seed)

    def init_state(self, n_tenants: int) -> dict:
        return self.module.init_state(self.cfg, n_tenants)

    def caps(self, tokens: int, segments: int) -> dict:
        return self.module.plan_caps(self.cfg, tokens, segments)

    def empty_plan(self, caps: dict, trash_row: int) -> dict:
        return self.module.empty_plan(self.cfg, caps, trash_row)

    def build_plan(self, caps, segments, tokens, tenant_ids, audit):
        return self.module.build_plan(self.cfg, caps, segments, tokens,
                                      tenant_ids, audit)

    def step(self, params: dict, state: dict, plan: dict):
        return self.module.append_step(self.cfg, params, state, plan)

    def fit(self, start: int, n: int, room: int) -> tuple:
        if hasattr(self.module, "fit"):
            return self.module.fit(self.cfg, start, n, room)
        return (min(n, room),) * 2


class HybridSsmMoE(ModuleModel):
    """:mod:`anomod.models.hybrid_ssm_moe`, whose sessions hold a state
    slot beside their blocks."""

    def __init__(self, spec: dict):
        from anomod.models import hybrid_ssm_moe as hm
        super().__init__(hm, hm.HybridConfig.from_dict(spec))
        self.state_slots = self.cfg.state_slots


class SwaMoE(ModuleModel):
    """:mod:`anomod.models.swa_moe`, whose sessions hold a trailing ring
    of window blocks beside their blocks."""

    def __init__(self, spec: dict):
        from anomod.models import swa_moe as wm
        super().__init__(wm, wm.SwaMoeConfig.from_dict(spec))
        self.window_blocks = self.cfg.window_blocks
        self.window = self.cfg.sliding_window


class BlockDiffusionMoE(ModuleModel):
    """:mod:`anomod.models.block_diffusion_moe`, whose step's rows are a
    round a scored token and a commit a whole block."""

    def __init__(self, spec: dict):
        from anomod.models import block_diffusion_moe as bm
        super().__init__(bm, bm.BlockDiffusionConfig.from_dict(spec))
        self.block_length = self.cfg.block_length


#: ``model_type`` of a configuration -> its model; a configuration that
#: names none is the latent-attention decoder's
MODELS = {"nemotron_h": HybridSsmMoE, "laguna": SwaMoE,
          "sdar_moe": BlockDiffusionMoE}


class SeqPlane:
    """The plane.  ``spec``: the configuration file's object (or its
    path); beside the public keys and ``assumed`` (which names the
    ``token_grid``) it may carry ``weights_seed`` and ``audit_tenants``."""

    def __init__(self, spec, tenant_ids, n_services: int, n_hist: int,
                 window_us: int, t0_us: int = 0, tracer=None):
        from anomod.replay import named_jit
        if isinstance(spec, str):
            with open(spec) as f:
                spec = json.load(f)
        self.model = MODELS.get(spec.get("model_type"), LatentMoE)(spec)
        self.cfg = cfg = self.model.cfg
        if vocab_needed(n_services, n_hist) > cfg.vocab_held:
            raise ValueError(
                f"{n_services} services x {n_hist} buckets need "
                f"{vocab_needed(n_services, n_hist)} event ids, the "
                f"vocabulary slice holds {cfg.vocab_held}")
        grid = spec.get("assumed", {}).get("token_grid")
        if not grid:
            raise ValueError("the configuration's `assumed` names no "
                             "`token_grid` (the packed-token sizes to "
                             "compile ahead)")
        self.grid = tuple(sorted(int(t) for t in grid))
        self.audit = frozenset(int(t) for t in spec.get("audit_tenants", ()))
        self.tenant_ids = np.asarray(sorted(int(t) for t in tenant_ids))
        self.n_tenants, self.n_hist = len(self.tenant_ids), int(n_hist)
        self.window_us, self.t0_us = int(window_us), int(t0_us)
        self.tracer = tracer
        self.params = self.model.init_params(
            int(spec.get("weights_seed", 0)))
        #: the donated device state, by name: the attention cache
        #: ``pool``, ``h_last``, and a model's own beside them
        self.state = self.model.init_state(self.n_tenants)
        self.table = SessionTable(cfg.pool_blocks, cfg.context_tokens,
                                  cfg.block_tokens, self.model.state_slots,
                                  self.model.window_blocks,
                                  self.model.window)
        self._step = named_jit("anomod_seq_step", self.model.step,
                               donate_argnums=(1,))
        self.counters = dict.fromkeys(COUNTERS, 0)
        #: (tenant, window, spans, mean surprisal, max surprisal) of
        #: closed windows, the newest last (bounded)
        self.scores = collections.deque(maxlen=1 << 16)
        self._open = {}                  # tenant -> [window, n, sum, max]
        #: audit tenants only, the newest last (bounded): per segment
        #: (tenant, session, start, tokens, surprisals), and logits rows
        #: (tenant, session, position, row), at most 64 rows a step
        self.audit_segments = collections.deque(maxlen=1 << 16)
        self.audit_logits = collections.deque(
            maxlen=AUDIT_KEEP * max(len(self.audit), 1))
        self.tick_doc = None

    # the attention cache and the last hidden states by their old names
    pool = property(lambda self: self.state.get("pool"),
                    lambda self, v: self.state.__setitem__("pool", v))
    h_last = property(lambda self: self.state.get("h_last"),
                      lambda self, v: self.state.__setitem__("h_last", v))

    def caps(self, tokens: int) -> dict:
        return self.model.caps(tokens, 2 * self.n_tenants + 64)

    def warm(self) -> None:
        """Compile every grid size (a step of pads each)."""
        for t in self.grid:
            self._run(self.model.empty_plan(self.caps(t), self.n_tenants))

    def _run(self, plan: dict, step: int = 0, tokens: int = 0,
             audit_rows: bool = False):
        """One step on the device in three spans: the plan to the device
        and the dispatch (``serve.seq_issue``), the host waiting for the
        device (``serve.seq_wait``), the answers onto the host
        (``serve.seq_fetch``; the audit logits only where the step has
        ``audit_rows``).  Returns ``(surprisal, tokens per held expert)``
        as numpy, the audit logits as a third where asked for."""
        import jax
        tags = {"step": step, "grid": len(plan["tok_id"]), "tokens": tokens}
        sent = sum(a.nbytes for a in jax.tree_util.tree_leaves(plan))
        with span_of(self.tracer, "serve.seq_issue", bytes=sent, **tags):
            self.state, surprisal, audit, counts = self._step(
                self.params, self.state, jax.device_put(plan))
        with span_of(self.tracer, "serve.seq_wait", **tags):
            jax.block_until_ready((self.state, surprisal, audit, counts))
        wanted = (surprisal, counts, audit)[:3 if audit_rows else 2]
        got = sum(a.nbytes for a in wanted)
        with span_of(self.tracer, "serve.seq_fetch", bytes=got, **tags):
            on_host = tuple(np.asarray(a) for a in wanted)
        self.counters["seq_plan_bytes"] += sent
        self.counters["seq_fetch_bytes"] += got
        return on_host

    def close(self) -> None:
        """Free the device state (the pools first)."""
        self.state = {}
        self.params = None

    # -- one tick ---------------------------------------------------------

    def step(self, served: list) -> None:
        """Score the tick's served batches."""
        if not sum(qb.n_spans for qb in served):
            self.tick_doc = {"tokens": 0}
            return
        c = self.counters
        with span_of(self.tracer, "serve.seq_stage", batches=len(served)):
            order = sorted(range(len(served)),
                           key=lambda i: served[i].tenant_id)
            cols = [served[i].spans for i in order]
            cat = lambda k: np.concatenate([getattr(b, k) for b in cols])
            tokens = tokenise(cat("service"), cat("duration_us"),
                              cat("status"), cat("kind"), self.n_hist)
            starts = cat("start_us")
            tenants = np.repeat([served[i].tenant_id for i in order],
                                [served[i].n_spans for i in order])
            ids, counts = np.unique(tenants, return_counts=True)
            # a block-diffusion model's open blocks, before a session that
            # rolls or is ended in the step takes its own with it
            before = {t: self.table.sessions.get(t) for t in ids.tolist()} \
                if self.model.block_length else None
            placed = self.table.place(list(zip(ids.tolist(),
                                               counts.tolist())))
            segments = [seg for step in placed for seg in step]
            steps, at = [], 0
            for step in placed:
                n = sum(seg[3] for seg in step)
                if before is not None:
                    step = self._open_blocks(step, tokens[at:at + n], before)
                steps += self._plan_steps(step, tokens[at:at + n])
                at += n
        with span_of(self.tracer, "serve.seq_model", steps=len(steps)):
            surprisal = []
            for number, (plan, stats, audit_rows, pad) in enumerate(steps):
                got, ecounts, *rows = self._run(
                    plan, number, stats["seq_tokens"], bool(audit_rows))
                surprisal.append(got[:stats["seq_tokens"]])
                if audit_rows:
                    self.audit_logits.extend(
                        (t, s, p, rows[0][i])
                        for i, (t, s, p) in enumerate(audit_rows))
                for k, v in stats.items():
                    c[k] += v
                c["seq_pad_tokens"] += pad
                c["seq_steps"] += 1
                if ecounts.size:
                    c["expert_tokens_max"] += int(ecounts.max(axis=1).sum())
                    c["expert_tokens_mean"] += float(
                        ecounts.mean(axis=1).sum())
            surprisal = np.concatenate(surprisal)
        with span_of(self.tracer, "serve.seq_score"):
            closed = self._roll_up(tenants, starts, surprisal)
            if self.audit:
                at = 0
                for tenant, number, start, n, *_ in segments:
                    if tenant in self.audit:
                        self.audit_segments.append(
                            (tenant, number, start, tokens[at:at + n],
                             surprisal[at:at + n]))
                    at += n
            table = self.table
            c.update(zip(GAUGES, (
                table.rolled, table.evicted, table.blocks_held,
                table.slots_held, table.evicted_by_slots,
                table.steps_split, table.win_blocks_held, table.win_freed,
                table.evicted_by_window, table.steps_split_by_window,
                sum(len(s.pending) for s in table.sessions.values())
                if before is not None else 0)))
            self.tick_doc = {
                "tokens": int(len(tokens)), "steps": len(steps),
                "absorbed_group_blocks": sum(
                    step[1].get("seq_absorbed_group_blocks", 0)
                    for step in steps),
                "windows_closed": closed,
                "sessions_rolled": self.table.rolled,
                "sessions_evicted": self.table.evicted,
                "blocks_held": self.table.blocks_held,
                **({"slots_held": self.table.slots_held}
                   if self.model.state_slots else {}),
                **({"win_blocks_held": self.table.win_blocks_held}
                   if self.model.window_blocks else {}),
                "digest": zlib.crc32(surprisal.tobytes())}

    def _open_blocks(self, segments: list, tokens: np.ndarray,
                     before: dict) -> list:
        """A block-diffusion model's segments with the ids of their
        session's open block up to their start (the session's
        ``pending`` before the step) as a last element; each session that
        goes on keeps the ids after its last whole diffusion block."""
        L, out, at = self.model.block_length, [], 0
        for seg in segments:
            tenant, number, start, n = seg[:4]
            s = before.get(tenant)
            head = s.pending if start % L and s is not None \
                and s.number == number else np.zeros((0,), np.int32)
            if len(head) != start % L:
                raise RuntimeError(f"tenant {tenant}'s open block holds "
                                   f"{len(head)} ids at {start}")
            out.append(seg + (head,))
            live = self.table.sessions.get(tenant)
            if live is not None and live.number == number:
                ids = np.concatenate([head, tokens[at:at + n]])
                live.pending = ids[len(ids) - (start + n) % L:].astype(
                    np.int32) if (start + n) % L else ids[:0]
            at += n
        return out

    def _plan_steps(self, segments: list, tokens: np.ndarray) -> list:
        """The tick's segments cut into steps of at most the grid's
        largest size (a cut segment goes on in the next step) and at most
        the plan's segment rows.  The model's ``fit`` counts the rows a
        segment's head takes and cuts where it says (a block-diffusion
        model: at a diffusion block's edge; any other: a row a token)."""
        biggest = self.grid[-1]
        seg_cap = self.caps(biggest)["segments"]
        steps, cur, room, at, taken = [], [], biggest, 0, 0
        queue = collections.deque(segments)
        while queue:
            tenant, number, start, n, *held = queue.popleft()
            take, rows = self.model.fit(start, n, room)
            if not (take or cur):
                raise ValueError(f"{n} tokens at {start} take more than "
                                 f"{biggest} rows")
            if take:
                cur.append((tenant, number, start, take, *held))
                room -= rows
                taken += take
            if take < n:
                queue.appendleft((tenant, number, start + take, n - take,
                                  *held))
            if not room or len(cur) == seg_cap or not queue or take < n:
                used = biggest - room
                size = next(t for t in self.grid if t >= used)
                steps.append(self.model.build_plan(
                    self.caps(size), cur, tokens[at:at + taken],
                    self.tenant_ids, self.audit) + (size - used,))
                at += taken
                cur, room, taken = [], biggest, 0
        return steps

    def _roll_up(self, tenants, starts, surprisal) -> int:
        """Per tenant and sketch window: span count, mean and max
        surprisal; a window closes when a later one's span is scored."""
        window = (starts - self.t0_us) // self.window_us
        key = tenants.astype(np.int64) << 32 | window.astype(np.int64)
        uniq, inv = np.unique(key, return_inverse=True)
        n = np.bincount(inv)
        total = np.bincount(inv, weights=surprisal)
        most = np.full(len(uniq), -np.inf)
        np.maximum.at(most, inv, surprisal)
        closed = 0
        for k, cnt, tot, mx in zip(uniq.tolist(), n.tolist(),
                                   total.tolist(), most.tolist()):
            t, w = k >> 32, k & 0xFFFFFFFF
            cur = self._open.get(t)
            if cur is not None and cur[0] == w:
                cur[1] += cnt
                cur[2] += tot
                cur[3] = max(cur[3], mx)
                continue
            if cur is not None:
                self.scores.append((t, cur[0], cur[1], cur[2] / cur[1],
                                    cur[3]))
                closed += 1
            self._open[t] = [w, cnt, tot, mx]
        return closed
