"""The absorbed append-attention kernel Mosaic-compiled on the chip against
the same kernel under the Pallas interpreter (the host's CPU device), at
the published widths of ``kimi-k2-ep32-share``, on ragged work: a group
that walks all 64 blocks of a full session beside groups of one block, a
last block partly filled, a group with one live row of ``GROUP``.
``tests/test_latent_moe.py`` holds the interpreter to the plain reference
in tier-1; ``tests/test_pool_layout_compile.py`` compiles the whole step
for a described chip.  Only this one runs what Mosaic made."""

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: (tokens a session holds already, tokens the step appends to it)
SESSIONS = [(8184, 8), (8100, 17), (0, 3), (300, 5), (127, 9), (1000, 1),
            (4090, 12), (0, 1)]


def test_the_compiled_kernel_equals_the_interpreter_at_published_widths():
    import jax
    import jax.numpy as jnp

    from anomod.models import latent_moe as lm
    from anomod.ops import latent_attention as la
    from anomod.serve import seqplane as sp

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-k2-ep32-share.json")) as f:
        cfg = lm.DecoderConfig.from_dict(json.load(f))
    table = sp.SessionTable(cfg.pool_blocks, cfg.context_tokens,
                            cfg.block_tokens)
    table.append([(t, held) for t, (held, _) in enumerate(SESSIONS) if held])
    segments = table.append([(t, n) for t, (_, n) in enumerate(SESSIONS)])
    n_tok = sum(n for _, n in SESSIONS)
    caps = lm.plan_caps(cfg, 64, len(SESSIONS))
    plan, stats, _ = sp.build_plan(
        cfg, caps, segments, np.zeros(n_tok, np.int32),
        np.arange(len(SESSIONS)), frozenset())
    assert stats["seq_absorbed_tokens"] == n_tok
    groups = plan["groups"]
    nblk = groups["nblk"][:int(groups["n_groups"])]
    assert nblk.max() == cfg.session_blocks and nblk.min() == 1
    assert 1 in groups["ntok"][:len(nblk)]

    T1 = caps["tokens"] + la.GROUP
    H, W, R = cfg.num_attention_heads, cfg.pool_row_width, cfg.kv_lora_rank
    rng = np.random.default_rng(30)
    cut = lambda a: jnp.asarray(a, jnp.bfloat16).at[
        ..., cfg.latent_width:].set(0)
    q_cat = cut(0.3 * rng.standard_normal((T1, H, W), np.float32))
    pool = cut(rng.standard_normal(
        (cfg.pool_blocks, cfg.block_tokens, W), np.float32))
    w_v = jnp.asarray(R ** -0.5 * rng.standard_normal(
        (R, H, cfg.v_head_dim), np.float32), jnp.bfloat16)
    pad = lambda a: np.concatenate([a, np.zeros(la.GROUP, a.dtype)])
    args = (q_cat[..., :R], q_cat[..., R:], pad(plan["tok_pos"]),
            pad(plan["tok_seg"] >= 0), pool, plan["seg_blocks"], groups, w_v)
    fn = jax.jit(lambda *a: la.absorbed_attention(
        *a, lm.softmax_scale(cfg), cfg.block_tokens))

    chip = jax.devices()[0]
    assert chip.platform == "tpu"
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
    got = np.asarray(fn(*jax.device_put(args, chip)), np.float32)
    want = np.asarray(fn(*jax.device_put(args, jax.devices("cpu")[0])),
                      np.float32)
    assert np.isfinite(got).all()
    assert np.abs(want[:n_tok]).min(axis=(1, 2)).max() > 0
    np.testing.assert_allclose(got, want, atol=0.02 * np.abs(want).max())
    assert not got[n_tok:].any() and not want[n_tok:].any()
