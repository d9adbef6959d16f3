"""Operations and bytes of the hybrid state-space, attention and
latent-expert decoder's serving step, from the configuration's sizes and
the plane's counters alone (what the ALGORITHM needs in the form the size
rule chose; tile padding, pads of the token grid and recomputation are
the implementation's and are not counted).

Counters (``anomod.serve.seqplane.COUNTERS``, deltas over the window; the
plane counts a step's tokens once, whatever the number of layers):
``seq_tokens`` appended tokens; ``ssm_recurrent_tokens`` /
``ssm_scan_tokens`` those of chunks through the recurrent / the chunked
form; ``ssm_scan_pairs`` the (token, earlier-or-same token) pairs inside
the chunked form's blocks; ``ssm_state_rows`` slot x layer states read
and written (summed over the Mamba layers already); ``gqa_pairs``
visible (new, cached) attention pairs, the new token itself among its
keys; ``gqa_keys`` cached tokens whose keys and values a chunk reads;
``expert_tokens_mean`` x ``experts_held``: token-expert pairs computed
here, summed over the expert layers; ``seq_steps``.
"""

from __future__ import annotations

from benchmark.reference.hybrid_ssm_moe_decoder import MIXERS, pattern

BF16, F32 = 2, 4


def sizes(c: dict) -> dict:
    D = c["hidden_size"]
    H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"],
                  c["ssm_state_size"], c["n_groups"])
    di = H * P
    C = di + 2 * G * N
    Hq, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    L, F = c["moe_latent_size"], c["moe_intermediate_size"]
    n = {kind: sum(MIXERS[ch] == kind for ch in pattern(c))
         for kind in MIXERS.values()}
    return {
        "n_mamba": n["mamba"], "n_attn": n["attn"], "n_moe": n["moe"],
        # multiply-adds a token, a layer, outside the kernels
        "mamba_proj": D * (di + C + H) + C * c["conv_kernel"] + di * D,
        "attn_proj": D * Hq * hd + 2 * D * kv * hd + Hq * hd * D,
        "moe_dense": D * c["n_routed_experts"] + 2 * D * L
        + 2 * D * c["moe_shared_expert_intermediate_size"],
        "head": D * c["vocab_held"],
        # the recurrence: a token's state update and read-out in either
        # form, and a pair inside a chunked block (C.B once a group, the
        # score times x once a head)
        "state": H * P * N, "ssm_token": 2 * H * P * N,
        "ssm_pair": G * N + H * P,
        "ssm_token_io": 2 * di + 2 * G * N,   # x, y | B, C (dt apart)
        "heads": H,
        "gqa_pair": 2 * Hq * hd, "kv_row": 2 * kv * hd,
        "q_row": Hq * hd, "expert": 2 * L * F, "latent": L, "width": F}


def ssm_flops(c: dict, n: dict) -> float:
    """All Mamba layers' recurrence."""
    s = sizes(c)
    per_layer = (n["ssm_recurrent_tokens"] + n["ssm_scan_tokens"]) \
        * s["ssm_token"] + n["ssm_scan_pairs"] * s["ssm_pair"]
    return 2.0 * per_layer * s["n_mamba"]


def gqa_flops(c: dict, n: dict) -> float:
    s = sizes(c)
    return 2.0 * n["gqa_pairs"] * s["gqa_pair"] * s["n_attn"]


def grouped_flops(c: dict, n: dict) -> float:
    """The held experts' two grouped matmuls, every expert layer."""
    return 2.0 * n["expert_tokens_mean"] * c["experts_held"] \
        * sizes(c)["expert"]


def step_flops(c: dict, n: dict) -> float:
    """The whole steps' model FLOPs over the counted tokens."""
    s = sizes(c)
    per_token = (s["n_mamba"] * s["mamba_proj"] + s["n_attn"] * s["attn_proj"]
                 + s["n_moe"] * s["moe_dense"] + s["head"])
    return (2.0 * n["seq_tokens"] * per_token + ssm_flops(c, n)
            + gqa_flops(c, n) + grouped_flops(c, n))


def ssm_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the recurrence's scope: every slot state
    a chunk continues read and written once a layer in the pool's
    bfloat16, ``x``, ``B``, ``C`` in and ``y`` out once a token a layer,
    the time steps in float32."""
    s = sizes(c)
    tokens = n["ssm_recurrent_tokens"] + n["ssm_scan_tokens"]
    return {"flops": ssm_flops(c, n),
            "bytes": float(n["ssm_state_rows"] * 2 * s["state"] * BF16
                           + tokens * s["n_mamba"]
                           * (s["ssm_token_io"] * BF16 + s["heads"] * F32))}


def gqa_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the attention scope: every cached row a
    chunk reads once a layer, queries in and results out once a token a
    layer."""
    s = sizes(c)
    per_layer = (n["gqa_keys"] * s["kv_row"]
                 + n["seq_tokens"] * 2 * s["q_row"]) * BF16
    return {"flops": gqa_flops(c, n), "bytes": float(per_layer * s["n_attn"])}


def grouped_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the grouped matmuls: the held experts'
    weights once a step a layer, each pair's latent row in and out and
    its expert-wide row out and in."""
    s = sizes(c)
    pairs = n["expert_tokens_mean"] * c["experts_held"]
    weights = n["seq_steps"] * s["n_moe"] * c["experts_held"] \
        * s["expert"] * BF16
    rows = pairs * 2 * (s["latent"] + s["width"]) * BF16
    return {"flops": grouped_flops(c, n), "bytes": float(weights + rows)}


#: the kernel families a roofline reader can ask for by name, each with
#: the counters it cannot do without
KERNEL_WORK = {
    "ssm": (ssm_work, ("ssm_recurrent_tokens", "ssm_scan_tokens",
                       "ssm_scan_pairs", "ssm_state_rows")),
    "gqa": (gqa_work, ("gqa_pairs", "gqa_keys", "seq_tokens")),
    "grouped": (grouped_work, ("expert_tokens_mean", "seq_steps"))}
#: what the whole step's count reads
STEP_COUNTERS = ("seq_tokens", "ssm_recurrent_tokens", "ssm_scan_tokens",
                 "ssm_scan_pairs", "gqa_pairs", "expert_tokens_mean")
