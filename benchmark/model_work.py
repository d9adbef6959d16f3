"""Operations and bytes of the latent-attention, routed-expert decoder's
serving step, from the configuration's sizes and the plane's counters
alone (what the ALGORITHM needs in the form the size rule chose; tile
padding, pads of the token grid and recomputation are the
implementation's and are not counted).

Counters (``anomod.serve.seqplane.COUNTERS``, deltas over the window):
``seq_tokens`` appended tokens; ``seq_pairs`` visible (new, cached)
pairs, the new token itself among its keys; ``seq_absorbed_pairs`` those
of absorbed chunks; ``seq_absorbed_tokens``; ``seq_expanded_keys`` cached
tokens whose keys and values expanded chunks materialise; ``seq_keys``
cached tokens whose latents a chunk reads; ``expert_tokens_mean`` x
``experts_held``: token-expert pairs computed here, summed over layers.
"""

from __future__ import annotations

BF16 = 2


def sizes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    R, Q = c["kv_lora_rank"], c["q_lora_rank"]
    n_dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return {
        "layers": c["num_hidden_layers"], "n_dense": n_dense,
        "n_moe": c["num_hidden_layers"] - n_dense,
        # multiply-adds a token, a layer, outside W_kvb
        "attn_proj": D * Q + Q * H * (nope + rope) + D * (R + rope)
        + H * v * D,
        "w_kvb": R * H * (nope + v),
        "pair_absorbed": H * (2 * R + rope),
        "pair_expanded": H * (nope + rope + v),
        "dense_ffn": 3 * D * c["intermediate_size"],
        "expert": 3 * D * c["moe_intermediate_size"],
        "router": D * c["n_routed_experts"],
        "head": D * c["vocab_held"],
        "latent": R + rope}


def attention_flops(c: dict, n: dict) -> float:
    """All layers' latent attention: scores and values per visible pair in
    the chunk's form, ``W_kvb`` once per new token (absorbed: the query
    through its key half, the result through its value half) or per
    cached token (expanded)."""
    s = sizes(c)
    expanded_pairs = n["seq_pairs"] - n["seq_absorbed_pairs"]
    per_layer = (n["seq_absorbed_pairs"] * s["pair_absorbed"]
                 + expanded_pairs * s["pair_expanded"]
                 + (n["seq_absorbed_tokens"] + n["seq_expanded_keys"])
                 * s["w_kvb"])
    return 2.0 * per_layer * s["layers"]


def grouped_flops(c: dict, n: dict) -> float:
    """The held experts' grouped matmuls, every layer."""
    return 2.0 * n["expert_tokens_mean"] * c["experts_held"] \
        * sizes(c)["expert"]


def step_flops(c: dict, n: dict) -> float:
    """The whole steps' model FLOPs over the counted tokens."""
    s = sizes(c)
    per_token = (s["layers"] * s["attn_proj"]
                 + s["n_dense"] * s["dense_ffn"]
                 + s["n_moe"] * (s["router"] + s["expert"]
                                 * c["n_shared_experts"])
                 + s["head"])
    return (2.0 * n["seq_tokens"] * per_token + attention_flops(c, n)
            + grouped_flops(c, n))


def attention_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the append-attention kernels: every
    latent a chunk reads once a layer, queries in and results out once a
    token a layer."""
    s = sizes(c)
    H = c["num_attention_heads"]
    per_layer = (n["seq_keys"] * s["latent"]
                 + n["seq_tokens"] * H * (c["qk_nope_head_dim"]
                                          + c["qk_rope_head_dim"]
                                          + c["v_head_dim"])) * BF16
    return {"flops": attention_flops(c, n),
            "bytes": float(per_layer * s["layers"])}


def grouped_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the grouped matmuls: the held experts'
    weights once a step a layer, each pair's row in and out."""
    s = sizes(c)
    pairs = n["expert_tokens_mean"] * c["experts_held"]
    weights = n["seq_steps"] * s["n_moe"] * c["experts_held"] \
        * s["expert"] * BF16
    rows = pairs * (2 * c["hidden_size"]
                    + 3 * c["moe_intermediate_size"]) * BF16
    return {"flops": grouped_flops(c, n), "bytes": float(weights + rows)}


#: the kernel families a roofline reader can ask for by name
KERNEL_WORK = {"attention": attention_work, "grouped": grouped_work}
