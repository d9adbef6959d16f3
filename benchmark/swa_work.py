"""Operations and bytes of the window-and-full attention decoder's serving
step, from the configuration's sizes and the plane's counters alone (what
the ALGORITHM needs: under a window the pairs and keys inside it only;
tile padding, pads of the token grid, keys a block holds beyond the window
and recomputation are the implementation's and are not counted).

Counters (``anomod.serve.seqplane.COUNTERS``, deltas over the window; the
plane counts a step's tokens once, whatever the number of layers):
``seq_tokens`` appended tokens; ``full_pairs`` / ``swa_pairs`` visible
(new, cached) attention pairs of a full / a sliding layer, the new token
itself among its keys; ``full_keys`` / ``swa_keys`` cached tokens whose
keys and values a chunk reads in a full / a sliding layer;
``expert_tokens_mean`` x ``experts_held``: token-expert pairs computed
here, summed over the sparse layers; ``seq_steps``.
"""

from __future__ import annotations

from benchmark.reference.swa_moe_decoder import FULL, SWA, kinds

BF16 = 2


def sizes(c: dict) -> dict:
    D, kv, hd = c["hidden_size"], c["num_key_value_heads"], c["head_dim"]
    layers = kinds(c)
    heads = {k: [H for kind, H, _ in layers if kind == k]
             for k in (FULL, SWA)}
    n_sparse = sum(mlp == "sparse" for _, _, mlp in layers)
    # multiply-adds a token outside the attention loops and the experts:
    # q, k, v, the gate and the output projection by the layer's own head
    # count; the dense MLP, or the router and the shared expert
    proj = sum(2 * D * H * hd + 2 * D * kv * hd + D * H
               for _, H, _ in layers)
    mlp = (len(layers) - n_sparse) * 3 * D * c["intermediate_size"] \
        + n_sparse * (D * c["num_experts"]
                      + 3 * D * c["shared_expert_intermediate_size"])
    return {"heads": heads, "n_sparse": n_sparse, "per_token": proj + mlp,
            "head": D * c["vocab_held"], "hd": hd, "kv_row": 2 * kv * hd,
            "expert": 3 * D * c["moe_intermediate_size"], "hidden": D,
            "width": c["moe_intermediate_size"]}


def attention_flops(c: dict, n: dict, kind: str) -> float:
    """Every layer of ``kind``: scores and weighted values, a visible
    pair a head."""
    s = sizes(c)
    pairs = n["full_pairs" if kind == FULL else "swa_pairs"]
    return 2.0 * pairs * 2 * s["hd"] * sum(s["heads"][kind])


def grouped_flops(c: dict, n: dict) -> float:
    """The held experts' three grouped matmuls, every sparse layer."""
    return 2.0 * n["expert_tokens_mean"] * c["experts_held"] \
        * sizes(c)["expert"]


def step_flops(c: dict, n: dict) -> float:
    """The whole steps' model FLOPs over the counted tokens."""
    s = sizes(c)
    return (2.0 * n["seq_tokens"] * (s["per_token"] + s["head"])
            + attention_flops(c, n, FULL) + attention_flops(c, n, SWA)
            + grouped_flops(c, n))


def _attention_work(c: dict, n: dict, kind: str) -> dict:
    s = sizes(c)
    keys = n["full_keys" if kind == FULL else "swa_keys"]
    io = sum(keys * s["kv_row"] + n["seq_tokens"] * 2 * H * s["hd"]
             for H in s["heads"][kind]) * BF16
    return {"flops": attention_flops(c, n, kind), "bytes": float(io)}


def full_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the full layers' attention scope: every
    cached row a chunk reads once a layer, queries in and results out
    once a token a layer."""
    return _attention_work(c, n, FULL)


def swa_work(c: dict, n: dict) -> dict:
    """The same of the sliding layers' scope: the rows inside the window."""
    return _attention_work(c, n, SWA)


def grouped_work(c: dict, n: dict) -> dict:
    """``flops`` and ``bytes`` of the grouped matmuls: the held experts'
    weights once a step a layer, each pair's hidden-wide row in and out
    and its expert-wide row out and in."""
    s = sizes(c)
    pairs = n["expert_tokens_mean"] * c["experts_held"]
    weights = n["seq_steps"] * s["n_sparse"] * c["experts_held"] \
        * s["expert"] * BF16
    rows = pairs * 2 * (s["hidden"] + s["width"]) * BF16
    return {"flops": grouped_flops(c, n), "bytes": float(weights + rows)}


#: the kernel families a roofline reader can ask for by name, each with
#: the counters it cannot do without
KERNEL_WORK = {
    "full": (full_work, ("full_pairs", "full_keys", "seq_tokens")),
    "swa": (swa_work, ("swa_pairs", "swa_keys", "seq_tokens")),
    "grouped": (grouped_work, ("expert_tokens_mean", "seq_steps"))}
#: what the whole step's count reads
STEP_COUNTERS = ("seq_tokens", "full_pairs", "swa_pairs",
                 "expert_tokens_mean")
