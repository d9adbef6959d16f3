"""Mesh construction + data sharding helpers.

The reference's only "distributed" machinery is kubectl/HTTP fan-out and
thread pools (SURVEY.md §2.4).  Here distribution is first-class: a
``jax.sharding.Mesh`` over however many chips exist (one axis ``data`` for
stream sharding; model axes come with the GNN), XLA collectives over ICI/DCN.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def make_mesh(n_devices: Optional[int] = None, axis: str = "data"):
    """1-D device mesh over the first n devices (defaults to all).

    Requesting more devices than are attached is an error, not a silent
    shrink — a throughput record labeled "8 devices" must have run on 8.
    """
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if n_devices is not None:
        if not 0 < n_devices <= len(devs):
            raise ValueError(
                f"requested a {n_devices}-device mesh but "
                f"{len(devs)} device(s) are attached")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def shard_chunks(chunks: dict, n_shards: int, dead_sid: int) -> dict:
    """Split the leading (chunk) dim across shards: [N, C] -> [D, N/D, C].

    Pads the chunk count to a multiple of n_shards with dead chunks
    (sid = ``dead_sid``, valid = 0) so every shard gets identical shapes.
    ``dead_sid`` must be the config's padding id (``cfg.sw``) — inferring
    it from the data (the old ``sid.max()`` heuristic) silently picked a
    REAL segment whenever the corpus length was an exact chunk multiple,
    and the HLL plane then counted the fill rows' phantom trace id.
    """
    out = {}
    n_chunks = next(iter(chunks.values())).shape[0]
    pad = (-n_chunks) % n_shards
    for k, v in chunks.items():
        if pad:
            fill = np.zeros((pad,) + v.shape[1:], v.dtype)
            if k == "sid":
                fill[:] = dead_sid
            v = np.concatenate([v, fill], axis=0)
        out[k] = v.reshape(n_shards, -1, *v.shape[1:])
    return out
