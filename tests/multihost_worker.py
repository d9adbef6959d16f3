"""Worker process for the 2-process multi-host mesh test.

Launched by tests/test_multihost.py as
``python tests/multihost_worker.py <process_id> <num_processes> <port>``.
Each worker pins 4 virtual CPU devices, joins the jax.distributed
coordinator, builds the hybrid (dcn, data) mesh, and runs three
cross-process exercises:

- a psum over both mesh axes (the gradient/sketch-state reduction shape),
- an HLL register pmax-merge where each process observes a disjoint item
  range (the distinct-count plane of the replay pipeline, merged over DCN),
- a full GCN training step with the batch dp-sharded over (dcn, data) and
  replicated params: each process stages only ITS half of the batch, XLA
  derives the cross-process gradient psum from the shardings — the
  multi-host analog of the reference's per-worker collection + merge, for
  training.

Prints one ``MHRESULT {json}`` line; the parent asserts both processes
produce identical, correct values.
"""

import json
import os
import sys


def main() -> int:
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    local_devices = 4
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={local_devices}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from anomod.parallel.multihost import (dcn_data_parallel_spec,
                                           initialize_distributed,
                                           make_hybrid_mesh,
                                           process_local_array,
                                           replicated_value)
    initialize_distributed(f"127.0.0.1:{port}", nproc, pid)

    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from anomod.ops.hll import hll_add, hll_estimate, hll_init
    from anomod.parallel.collectives import pmax_merge_hll

    mesh = make_hybrid_mesh()
    spec = dcn_data_parallel_spec(mesh)
    n_global = nproc * local_devices

    # --- psum across the process boundary -------------------------------
    fn = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, tuple(mesh.axis_names)),
        mesh=mesh, in_specs=spec, out_specs=P()))
    local = np.arange(pid * local_devices, (pid + 1) * local_devices,
                      dtype=np.float32)
    psum = float(replicated_value(
        fn(process_local_array(mesh, spec, local))).ravel()[0])

    # --- HLL sketch-state merge over DCN --------------------------------
    p = 10
    # each global shard observes a disjoint 500-item range
    per_shard = np.stack([
        hll_add(hll_init(p=p), np.arange(d * 500, (d + 1) * 500,
                                         dtype=np.uint64), p=p)
        for d in range(pid * local_devices, (pid + 1) * local_devices)])
    merge = jax.jit(shard_map(
        lambda r: pmax_merge_hll(r[0], tuple(mesh.axis_names)),
        mesh=mesh, in_specs=spec, out_specs=P()))
    merged = replicated_value(merge(
        process_local_array(mesh, spec, per_shard)))
    est = float(hll_estimate(merged))

    # --- dp training step across the process boundary -------------------
    # THE shared distributed step (anomod.parallel.train), on the hybrid
    # mesh with process-local staging: each process passes only its rows.
    from anomod.parallel.train import make_distributed_train_step
    from anomod.rca import _stack, build_dataset

    samples, _ = build_dataset("TT", seeds=[0], n_traces=8, n_windows=4)
    n_batch = 2 * n_global                      # dp axis | global devices
    stacked = _stack((samples * ((n_batch // len(samples)) + 1))[:n_batch])
    params, opt_state, train_step, put_batch = make_distributed_train_step(
        "gcn", stacked, mesh, stage="process-local")
    rows = slice(pid * (n_batch // nproc), (pid + 1) * (n_batch // nproc))
    batch = put_batch({k: v[rows] for k, v in stacked.items()})
    params, opt_state, loss = train_step(params, opt_state, batch)
    loss = float(replicated_value(loss))
    leaf0 = sorted(jax.tree_util.tree_leaves_with_path(params),
                   key=lambda kv: str(kv[0]))[0][1]
    param_sum = float(np.sum(replicated_value(leaf0)))

    print("MHRESULT " + json.dumps({
        "pid": pid,
        "process_count": jax.process_count(),
        "global_devices": jax.device_count(),
        "psum": psum,
        "expected_psum": float(sum(range(n_global))),
        "hll_estimate": est,
        "true_distinct": n_global * 500,
        "train_loss": loss,
        "param_sum": param_sum,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
