"""Subprocess worker for the 2-process multihost (DCN-tier) smoke test.

Each process owns 4 virtual CPU devices; jax.distributed.initialize
joins them into one 8-device global mesh — the single-machine stand-in
for the reference's one-MPI-rank-per-node launch (mpirun --map-by
ppr:1:node, README.md:109-116). The SAME SPMD program then runs
unchanged; only the mesh spans two controllers, which exercises the
multi-controller branches (_to_mesh, _fetch, checkpoint._to_np).

Usage: python tests/_multihost_worker.py PORT PROCESS_ID NUM_PROCESSES \
           [MODE CHECKPOINT_PATH [MAX_ROUNDS]]

MODE "plain" (default) runs to completion without durability. "trunc"
runs the SEGMENTED driver with a checkpoint and a round ceiling (the
kill half of the multihost kill/resume invariant: only process 0 writes
the file — checkpoint.save rank-gating). "resume" loads that checkpoint
on every process and finishes the search.
"""

import json
import os
import sys


def main():
    port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "plain"
    ckpt = sys.argv[5] if len(sys.argv) > 5 else None
    max_rounds = int(sys.argv[6]) if len(sys.argv) > 6 else None
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_num_cpu_devices", 4)     # per process
    jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                               num_processes=nproc, process_id=pid)
    assert jax.process_count() == nproc
    assert jax.device_count() == 4 * nproc

    from tpu_tree_search.engine import distributed
    from tpu_tree_search.problems.pfsp import PFSPInstance

    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=0)
    opt = inst.brute_force_optimum()
    kw = {}
    if mode in ("trunc", "resume"):
        kw = dict(segment_iters=8, checkpoint_path=ckpt, heartbeat=None)
        if mode == "trunc":
            kw["max_rounds"] = max_rounds
    res = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                             chunk=8, capacity=1 << 12, min_seed=4, **kw)
    print("RESULT " + json.dumps({
        "process": pid,
        "tree": res.explored_tree,
        "sol": res.explored_sol,
        "best": res.best,
        "complete": res.complete,
    }), flush=True)


if __name__ == "__main__":
    main()
