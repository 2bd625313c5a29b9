"""Measure the SPMD program's per-step tax on ONE real chip.

The pod-scale projection multiplies the single-device
chip rate by the CPU-mesh's device-count invariance; the missing term
is what the distributed program itself costs per step on real hardware
— shard_map, the cond-gated balance round, the pmin incumbent fold.
That term is measurable on a mesh of ONE real chip: the program is the
full SPMD loop (same collectives, degenerate membership), so its
per-iteration cost against the plain single-device loop is exactly the
per-chip overhead (collective latency at D>1 rides ICI and is priced
separately by the CPU-mesh invariance tests).

Method: ONE pool state, warmed past the ramp with `device.run`, is the
common input; the plain `jit(while(step))` loop and the full
`build_dist_loop` program (stacked to a 1-chip mesh) are then timed on
IDENTICAL state and iteration windows, warming each executable at its
final input signature first. Two earlier methodologies gave garbage and
are kept out on purpose: timing two *independently warmed* searches
compares different pool states (±10% swings either way), and timing a
window whose input signature differs from its warm-up catches a fresh
XLA compile (~100 s) inside the window — the first version of this tool
reported a fictitious 2700% "tax" that way.

    python tools/bench_spmd_tax.py [--inst 21] [--lb 2] [--chunk 32768]
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from tpu_tree_search.utils import compile_cache  # noqa: E402

compile_cache.enable()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpu_tree_search.engine import device, distributed  # noqa: E402
from tpu_tree_search.ops import batched  # noqa: E402
from tpu_tree_search.parallel.mesh import worker_mesh  # noqa: E402
from tpu_tree_search.problems import taillard  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--inst", type=int, default=21)
    ap.add_argument("--lb", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=32768)
    ap.add_argument("--capacity", type=int, default=1 << 22)
    ap.add_argument("--warm", type=int, default=500)
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--balance-period", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    p = taillard.processing_times(args.inst)
    ub = taillard.optimal_makespan(args.inst)
    tables = batched.make_tables(p)
    jobs, machines = p.shape[1], p.shape[0]
    chunk, lb = args.chunk, args.lb

    state = device.init_state(jobs, args.capacity, ub, p_times=p)
    state = device.run(tables, state, lb, chunk, max_iters=args.warm)
    state.size.block_until_ready()
    assert not bool(state.overflow) and int(state.size) > 0
    base = int(state.iters)
    target = base + args.iters

    def timed(call):
        call()  # warm/compile at the exact final input signature
        best = float("inf")
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            call()
            best = min(best, time.perf_counter() - t0)
        return best / args.iters * 1e3

    # plain single-device loop (device.run's compiled while_loop)
    def single():
        out = device.run(tables, state, lb, chunk, max_iters=target)
        out.size.block_until_ready()

    ms_single = timed(single)

    # the full SPMD program on a 1-chip mesh, same state stacked
    adt = device.aux_dtype(p)
    tc, mt = distributed.balance_defaults(chunk, jobs, machines, 1,
                                          distributed.MIN_SEED,
                                          aux_itemsize=adt.itemsize)
    limit = min(device.row_limit(args.capacity, chunk, jobs),
                args.capacity - tc)

    def mls(t, lim):
        return functools.partial(device.step, t, lb, chunk, limit=lim)

    loop = distributed.build_dist_loop(
        worker_mesh(1), tables, mls, args.balance_period, tc, mt, limit)
    stacked = tuple(x[None] for x in state)

    def dist():
        out = loop(tables, jnp.int64(target),
                   jnp.int32(distributed.I32_MAX), *stacked)
        jax.block_until_ready(out)

    ms_dist = timed(dist)

    print(json.dumps({
        "inst": args.inst, "lb": lb, "chunk": chunk,
        "balance_period": args.balance_period,
        "window_iters": args.iters, "repeats": args.repeats,
        "single_ms_per_iter": round(ms_single, 4),
        "dist1_ms_per_iter": round(ms_dist, 4),
        "spmd_tax_pct": round((ms_dist / ms_single - 1) * 100, 2),
    }))


if __name__ == "__main__":
    main()
