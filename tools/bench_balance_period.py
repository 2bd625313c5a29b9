"""Ground the balance-period default with ON-CHIP cost data.

The round-3 sensitivity table measured balance_period on
the virtual CPU mesh, where collectives serialize on the host — its
wall-clock preference for sparse periods (16 beat 4 by 1.7x) is an
artifact of that backend, and the default was never defended.

This tool prices the period where it matters: the per-iteration cost of
the FULL SPMD program at each period, on IDENTICAL warmed state and
windows. The measurement harness itself now lives in
tpu_tree_search/tune/probe.py (ProbeHarness / measure_balance_periods)
— the SAME warmed same-state method the offline Autotuner's probes
run, so this sweep and the tuner can never measure different things;
this file is the thin CLI that survives for operators who want the
hand-run sweep. The spread side of the tradeoff (per-worker tree CV vs
period) is backend-independent and comes from the round-3 CPU-mesh
table; this measurement supplies the cost side.

    python tools/bench_balance_period.py [--inst 21] [--lb 2]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_tree_search.utils import compile_cache  # noqa: E402

compile_cache.enable()

from tpu_tree_search.problems import taillard  # noqa: E402
from tpu_tree_search.tune.probe import measure_balance_periods  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--inst", type=int, default=21)
    ap.add_argument("--lb", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=32768)
    ap.add_argument("--capacity", type=int, default=1 << 22)
    ap.add_argument("--warm", type=int, default=500)
    ap.add_argument("--iters", type=int, default=256)
    ap.add_argument("--periods", type=int, nargs="*",
                    default=[1, 2, 4, 8, 16, 64])
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    p = taillard.processing_times(args.inst)
    ub = taillard.optimal_makespan(args.inst)
    rows = measure_balance_periods(
        p, args.lb, args.chunk, args.periods, capacity=args.capacity,
        warm_iters=args.warm, window_iters=args.iters,
        repeats=args.repeats, init_ub=ub)
    for row in rows:
        print(json.dumps(row), flush=True)
    print(json.dumps({"inst": args.inst, "lb": args.lb,
                      "chunk": args.chunk,
                      "window_iters": args.iters,
                      "rows": rows,
                      "note": "identical warmed state across periods "
                              "(tune/probe.ProbeHarness)"}))


if __name__ == "__main__":
    main()
