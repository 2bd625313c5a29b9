"""Headline single-device table: solve ta021-ta030 end-to-end on chip.

Run every instance of the reference's published
single-GPU campaign (pfsp/data/single-GPU.py) to the proven optimum on
one chip and tabulate against the V100/MI50 columns. LB2 with ub=opt
(the reference's campaign default operating point is ub=opt; its lb
default is LB1 — the repo chooses its strongest bound, which BASELINE.md
allows). Segmented driving keeps each dispatch bounded and
appends one JSON line per instance so a crash loses nothing.

    nohup python -u tools/run_single_device_table.py \
        > /tmp/table.log 2>&1 &
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from tpu_tree_search.utils import compile_cache  # noqa: E402

compile_cache.enable()

from tpu_tree_search.engine import checkpoint, device  # noqa: E402
from tpu_tree_search.ops import batched  # noqa: E402
from tpu_tree_search.problems import taillard  # noqa: E402

from tpu_tree_search.utils import config as _cfg  # noqa: E402

OUT = _cfg.env_str("TTS_TABLE_OUT")
CHUNK = 32768
CAPACITY = 1 << 22
SEG = 2000

# V100 single-GPU runtimes, instance order ta29,30,22,27,23,28,25,26,24,21
# (reference pfsp/data/single-GPU.py:6,21)
V100 = {29: 4.18, 30: 4.91, 22: 5.63, 27: 19.82, 23: 41.04, 28: 73.75,
        25: 81.97, 26: 176.40, 24: 738.93, 21: 1308.79}
MI50 = {29: 7.56, 30: 9.14, 22: 10.52, 27: 38.08, 23: 79.44, 28: 140.81,
        25: 159.35, 26: 379.45, 24: 1445.49, 21: 2538.23}


def solve(inst: int) -> dict:
    p = taillard.processing_times(inst)
    ub = taillard.optimal_makespan(inst)
    tables = batched.make_tables(p)
    jobs = p.shape[1]
    state = device.init_state(jobs, CAPACITY, ub, p_times=p)
    t0 = time.perf_counter()

    def run_fn(s, target):
        return device.run(tables, s, 2, CHUNK, max_iters=target)

    def heartbeat(r):
        # segment deltas identify stalls (host load 0 for minutes) so
        # contaminated rows can be re-run or annotated
        print(f"  [seg {r.segment}] iters={r.iters} tree={r.tree} "
              f"t={r.elapsed:.1f}s", flush=True)

    out = checkpoint.run_segmented(run_fn, state, segment_iters=SEG,
                                  heartbeat=heartbeat)
    elapsed = time.perf_counter() - t0
    assert int(out.size) == 0 and not bool(out.overflow)
    assert int(out.best) == ub, (inst, int(out.best), ub)
    return {"inst": inst, "elapsed_s": round(elapsed, 2),
            "tree": int(out.tree), "sol": int(out.sol),
            "best": int(out.best), "evals": int(out.evals),
            "iters": int(out.iters),
            "v100_s": V100[inst], "mi50_s": MI50[inst],
            "vs_v100": round(V100[inst] / elapsed, 3),
            "vs_mi50": round(MI50[inst] / elapsed, 3)}


def main():
    done = set()
    if os.path.exists(OUT):
        with open(OUT) as f:
            done = {json.loads(ln)["inst"] for ln in f if ln.strip()}
    order = ([int(x) for x in sys.argv[1:]] or
             [29, 30, 22, 27, 23, 28, 25, 26, 24])  # ta021 solved separately
    for inst in order:
        if inst in done:
            print(f"ta{inst:03d}: already done, skipping", flush=True)
            continue
        print(f"ta{inst:03d}: solving...", flush=True)
        row = solve(inst)
        with open(OUT, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"ta{inst:03d}: {row['elapsed_s']}s "
              f"(V100 {row['v100_s']}s, x{row['vs_v100']}) "
              f"tree={row['tree']}", flush=True)


if __name__ == "__main__":
    main()
