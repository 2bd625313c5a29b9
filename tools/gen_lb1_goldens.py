"""Generate LB1 / LB1_d goldens from the reference's own library.

The repo's LB1 tree counts (the basis of the "published V100 table is
de facto LB2" finding, tests/golden/pfsp_20x20_full.jsonl) were
never goldened against the reference the way the LB2 counts are
(tests/golden/pfsp_lb2_ub1.jsonl). This script drives the reference's
verbatim decompose/lb1_bound/lb1_children_bounds through the
matrix-input wrapper (.ref_build/wrap/pfsp/pfsp_mat.c — the same
oracle binary tools/gen_matrix_goldens.py uses) on every 20-job
Taillard instance at ub=opt (sgpu_launch.sh:84 pins `-l 1`;
PFSP_lib.c:7-43 is the counting semantics being pinned).

Billion-node LB1 trees (the ta022/27/29/30 class) are goldened as
PREFIXES: the wrapper stops after a fixed number of popped parents and
records the exact counters at that point. The native engine reproduces
the same DFS order as the reference (LIFO pool, slot-order child
pushes), so prefix counts are exact invariants; rows record
`expanded < max_nodes` as `complete` so full-tree rows double as
order-independent goldens for the device engine.

    python tools/gen_lb1_goldens.py [--budget 500000]

Writes tests/golden/pfsp_lb1_ub1.jsonl (lb=1) and
tests/golden/pfsp_lb1d_ub1.jsonl (lb=0).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WRAPPER = os.path.join(REPO, ".ref_build", "wrap", "pfsp", "pfsp_mat.out")


def reference_counts(wrapper, p, lb, ub, max_nodes):
    with tempfile.NamedTemporaryFile("w", suffix=".mat", delete=False) as f:
        f.write(f"{p.shape[0]} {p.shape[1]}\n")
        for row in p:
            f.write(" ".join(map(str, row)) + "\n")
        path = f.name
    try:
        out = subprocess.run(
            [wrapper, path, str(lb), str(ub), str(max_nodes)],
            capture_output=True, text=True, timeout=600, check=True)
    finally:
        os.unlink(path)
    golden = [ln for ln in out.stdout.splitlines()
              if ln.startswith("GOLDEN ")][0]
    expanded = [ln for ln in out.stdout.splitlines()
                if ln.startswith("EXPANDED ")][0]
    tree, sol, best = (int(x) for x in golden.split()[1:])
    return tree, sol, best, int(expanded.split()[1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wrapper", default=WRAPPER)
    ap.add_argument("--budget", type=int, default=500_000,
                    help="popped-parent cap for the prefix goldens")
    args = ap.parse_args()

    if not os.path.exists(args.wrapper):
        raise SystemExit(
            f"{args.wrapper} missing — compile it first (see "
            "tools/gen_matrix_goldens.py --help for the recipe; set "
            "MAX_JOBS=50 in lib/macro.h)")

    from tpu_tree_search import native  # noqa: E402
    from tpu_tree_search.problems import taillard  # noqa: E402

    for lb, fname in ((1, "pfsp_lb1_ub1.jsonl"), (0, "pfsp_lb1d_ub1.jsonl")):
        rows = []
        for inst in range(1, 31):
            p = np.asarray(taillard.processing_times(inst), np.int32)
            ub = int(taillard.optimal_makespan(inst))
            tree, sol, best, expanded = reference_counts(
                args.wrapper, p, lb, ub, args.budget)
            complete = expanded < args.budget
            # cross-check the native engine right here — a golden that
            # the in-repo oracle cannot reproduce must never be written
            nt, ns, nb, ne = native.search(
                p, lb_kind=lb, init_ub=ub,
                max_nodes=0 if complete else args.budget)
            assert (nt, ns, nb) == (tree, sol, best), (
                f"native disagrees with reference on ta{inst:03d} lb{lb}: "
                f"native=({nt},{ns},{nb}) ref=({tree},{sol},{best})")
            rows.append({"inst": inst, "lb": lb, "ub": 1, "tree": tree,
                         "sol": sol, "best": best,
                         "complete": complete,
                         "max_nodes": 0 if complete else args.budget})
            print(f"ta{inst:03d} lb{lb}: tree={tree} sol={sol} best={best}"
                  f" {'complete' if complete else f'prefix@{args.budget}'}",
                  flush=True)
        out = os.path.join(REPO, "tests", "golden", fname)
        with open(out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        print(f"wrote {len(rows)} rows to {out}")


if __name__ == "__main__":
    main()
