"""Plain reference of the configurations' semantics, independent of the
program: Taillard's instances and an exact depth-first branch and bound
for the permutation flow-shop with the LB1 and LB2 bounds, as the
sequential C programs of ivantag13/dist-GPU-accelerated-tree-search
define them (pfsp/pfsp_c.c, pfsp/lib/c_bound_simple.c,
pfsp/lib/c_bound_johnson.c; Taillard 1993, EJOR 64:278-285; Lageweg,
Lenstra and Rinnooy Kan 1978 for the two-machine bound).

It imports nothing of tpu_tree_search. It is slow: the benchmark's
runs compare against the answers recorded in `oracle/`, and the tests
use this module to derive the small rows of those records again from
first principles.

Counting: a child whose bound is below the incumbent is pushed and
counted in `tree` (the root is not counted); a leaf child is counted in
`sol` and, with a bound below the incumbent, becomes the incumbent.
Children are bounded as in the reference's forward branching: the
prefix's machine completion times, the unscheduled work, and the
smallest tails below each machine.
"""

from __future__ import annotations

import numpy as np

# Taillard's published generator seeds and optimal makespans, ta001-ta030
TIME_SEEDS = (
    873654221, 379008056, 1866992158, 216771124, 495070989,
    402959317, 1369363414, 2021925980, 573109518, 88325120,
    587595453, 1401007982, 873136276, 268827376, 1634173168,
    691823909, 73807235, 1273398721, 2065119309, 1672900551,
    479340445, 268827376, 1958948863, 918272953, 555010963,
    2010851491, 1519833303, 1748670931, 1923497586, 1829909967,
)
OPTIMA = (
    1278, 1359, 1081, 1293, 1235, 1195, 1234, 1206, 1230, 1108,
    1582, 1659, 1496, 1377, 1419, 1397, 1484, 1538, 1593, 1591,
    2297, 2099, 2326, 2223, 2291, 2226, 2273, 2200, 2237, 2178,
)


def instance(inst: int) -> np.ndarray:
    """Processing times (machines, jobs) of ta001-ta030: 20 jobs on 5,
    10 or 20 machines, from the Lehmer generator x <- 16807 x mod
    (2^31 - 1), each draw 1 + floor(99 * x / (2^31 - 1)) with the
    division in single precision, as the published C code does it."""
    machines = 5 if inst <= 10 else 10 if inst <= 20 else 20
    jobs = 20
    seed = TIME_SEEDS[inst - 1]
    out = np.empty((machines, jobs), np.int64)
    for i in range(machines):
        for j in range(jobs):
            k = seed // 127773
            seed = 16807 * (seed % 127773) - k * 2836
            if seed < 0:
                seed += 2147483647
            q = np.float32(seed) / np.float32(2147483647)
            out[i, j] = 1 + int(float(q) * 99.0)
    return out


def _tables(p: np.ndarray):
    m, n = p.shape
    tails = np.cumsum(p[::-1], axis=0)[::-1]
    min_tails = np.zeros(m, np.int64)
    min_tails[:-1] = tails[1:].min(axis=1)
    pairs = [(a, b) for a in range(m - 1) for b in range(a + 1, m)]
    ma0 = np.array([a for a, _ in pairs])
    ma1 = np.array([b for _, b in pairs])
    csum = np.concatenate([np.zeros((1, n), np.int64), np.cumsum(p, 0)])
    lags = csum[ma1] - csum[ma0 + 1]                    # (P, n)
    t0, t1 = p[ma0] + lags, p[ma1] + lags
    # Johnson's rule: jobs faster on the first machine by ascending
    # first time, then the rest by descending second time
    first = t0 < t1
    key = np.where(first, t0, -t1)
    order = np.lexsort((np.arange(n)[None, :].repeat(len(pairs), 0), key,
                        ~first), axis=-1)
    return min_tails, ma0, ma1, lags, order


def _lb1(front, remain, back):
    """One-machine bound, chained over the machines: (C, M) inputs."""
    tmp = front[:, 0] + remain[:, 0]
    lb = tmp + back[0]
    for i in range(1, front.shape[1]):
        tmp = np.maximum(tmp, front[:, i] + remain[:, i])
        lb = np.maximum(lb, tmp + back[i])
    return lb


def _lb2(p, front, unsched, tabs):
    """Two-machine Johnson bound over all machine pairs: (C, M) fronts,
    (C, n) 0/1 unscheduled flags."""
    min_tails, ma0, ma1, lags, order = tabs
    t0 = front[:, ma0].copy()                           # (C, P)
    t1 = front[:, ma1].copy()
    cols = np.arange(len(ma0))
    for k in range(p.shape[1]):
        job = order[:, k]                               # (P,)
        on = unsched[:, job] == 1                       # (C, P)
        t0n = t0 + p[ma0, job]
        t1n = np.maximum(t1, t0n + lags[cols, job]) + p[ma1, job]
        t0 = np.where(on, t0n, t0)
        t1 = np.where(on, t1n, t1)
    return np.maximum(t1 + min_tails[ma1], t0 + min_tails[ma0]).max(axis=1)


def search(p: np.ndarray, lb: int, ub: int) -> tuple[int, int, int]:
    """Exact DFS with initial incumbent `ub`: (tree, sol, best)."""
    m, n = p.shape
    tabs = _tables(p)
    min_tails = tabs[0]
    total = p.sum(axis=1)
    best, tree, sol = int(ub), 0, 0
    stack = [(np.arange(n), 0, np.zeros(m, np.int64))]
    while stack:
        perm, depth, front = stack.pop()
        jobs = perm[depth:]                             # child i takes job i
        c = len(jobs)
        fr = np.empty((c, m), np.int64)                 # children's fronts
        fr[:, 0] = front[0] + p[0, jobs]
        for k in range(1, m):
            fr[:, k] = np.maximum(fr[:, k - 1], front[k]) + p[k, jobs]
        unsched = np.ones((c, n), np.int64)
        unsched[:, perm[:depth]] = 0
        unsched[np.arange(c), jobs] = 0
        remain = total[None, :] - (p[:, perm[:depth]].sum(axis=1)[None, :]
                                   + p[:, jobs].T)
        if lb == 1:
            bound = _lb1(fr, remain, min_tails)
        else:
            bound = _lb2(p, fr, unsched, tabs)
        for i in range(c):
            if depth + 1 == n:
                sol += 1
                best = min(best, int(bound[i]))
            elif bound[i] < best:
                child = perm.copy()
                child[depth], child[depth + i] = child[depth + i], \
                    child[depth]
                stack.append((child, depth + 1, fr[i]))
                tree += 1
    return tree, sol, best
