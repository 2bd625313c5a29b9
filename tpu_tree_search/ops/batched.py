"""Batched JAX bound kernels: (B,) parent nodes -> (B, J) child bounds.

This is the TPU replacement for the reference's CUDA bound kernels
(reference: pfsp/lib/bounds_gpu.cu, pfsp/lib/PFSP_gpu_lib.cu:43-127).
Where the GPU code launches one thread per (parent, child) with ragged
`nodeIndex`/`sumOffSets` maps, the TPU version evaluates a *dense*
`(batch, jobs)` grid of candidate children — slot `i` of parent `b` is the
child created by swapping `prmu[b, depth] <-> prmu[b, i]` — and masks the
slots `i < depth` that do not correspond to real children. Wasted lanes are
the price of static shapes; they vanish as depth grows.

Key algebraic fact used throughout: a child's scheduled prefix is its
parent's prefix plus one appended job, so the child's machine-completion
vector (`front`) is one O(machines) `add_forward` chain away from the
parent's — no per-child O(jobs * machines) DP is needed. The machine-axis
max-plus chains are unrolled Python loops over `machines <= 20`, which XLA
fuses into a handful of vector ops over the (B, J) lanes.

All engines branch forward-only, so the suffix is empty, `limit2 == jobs`,
and `back == min_tails` (reference: c_bound_simple.c:78-81).

Dtypes: permutations int16, bound arithmetic int32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import tracelog
from . import reference as ref

I32_MAX = jnp.int32(2**31 - 1)


class BoundTables(NamedTuple):
    """Device-resident precomputed tables for all three bounds.

    The LB1 part mirrors `lb1_bound_data` (reference: c_bound_simple.h:21-27);
    the LB2 part mirrors `lb2_bound_data` (c_bound_johnson.h:32-40) but with
    the Johnson schedules pre-gathered into contiguous per-pair arrays so the
    device never chases job-id indirection for processing times.
    """

    p: jax.Array          # (M, J) int32 processing times
    p_t: jax.Array        # (J, M) int32 transpose (gather-friendly)
    min_tails: jax.Array  # (M,) int32
    total_work: jax.Array  # (M,) int32 = p.sum(axis=1)
    # LB2 tables, one row per machine pair (P = M*(M-1)/2):
    ma0: jax.Array        # (P,) int32 first machine of pair
    ma1: jax.Array        # (P,) int32 second machine
    js: jax.Array         # (P, J) int32 job ids in Johnson order
    ptm0_js: jax.Array    # (P, J) int32 p[ma0, js] in Johnson order
    ptm1_js: jax.Array    # (P, J) int32 p[ma1, js]
    lag_js: jax.Array     # (P, J) int32 lags[pair, js]


# pair count of the strong-pair prefilter tier (engine/device.step):
# calibration shows the top frequency-ordered pairs reproduce the full
# 190-pair prune decision for >99.5% of pruned children on the 20x20
# class. 24 measured fastest end-to-end on chip (r3 sweep over
# {16,20,24,28,32,48,64}: 41.4M evals/s vs 39.4M at 32 on ta021, with
# bit-identical explored trees — the prefilter is a pure perf knob)
PAIR_PREFILTER = 24


@functools.lru_cache(maxsize=8)
def _calibration_samples(J: int, n_samples: int = 2048, seed: int = 0):
    """The synthetic partial schedules the pair order is calibrated on,
    drawn exactly as `reference.calibrate_pair_order` draws them: each
    row a permutation, each job's position in it, and a depth. They
    depend on J alone, so they are drawn once per J."""
    rng = np.random.default_rng(seed)
    prmu = np.argsort(rng.random((n_samples, J)), axis=1)
    lo = max(1, J // 4)
    depth = rng.integers(lo, max(lo + 1, J - 1), n_samples)
    out = (prmu.astype(np.int32), np.argsort(prmu, axis=1).astype(np.int32),
           depth.astype(np.int32))
    for a in out:
        a.flags.writeable = False
    return out


def _hot(idx, size: int):
    """(size, len(idx)) one-hot columns: column c is 1 at row idx[c]."""
    return jnp.arange(size)[:, None] == idx[None, :]


def _mm(a, b):
    """Integer matmul through the MXU, for a one-hot selection: exact at
    HIGHEST precision while every value is below 2^24. Per-element
    dynamic gathers serialize on TPU; these matmuls do not."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


@jax.jit
def _strongest_first(p_t, min_tails, prmu, pos, depth,
                     ma0, ma1, js, pt0, pt1, lag):
    """The pair arrays `ma0, ma1, js, pt0, pt1, lag`, taken in the order
    of `reference.calibrate_pair_order`: by how often each pair attains
    the LB2 max over the sampled partial schedules, most often first,
    ties by pair index. The same arithmetic in int32 (exact below the
    2^24 ceiling `make_tables` checks) as one program on the device,
    which the host issues and never waits on."""
    n, J = prmu.shape
    M = p_t.shape[1]

    def add_job(front, col):            # one prefix position
        q, job = col
        pj = _mm(_hot(job, J).T, p_t)                 # (n, M)
        chain = front[:, 0] + pj[:, 0]
        cols = [chain]
        for k in range(1, M):
            chain = jnp.maximum(chain, front[:, k]) + pj[:, k]
            cols.append(chain)
        new = jnp.stack(cols, axis=1)
        return jnp.where((q < depth)[:, None], new, front), None

    front, _ = jax.lax.scan(add_job, jnp.zeros((n, M), jnp.int32),
                            (jnp.arange(J - 1), prmu.T[:J - 1]))
    unsched = pos >= depth[:, None]                   # (n, J) by job id

    def johnson(carry, col):            # one Johnson position, all pairs
        t0, t1 = carry
        job, a, b, lg = col                           # each (P,)
        active = _mm(unsched, _hot(job, J)) > 0       # (n, P)
        n0 = t0 + a
        n1 = jnp.maximum(t1, n0 + lg) + b
        return (jnp.where(active, n0, t0), jnp.where(active, n1, t1)), None

    sel0, sel1 = _hot(ma0, M), _hot(ma1, M)           # (M, P)
    (t0, t1), _ = jax.lax.scan(johnson, (_mm(front, sel0), _mm(front, sel1)),
                               (js.T, pt0.T, pt1.T, lag.T))
    per_pair = jnp.maximum(t1 + _mm(min_tails[None], sel1),
                           t0 + _mm(min_tails[None], sel0))
    # argmax keeps the first max, as numpy's does
    best = jnp.argmax(per_pair, axis=1)
    freq = (best[:, None] == jnp.arange(ma0.shape[0])).sum(axis=0)
    order = jnp.argsort(-freq, stable=True)
    return tuple(jnp.take(x, order, axis=0)
                 for x in (ma0, ma1, js, pt0, pt1, lag))


def pair_split(t: BoundTables, k: int):
    """(head, tail) BoundTables whose pair arrays are the first k /
    remaining P-k rows. max(head sweep, tail sweep) == the full LB2 —
    used by the two-phase engine's prefilter tier."""
    def cut(sl):
        return t._replace(ma0=t.ma0[sl], ma1=t.ma1[sl], js=t.js[sl],
                          ptm0_js=t.ptm0_js[sl], ptm1_js=t.ptm1_js[sl],
                          lag_js=t.lag_js[sl])
    return cut(slice(None, k)), cut(slice(k, None))


def make_tables(p_times: np.ndarray) -> BoundTables:
    """Host-side precompute; the analogue of `lb1_alloc_gpu`/`lb2_alloc_gpu`
    (reference: PFSP_gpu_lib.cu:154-200). Machine pairs are stored
    strongest-first (see _strongest_first)."""
    lb1 = ref.make_lb1_data(p_times)
    lb2 = ref.make_lb2_data(lb1)
    p = np.asarray(p_times, dtype=np.int32)
    # The TPU pair-sweep kernel (pallas_expand._lb2_kernel) runs its
    # Johnson chain in f32, which is exact only while every partial
    # completion value stays below 2^24. A sound ceiling on any chain
    # value is front+lag accumulation bounded by twice the total work
    # plus the largest tail; enforce it HERE (host side, concrete
    # values) because inside jit the magnitudes are untraceable.
    ceiling = 2 * int(p.sum()) + int(np.asarray(lb1.min_tails).max())
    if ceiling >= 1 << 24:
        raise ValueError(
            f"instance magnitudes too large for the f32-exact LB2 kernel "
            f"(bound ceiling {ceiling} >= 2^24); rescale processing times")
    ma0 = np.asarray(lb2.pairs_m1)
    ma1 = np.asarray(lb2.pairs_m2)
    js = np.asarray(lb2.johnson_schedules)
    pt0 = p[ma0[:, None], js]
    pt1 = p[ma1[:, None], js]
    lag = np.take_along_axis(lb2.lags, lb2.johnson_schedules, axis=1)
    pairs = tuple(np.asarray(x, dtype=np.int32)
                  for x in (ma0, ma1, js, pt0, pt1, lag))
    # calibrate only when the prefilter can consume the order (enough
    # pairs to split into a strong head and a tail)
    if len(ma0) > 2 * PAIR_PREFILTER and p.shape[1] >= 3:
        samples = _calibration_samples(p.shape[1])
        with tracelog.span("tables.calibrate", pairs=len(ma0),
                           samples=len(samples[2])):
            pairs = _strongest_first(
                p.T, np.asarray(lb1.min_tails, dtype=np.int32), *samples,
                *pairs)
    else:
        pairs = tuple(jnp.asarray(x) for x in pairs)
    return BoundTables(
        p=jnp.asarray(p),
        p_t=jnp.asarray(p.T.copy()),
        min_tails=jnp.asarray(lb1.min_tails, dtype=jnp.int32),
        total_work=jnp.asarray(p.sum(axis=1), dtype=jnp.int32),
        ma0=pairs[0], ma1=pairs[1], js=pairs[2],
        ptm0_js=pairs[3], ptm1_js=pairs[4], lag_js=pairs[5],
    )


def parent_tables(t: BoundTables, prmu: jax.Array, depth: jax.Array):
    """front/remain of each parent's prefix, one `lax.scan` over positions.

    Equivalent of `schedule_front` + `sum_unscheduled`
    (reference: c_bound_simple.c:51-69, 108-124) for a whole batch: scan
    positions j = 0..J-1; a position participates only while j < depth(b).

    Returns front (B, M) and remain (B, M), both int32.
    """
    prmu = jnp.asarray(prmu)
    depth = jnp.asarray(depth)
    B, J = prmu.shape
    M = t.p.shape[0]

    def body(carry, j):
        front, sched_sum = carry
        job = prmu[:, j].astype(jnp.int32)          # (B,)
        pj = t.p_t[job]                              # (B, M)
        active = (j < depth)[:, None]                # (B, 1)

        # add_forward chain over machines (unrolled, M small)
        chain = front[:, 0] + pj[:, 0]
        cols = [chain]
        for k in range(1, M):
            chain = jnp.maximum(chain, front[:, k]) + pj[:, k]
            cols.append(chain)
        new_front = jnp.stack(cols, axis=1)

        front = jnp.where(active, new_front, front)
        sched_sum = sched_sum + jnp.where(active, pj, 0)
        return (front, sched_sum), None

    init = (jnp.zeros((B, M), jnp.int32), jnp.zeros((B, M), jnp.int32))
    (front, sched_sum), _ = jax.lax.scan(body, init, jnp.arange(J))
    remain = t.total_work[None, :] - sched_sum
    return front, remain


def _child_fronts(t: BoundTables, prmu, front):
    """front of every dense child: append job prmu[b, i] to parent b's prefix
    (one add_forward chain, c_bound_simple.c:31-38, on (B, J) lanes).

    The job-id -> processing-times lookup is a one-hot matmul on the MXU
    rather than a gather: per-element dynamic gathers serialize on TPU
    (~ms at 100k+ lanes) while a (B*J, J) x (J, M) matmul is microseconds.
    f32 accumulates integers exactly (p_times < 2^24).

    Returns (child_front [(B, J, M)], child_p [(B, J, M)] the per-machine
    processing times of the appended job)."""
    B, J = prmu.shape
    M = t.p.shape[0]
    onehot = (prmu[..., None].astype(jnp.int32)
              == jnp.arange(J, dtype=jnp.int32)).astype(jnp.float32)
    # HIGHEST precision: the default TPU matmul pass rounds f32 inputs
    # through bfloat16, which would corrupt processing times > 256
    child_p = jnp.dot(onehot.reshape(B * J, J),
                      t.p_t.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    child_p = child_p.astype(jnp.int32).reshape(B, J, M)   # (B, J, M)
    chain = front[:, None, 0] + child_p[..., 0]
    cols = [chain]
    M = t.p.shape[0]
    for k in range(1, M):
        chain = jnp.maximum(chain, front[:, None, k]) + child_p[..., k]
        cols.append(chain)
    return jnp.stack(cols, axis=-1), child_p


def child_mask(prmu: jax.Array, depth: jax.Array, valid: jax.Array):
    """(B, J) mask of real children: slot i exists iff depth <= i < J."""
    B, J = prmu.shape
    depth = jnp.asarray(depth)
    valid = jnp.asarray(valid)
    return (jnp.arange(J)[None, :] >= depth[:, None]) & valid[:, None]


def lb1_from_parts(t: BoundTables, child_front, child_remain, mask):
    """LB1 combine chain given each child's front/remain
    (machine_bound_from_parts, c_bound_simple.c:126-141, on (B, J) lanes).

    Returns (B, J) int32; masked slots hold I32_MAX (always pruned).
    """
    M = t.p.shape[0]
    back = t.min_tails
    tmp0 = child_front[..., 0] + child_remain[..., 0]
    lb = tmp0 + back[0]
    for k in range(1, M):
        tmp1 = jnp.maximum(tmp0, child_front[..., k] + child_remain[..., k])
        lb = jnp.maximum(lb, tmp1 + back[k])
        tmp0 = tmp1
    return jnp.where(mask, lb, I32_MAX)


def lb1_children(t: BoundTables, prmu, depth, valid):
    """LB1 bound of every child (reference semantics: lb1_bound of the child
    permutation, c_bound_simple.c:143-158, as launched per-child by
    evaluate_gpu_lb1, PFSP_gpu_lib.cu:43-65).

    Recomputes the parents' prefix tables; the engines instead carry
    front/remain in the pool and call `lb1_from_parts` directly.
    """
    front, remain = parent_tables(t, prmu, depth)
    child_front, child_p = _child_fronts(t, prmu, front)
    child_remain = remain[:, None, :] - child_p       # job leaves 'remain'
    return lb1_from_parts(t, child_front, child_remain,
                          child_mask(prmu, depth, valid))


def lb1d_from_parts(t: BoundTables, front, remain, child_p, mask):
    """LB1_d chain given the parents' front/remain and each child's
    per-machine processing times (`add_front_and_bound`,
    c_bound_simple.c:218-244, on (B, J) lanes).

    Returns (B, J) int32; masked slots hold I32_MAX.
    """
    back = t.min_tails
    M = t.p.shape[0]
    lb = (front[:, None, 0] + remain[:, None, 0] + back[0]) \
        * jnp.ones_like(child_p[..., 0])
    tmp0 = front[:, None, 0] + child_p[..., 0]
    for k in range(1, M):
        tmp1 = jnp.maximum(tmp0, front[:, None, k])
        lb = jnp.maximum(lb, tmp1 + remain[:, None, k] + back[k])
        tmp0 = tmp1 + child_p[..., k]
    return jnp.where(mask, lb, I32_MAX)


def lb1d_children(t: BoundTables, prmu, depth, valid):
    """LB1_d incremental bound of every child (as launched per-parent by
    evaluate_gpu_lb1_d, PFSP_gpu_lib.cu:73-102). Recomputes parent tables;
    engines use `lb1d_from_parts`."""
    front, remain = parent_tables(t, prmu, depth)
    _, child_p = _child_fronts(t, prmu, front)        # only needs p of the job
    return lb1d_from_parts(t, front, remain, child_p,
                           child_mask(prmu, depth, valid))


def lb2_from_parts(t: BoundTables, prmu, depth, child_front, mask):
    """LB2 Johnson bound of every child given each child's front
    (reference: lb2_bound, c_bound_johnson.c:239-254, per-child as
    evaluate_gpu_lb2, PFSP_gpu_lib.cu:105-127).

    The reference's data-dependent early exit over machine pairs
    (c_bound_johnson.c:231-233) is replaced by a full masked max over all
    pairs — the exit can only fire when the bound already exceeds the
    incumbent, in which case the child is pruned either way, so search
    behavior is identical (and the vector unit stays busy).

    Returns (B, J) int32; masked slots hold I32_MAX.
    """
    prmu = jnp.asarray(prmu)
    depth = jnp.asarray(depth)
    B, J = prmu.shape

    # inverse permutation: slot_of_job[b, job] = position of job in prmu[b]
    slot_of_job = jnp.zeros((B, J), jnp.int32).at[
        jnp.arange(B)[:, None], prmu.astype(jnp.int32)
    ].set(jnp.arange(J, dtype=jnp.int32)[None, :])

    # tmp0/tmp1 start at the child's front on each pair's two machines
    tmp0 = jnp.take(child_front, t.ma0, axis=-1)      # (B, J, P)
    tmp1 = jnp.take(child_front, t.ma1, axis=-1)

    depth_b = depth[:, None, None]                    # (B, 1, 1)

    def body(carry, j):
        tmp0, tmp1 = carry
        jsj = t.js[:, j]                              # (P,) job id per pair
        # child-unscheduled test: job's slot >= depth and it is not the
        # appended job (which sits at slot i of the dense child grid)
        slot = jnp.take(slot_of_job, jsj, axis=1)     # (B, P)
        is_appended = slot[:, None, :] == jnp.arange(J)[None, :, None]
        active = (slot[:, None, :] >= depth_b) & ~is_appended    # (B, J, P)

        pt0 = t.ptm0_js[:, j]                         # (P,)
        pt1 = t.ptm1_js[:, j]
        lag = t.lag_js[:, j]
        new0 = tmp0 + pt0
        new1 = jnp.maximum(tmp1, new0 + lag) + pt1
        tmp0 = jnp.where(active, new0, tmp0)
        tmp1 = jnp.where(active, new1, tmp1)
        return (tmp0, tmp1), None

    (tmp0, tmp1), _ = jax.lax.scan(body, (tmp0, tmp1), jnp.arange(J))

    back0 = jnp.take(t.min_tails, t.ma0)              # (P,)
    back1 = jnp.take(t.min_tails, t.ma1)
    per_pair = jnp.maximum(tmp1 + back1, tmp0 + back0)
    lb = per_pair.max(axis=-1)                        # (B, J)
    return jnp.where(mask, lb, I32_MAX)


def lb2_children(t: BoundTables, prmu, depth, valid):
    """LB2 bound of every child, recomputing parent tables; engines use
    `lb2_from_parts`."""
    front, _ = parent_tables(t, prmu, depth)
    child_front, _ = _child_fronts(t, prmu, front)    # (B, J, M)
    return lb2_from_parts(t, prmu, depth, child_front,
                          child_mask(prmu, depth, valid))


def children_bounds(lb_kind: int):
    """Dispatch like the reference's `decompose`/`evaluate_gpu`
    (PFSP_lib.h:30-48, PFSP_gpu_lib.cu:129-152): 0=LB1_d, 1=LB1, 2=LB2."""
    return {0: lb1d_children, 1: lb1_children, 2: lb2_children}[lb_kind]


def bounds_from_parts(lb_kind: int, t: BoundTables, prmu, depth, valid,
                      front, remain, child_front, child_p, mask):
    """Bound dispatch for engines that carry front/remain in the pool —
    no O(jobs) prefix rescan (the reference pays that rescan per bound,
    c_bound_simple.c:51-69; here each node's tables ride along with it)."""
    if lb_kind == 0:
        return lb1d_from_parts(t, front, remain, child_p, mask)
    if lb_kind == 1:
        child_remain = remain[:, None, :] - child_p
        return lb1_from_parts(t, child_front, child_remain, mask)
    if lb_kind == 2:
        return lb2_from_parts(t, prmu, depth, child_front, mask)
    raise ValueError(f"unknown lb_kind {lb_kind}")
