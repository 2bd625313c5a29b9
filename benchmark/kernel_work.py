"""Operations and bytes of one launch of each Pallas kernel, from the
shapes the trace records for it.

The per-child operation counts are the reference's model (its
`flop_lb1` / `flop_lb2`, pfsp/lib/PFSP_gpu_lib.cu of
ivantag13/dist-GPU-accelerated-tree-search), as `utils/roofline.py`
restates them; they are copied here so the yardstick does not move with
the program. One add or one max of the dynamic-programming chain counts
as one operation. Bytes are what the launch reads and writes: every
operand and the result, at their recorded shapes.

Both kernels below do at least the work counted: the LB2 sweep kernel
spends 7 vector operations per (pair, job, child) where the model
counts 5 (its mask products and the step-select run as multiplies and
maxes), and its one-hot matmuls run on the MXU and are not counted at
all. So a share of the roofline computed from these counts is a lower
bound of the kernel's true share, never above it.
"""

from __future__ import annotations

import math

BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}


def pairs_of(machines: int) -> int:
    return machines * (machines - 1) // 2


def lb1_ops_per_child(machines: int) -> float:
    """Child-front chain (2M) + remain update (M) + combine (3M)."""
    return 6.0 * machines


def lb2_sweep_ops_per_child(jobs: int, pairs: int) -> float:
    """All-pairs Johnson sweep: 5 per (pair, job) plus 2 per pair."""
    return 5.0 * jobs * pairs + 2.0 * pairs


def nbytes(shapes) -> float:
    return float(sum(BYTES[dt] * math.prod(dims) for dt, dims in shapes))


def expand_bounds(shapes) -> tuple[float, float]:
    """`expand_bounds_tpu`: result (1, J*TB) child bounds; operands p
    (M, J), tails (1, M), prmu (J, TB), depth (1, TB), front (M, TB)."""
    (_, out), (_, p), *_ = shapes
    machines = p[0]
    children = math.prod(out)
    return children * lb1_ops_per_child(machines), nbytes(shapes)


def lb2_sweep(shapes) -> tuple[float, float]:
    """`lb2_bounds_tpu`: result (1, N) bounds; operands sel0 (P, M),
    sel1, js1h (J, P, J), pt0/pt1/lag (P, J), tails0/tails1 (P, 1),
    child fronts (M, N), unscheduled (J, N)."""
    (_, out), (_, sel0), _, (_, js1h), *_ = shapes
    pairs, jobs = sel0[0], js1h[0]
    children = math.prod(out)
    return (children * lb2_sweep_ops_per_child(jobs, pairs),
            nbytes(shapes))


def probe(shapes, iters: int, ops_per_iter: int) -> float:
    """Operations of one probe launch (benchmark/probe.py)."""
    (_, out), *_ = shapes
    return float(math.prod(out) * iters * ops_per_iter)


WORK = {"expand_bounds_tpu": expand_bounds, "lb2_bounds_tpu": lb2_sweep}


def roofline_share(launches, kernel: str, peaks: dict):
    """Share (%) of the roofline over all launches of `kernel`: the
    least time the chip could take for their operations and bytes,
    over the time they took, launch by launch. Returns (share, bound) with bound
    "compute" or "memory", or None when the kernel did not run."""
    runs = launches.get(kernel)
    if not runs:
        return None
    work = WORK[kernel]
    least = t_ops = t_mem = took = 0.0
    for dur, shapes in runs:
        ops, b = work(shapes)
        o, m = ops / peaks["vector_ops_per_s"], b / peaks["hbm_bytes_per_s"]
        least += max(o, m)
        t_ops, t_mem, took = t_ops + o, t_mem + m, took + dur
    if took <= 0:
        return None
    return 100.0 * least / took, ("compute" if t_ops >= t_mem else "memory")
