"""Single-device engine vs the sequential oracle.

With `ub=opt` the incumbent never improves, so the B&B tree is independent
of exploration order and the device engine's (tree, sol, best) must equal
the oracle's exactly (SURVEY.md §4's cross-version invariant). With
`ub=inf` only the discovered optimum must match (order affects counts).
"""

import numpy as np
import pytest

from tpu_tree_search.engine import device, sequential as seq
from tpu_tree_search.problems.pfsp import PFSPInstance


@pytest.mark.parametrize("jobs,machines,seed", [(7, 4, 0), (8, 5, 1), (9, 3, 2)])
@pytest.mark.parametrize("lb_kind", [0, 1, 2])
def test_engine_matches_oracle_ub_opt(jobs, machines, seed, lb_kind):
    inst = PFSPInstance.synthetic(jobs=jobs, machines=machines, seed=seed)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=lb_kind, init_ub=opt)
    got = device.search(inst.p_times, lb_kind=lb_kind, init_ub=opt,
                        chunk=8, capacity=1 << 12)
    assert (got.explored_tree, got.explored_sol, got.best) == \
           (want.explored_tree, want.explored_sol, want.best)


@pytest.mark.parametrize("lb_kind", [0, 1, 2])
def test_engine_finds_optimum_ub_inf(lb_kind):
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=3)
    opt = inst.brute_force_optimum()
    got = device.search(inst.p_times, lb_kind=lb_kind, init_ub=None,
                        chunk=8, capacity=1 << 12)
    assert got.best == opt


@pytest.mark.parametrize("chunk", [1, 4, 32])
def test_chunk_size_invariance(chunk):
    """Tree counts with ub=opt must not depend on the pop-chunk size."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=4)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    got = device.search(inst.p_times, lb_kind=1, init_ub=opt,
                        chunk=chunk, capacity=1 << 12)
    assert (got.explored_tree, got.explored_sol) == \
           (want.explored_tree, want.explored_sol)


def test_overflow_recovery():
    """A deliberately tiny pool must trigger the grow-and-retry path."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=5)
    opt = inst.brute_force_optimum()
    got = device.search(inst.p_times, lb_kind=1, init_ub=opt,
                        chunk=8, capacity=16)
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    assert got.explored_tree == want.explored_tree


def test_max_iters_truncation():
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=6)
    got = device.search(inst.p_times, lb_kind=1, init_ub=None,
                        chunk=4, capacity=1 << 12, max_iters=3)
    assert got.iters == 3


@pytest.mark.parametrize("chunk,tile", [
    (64, 64),       # tile == chunk: one tile per step
    (128, 64),      # multi-tile grid
    (96, 64),       # tile-remainder chunk: one batch-wide tile
    (64, 1024),     # requested tile above the chunk: the shrink path
    (512, 256),
    (512, 128),
])
@pytest.mark.parametrize("lb_kind", [1, 2])
def test_tile_partition_invariance(lb_kind, chunk, tile):
    """The expand tile size changes only the internal child-column order;
    with a fixed UB the explored set — and so tree/sol/best — must equal
    the sequential oracle's for every tile rule (guards the step/expand
    column-order contract when default_tile shrinks tiles for big
    instances, and the LB2 routes' regather under each geometry)."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=5)
    ub = inst.brute_force_optimum()   # fixed-point UB => order-independent
    want = seq.pfsp_search(inst, lb=lb_kind, init_ub=ub)
    out = device.search(inst.p_times, lb_kind=lb_kind, init_ub=ub,
                        chunk=chunk, capacity=1 << 12, tile=tile)
    assert (out.explored_tree, out.explored_sol, out.best) == \
           (want.explored_tree, want.explored_sol, want.best)


@pytest.mark.parametrize("inst,chunk", [(31, 256), (111, 64)])
def test_wide_instance_classes_run(inst, chunk):
    """Every Taillard shape class compiles and searches: 50-job (adaptive
    tile shrink) and 500-job (beyond the kernel's bitmask/lane budget,
    XLA fallback) — the reference needs a macro.h edit + rebuild for
    these (pfsp/README.md:52)."""
    from tpu_tree_search.problems import taillard

    p = taillard.processing_times(inst)
    opt = taillard.optimal_makespan(inst)
    out = device.search(p, lb_kind=1, init_ub=opt, chunk=chunk,
                        capacity=1 << 16, max_iters=4)
    assert out.explored_tree > 0
    assert out.best == opt
