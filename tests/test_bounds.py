"""Batched JAX bound kernels vs the scalar numpy oracle.

Property checked on randomized partial permutations: for every real child
slot, the batched (B, J) kernels reproduce the scalar reference bound
exactly (these are integer algorithms — equality, not closeness).
"""

import numpy as np
import pytest

from tpu_tree_search.ops import batched, reference as ref
from tpu_tree_search.problems import taillard
from tpu_tree_search.problems.pfsp import PFSPInstance


def random_parents(jobs: int, batch: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """Random nodes: a random permutation with a random prefix depth."""
    prmu = np.stack([rng.permutation(jobs) for _ in range(batch)]).astype(np.int16)
    depth = rng.integers(0, jobs, size=batch).astype(np.int32)
    return prmu, depth


def scalar_child_bounds(lb1, lb2, prmu, depth, lb_kind, jobs):
    """Dense (J,) child bounds of one parent via the scalar oracle."""
    out = np.full(jobs, 2**31 - 1, dtype=np.int64)
    limit1 = depth - 1
    if lb_kind == 0:
        lb_begin = ref.lb1_children_bounds(lb1, prmu, limit1, jobs)
        for i in range(depth, jobs):
            out[i] = lb_begin[int(prmu[i])]
        return out
    for i in range(depth, jobs):
        child = prmu.copy()
        child[depth], child[i] = child[i], child[depth]
        if lb_kind == 1:
            out[i] = ref.lb1_bound(lb1, child, limit1 + 1, jobs)
        else:
            # best=I32_MAX disables the early exit -> full max over pairs,
            # which is what the batched kernel computes
            out[i] = ref.lb2_bound(lb1, lb2, child, limit1 + 1, jobs, 2**31 - 1)
    return out


@pytest.mark.parametrize("jobs,machines,seed", [(8, 4, 0), (12, 6, 1), (20, 5, 2)])
@pytest.mark.parametrize("lb_kind", [0, 1, 2])
def test_batched_matches_scalar_synthetic(jobs, machines, seed, lb_kind):
    rng = np.random.default_rng(seed)
    inst = PFSPInstance.synthetic(jobs=jobs, machines=machines, seed=seed)
    lb1 = ref.make_lb1_data(inst.p_times)
    lb2 = ref.make_lb2_data(lb1)
    tables = batched.make_tables(inst.p_times)

    B = 16
    prmu, depth = random_parents(jobs, B, rng)
    valid = np.ones(B, dtype=bool)
    got = np.asarray(
        batched.children_bounds(lb_kind)(tables, prmu, depth, valid)
    )
    for b in range(B):
        want = scalar_child_bounds(lb1, lb2, prmu[b], int(depth[b]), lb_kind, jobs)
        np.testing.assert_array_equal(got[b], want, err_msg=f"parent {b}")


@pytest.mark.parametrize("lb_kind", [0, 1, 2])
def test_batched_matches_scalar_ta014(lb_kind):
    """Real instance shape (20x10)."""
    rng = np.random.default_rng(14)
    inst = PFSPInstance.from_taillard(14)
    lb1 = ref.make_lb1_data(inst.p_times)
    lb2 = ref.make_lb2_data(lb1)
    tables = batched.make_tables(inst.p_times)

    B = 8
    prmu, depth = random_parents(inst.jobs, B, rng)
    valid = np.ones(B, dtype=bool)
    got = np.asarray(
        batched.children_bounds(lb_kind)(tables, prmu, depth, valid)
    )
    for b in range(B):
        want = scalar_child_bounds(lb1, lb2, prmu[b], int(depth[b]), lb_kind,
                                   inst.jobs)
        np.testing.assert_array_equal(got[b], want, err_msg=f"parent {b}")


def test_invalid_parents_masked():
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=5)
    tables = batched.make_tables(inst.p_times)
    rng = np.random.default_rng(5)
    prmu, depth = random_parents(8, 4, rng)
    valid = np.array([True, False, True, False])
    got = np.asarray(batched.lb1_children(tables, prmu, depth, valid))
    assert (got[1] == 2**31 - 1).all()
    assert (got[3] == 2**31 - 1).all()


def test_leaf_child_bound_is_makespan():
    """At depth J-1 the single child is a complete schedule; its LB1 bound
    must equal the true makespan (reference: eval_solution semantics)."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=7)
    tables = batched.make_tables(inst.p_times)
    rng = np.random.default_rng(7)
    prmu = np.stack([rng.permutation(8) for _ in range(4)]).astype(np.int16)
    depth = np.full(4, 7, dtype=np.int32)
    valid = np.ones(4, dtype=bool)
    got = np.asarray(batched.lb1_children(tables, prmu, depth, valid))
    for b in range(4):
        assert got[b, 7] == inst.makespan(prmu[b])


@pytest.mark.parametrize("jobs,machines", [(40, 8), (50, 10), (50, 20)])
def test_lb2_multiword_bitmask_matches_scalar(jobs, machines):
    """Wide instances (jobs > 31) take the multi-word scheduled-set
    bitmask through the column-major LB2 path (sched_mask_cols +
    lb2_cols) — the generalization of the single-int32 fast path that
    previously dropped 50-job instances to the slow row-major scan."""
    import jax.numpy as jnp

    from tpu_tree_search.ops import pallas_expand

    rng = np.random.default_rng(jobs + machines)
    inst = PFSPInstance.synthetic(jobs=jobs, machines=machines, seed=jobs)
    lb1 = ref.make_lb1_data(inst.p_times)
    lb2 = ref.make_lb2_data(lb1)
    tables = batched.make_tables(inst.p_times)
    assert pallas_expand.sched_words(jobs) == 2

    B = 8
    prmu, depth = random_parents(jobs, B, rng)
    front, _ = batched.parent_tables(tables, prmu, depth)
    got = np.asarray(pallas_expand.expand_bounds_xla(
        tables, jnp.asarray(prmu.T),
        jnp.asarray(depth, dtype=jnp.int32)[None, :],
        jnp.asarray(front).T, lb_kind=2))
    got = got.reshape(jobs, B).T          # column c = i*B + b -> (B, J)
    for b in range(B):
        want = scalar_child_bounds(lb1, lb2, prmu[b], int(depth[b]), 2, jobs)
        d = int(depth[b])
        np.testing.assert_array_equal(got[b, d:], want[d:],
                                      err_msg=f"parent {b}")


def test_lb2_j500_matches_scalar():
    """The 500-job envelope: the XLA LB2 path at J=500
    (sched_words=16 bitmask words, int32 pool aux — aux_dtype's
    overflow fallback) against the scalar oracle. Parents sit near the
    leaves so the scalar side stays cheap (J - depth children each),
    while the batched side still evaluates the full dense (J, B)
    grid."""
    import jax.numpy as jnp

    from tpu_tree_search.engine import device
    from tpu_tree_search.ops import pallas_expand

    jobs, machines = 500, 20
    rng = np.random.default_rng(500)
    inst = PFSPInstance.synthetic(jobs=jobs, machines=machines, seed=500)
    assert device.aux_dtype(inst.p_times) == np.dtype(np.int32)
    assert pallas_expand.sched_words(jobs) == 16
    lb1 = ref.make_lb1_data(inst.p_times)
    lb2 = ref.make_lb2_data(lb1)
    tables = batched.make_tables(inst.p_times)

    B = 2
    prmu = np.stack([rng.permutation(jobs)
                     for _ in range(B)]).astype(np.int16)
    depth = np.array([jobs - 3, jobs - 8], dtype=np.int32)
    front, _ = batched.parent_tables(tables, prmu, depth)
    got = np.asarray(pallas_expand.expand_bounds_xla(
        tables, jnp.asarray(prmu.T),
        jnp.asarray(depth, dtype=jnp.int32)[None, :],
        jnp.asarray(front).T, lb_kind=2))
    got = got.reshape(jobs, B).T
    for b in range(B):
        want = scalar_child_bounds(lb1, lb2, prmu[b], int(depth[b]), 2,
                                   jobs)
        d = int(depth[b])
        np.testing.assert_array_equal(got[b, d:], want[d:],
                                      err_msg=f"parent {b}")


@pytest.mark.parametrize("jobs,machines", [(20, 5), (50, 10)])
def test_regather_multiword_sched_mask(jobs, machines):
    """The two-phase engine's survivor regather rebuilds each child's
    scheduled-set bitmask from its parent (device._regather
    with_sched=True). Verify every word against a directly-built mask on
    deep prefixes (many bits in the second word for jobs > 32) — the
    TPU-only two-phase path consumes this, so a word-accumulation bug
    here would not show up in the CPU engine tests."""
    import jax.numpy as jnp

    from tpu_tree_search.engine import device

    rng = np.random.default_rng(jobs)
    inst = PFSPInstance.synthetic(jobs=jobs, machines=machines, seed=1)
    tables = batched.make_tables(inst.p_times)
    B = 16
    prmu, depth = random_parents(jobs, B, rng)
    # deep prefixes so high-word bits accumulate
    depth = np.clip(depth + jobs // 2, 0, jobs - 1).astype(np.int32)
    front, _ = batched.parent_tables(tables, prmu, depth)

    TB = B
    N = B * jobs
    # child columns c = slot*TB + parent (single tile): pick every real
    # child slot of every parent
    idx = []
    for b in range(B):
        for i in range(int(depth[b]), jobs):
            idx.append(i * TB + b)
    idx = jnp.asarray(np.asarray(idx, np.int32))
    child, caux, sched = device._regather(
        tables, jnp.asarray(prmu.T), jnp.asarray(depth, jnp.int32)[None, :],
        jnp.asarray(front).T, idx, TB, with_sched=True)
    sched = np.asarray(sched)

    W = (jobs + 31) // 32
    assert sched.shape[0] == W
    k = 0
    for b in range(B):
        d = int(depth[b])
        for i in range(d, jobs):
            want = np.zeros(W, np.uint32)
            for v in list(prmu[b, :d]) + [prmu[b, i]]:
                want[int(v) // 32] |= np.uint32(1 << (int(v) % 32))
            np.testing.assert_array_equal(
                sched[:, k].view(np.uint32), want,
                err_msg=f"parent {b} slot {i}")
            k += 1


def test_taillard_oracle_table_spotchecks():
    assert taillard.optimal_makespan(14) == 1377
    assert taillard.optimal_makespan(21) == 2297
    assert taillard.optimal_makespan(31) == 2724
    assert taillard.optimal_makespan(56) == 3679
    assert taillard.nb_jobs(14) == 20 and taillard.nb_machines(14) == 10
    assert taillard.nb_jobs(56) == 50 and taillard.nb_machines(56) == 20


@pytest.mark.parametrize("jobs,machines", [(80, 5), (100, 10), (200, 20)])
def test_lb2_bigj_kernel_interpret_matches_scan(jobs, machines):
    """The streaming big-J pair-sweep kernel (pallas interpreter on CPU)
    against the XLA bitmask scan on random fronts/masks: bit-exact.
    These are the J > 64 classes lb2_kernel_fits gates off the register
    kernel (mosaic scoped-VMEM walls); hardware parity for the compiled
    kernel is pinned by tests/test_pallas_tpu.py."""
    from tpu_tree_search.ops import pallas_expand

    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    p = rng.integers(1, 100, size=(machines, jobs)).astype(np.int32)
    tables = batched.make_tables(p)
    N = 1024
    cf = jnp.asarray(rng.integers(0, 3000, size=(machines, N)), jnp.int32)
    unsched = rng.random((jobs, N)) < 0.5
    W = pallas_expand.sched_words(jobs)
    words = np.zeros((W, N), np.uint32)
    for v in range(jobs):
        words[v // 32] |= np.where(unsched[v], np.uint32(0),
                                   np.uint32(1 << (v % 32)))
    sched = jnp.asarray(words.view(np.int32))
    want = np.asarray(pallas_expand.lb2_cols(tables, sched, cf))
    nt = pallas_expand.lb2_bigj_tile(jobs, machines, N)
    assert nt > 0, "no streaming tile at test width"
    got = np.asarray(pallas_expand.lb2_bounds_bigj_tpu(
        tables, cf, jnp.asarray(unsched.astype(np.float32)), tile=nt,
        interpret=True))
    np.testing.assert_array_equal(got, want)
