"""TPU-only parity tests for the Pallas expand kernel.

The CI suite runs on a virtual CPU mesh where `expand` dispatches to the
XLA fallback, so the kernel itself is only exercised on real hardware —
these tests run when a TPU backend is attached (the driver's bench
environment) and are skipped elsewhere.
"""

import jax
import numpy as np
import pytest

from tpu_tree_search.ops import batched, pallas_expand
from tpu_tree_search.ops import reference as ref
from tpu_tree_search.problems import taillard

pytestmark = pytest.mark.skipif(
    jax.default_backend() not in ("tpu",),
    reason="pallas kernel parity needs a TPU backend")


def _random_parents(p, B, seed=0):
    import jax.numpy as jnp
    J = p.shape[1]
    rng = np.random.default_rng(seed)
    prmu = np.stack([rng.permutation(J) for _ in range(B)]).astype(np.int16)
    depth = rng.integers(0, J, B).astype(np.int32)
    aux = ref.prefix_front_remain(p, prmu, depth)
    return (jnp.asarray(prmu.T.copy()), jnp.asarray(depth[None, :]),
            jnp.asarray(aux[:, :p.shape[0]].T.copy()))


@pytest.mark.parametrize("lb_kind", [0, 1])
def test_kernel_matches_xla_fallback(lb_kind):
    p = taillard.processing_times(21)
    tables = batched.make_tables(p)
    args = _random_parents(p, 2048)
    t = pallas_expand.expand_tpu(tables, *args, lb_kind=lb_kind, tile=1024)
    x = pallas_expand.expand_xla(tables, *args, lb_kind=lb_kind, tile=1024)
    for a, b, name in zip(t, x, ("children", "aux", "bounds")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_engine_on_tpu_matches_golden():
    """End-to-end on hardware: the kernel-driven engine reproduces the
    golden totals of ta014 LB1 UB=opt (tree=2573652, sol=2648,
    Cmax=1377 — the instance every other engine path is validated
    against). Driven in bounded segments: a single device dispatch that
    runs for minutes trips the remote-worker watchdog in this
    environment (its crash takes the chip down for every later test),
    and segmenting is also how real long runs are driven."""
    import functools

    from tpu_tree_search.engine import checkpoint, device
    from tpu_tree_search.ops import batched

    p = taillard.processing_times(14)
    opt = taillard.optimal_makespan(14)
    tables = batched.make_tables(p)
    state = device.init_state(20, 1 << 20, opt, p_times=p)
    run_fn = functools.partial(device.run, tables, lb_kind=1, chunk=1024)

    def run(state, target):
        return run_fn(state=state, max_iters=target)

    out = checkpoint.run_segmented(run, state, segment_iters=2000,
                                   heartbeat=lambda r: None)
    assert (int(out.tree), int(out.sol), int(out.best)) == \
           (2573652, 2648, 1377)


@pytest.mark.parametrize("lb_kind", [0, 1])
def test_bounds_kernel_matches_xla_fallback(lb_kind):
    """The bounds-only kernel (what device.step actually runs since the
    regather rewrite) must equal the bounds-only XLA fallback."""
    p = taillard.processing_times(21)
    tables = batched.make_tables(p)
    args = _random_parents(p, 2048, seed=7)
    t = pallas_expand.expand_bounds_tpu(tables, *args, lb_kind=lb_kind,
                                        tile=1024)
    x = pallas_expand.expand_bounds_xla(tables, *args, lb_kind=lb_kind,
                                        tile=1024)
    np.testing.assert_array_equal(np.asarray(t), np.asarray(x))


def test_two_phase_lb2_engine_matches_golden():
    """End-to-end on hardware through the two-phase LB2 step (LB1
    pre-prune -> regather -> strong-pair prefilter -> tiered pair sweep
    -> final compaction): ta003 with UB=opt must reproduce the golden
    totals exactly (tests/golden/pfsp_lb2_ub1.jsonl: tree=80062)."""
    from tpu_tree_search.engine import device

    p = taillard.processing_times(3)
    opt = taillard.optimal_makespan(3)
    out = device.search(p, lb_kind=2, init_ub=opt, chunk=1024,
                        capacity=1 << 18)
    assert (out.explored_tree, out.explored_sol, out.best) == \
           (80062, 0, opt)


def test_two_phase_lb2_engine_matches_golden_large():
    """Same, on the largest small-class golden (ta008: a 13.9M-node LB2
    tree) at a production chunk — hundreds of steps through every sweep
    and compaction tier. Segmented like real long runs (one unbounded
    dispatch would trip the remote-worker watchdog)."""
    import functools

    from tpu_tree_search.engine import checkpoint, device
    from tpu_tree_search.ops import batched

    p = taillard.processing_times(8)
    opt = taillard.optimal_makespan(8)
    tables = batched.make_tables(p)
    state = device.init_state(20, 1 << 22, opt, p_times=p)
    run_fn = functools.partial(device.run, tables, lb_kind=2, chunk=8192)

    def run(state, target):
        return run_fn(state=state, max_iters=target)

    out = checkpoint.run_segmented(run, state, segment_iters=2000,
                                   heartbeat=lambda r: None)
    assert (int(out.tree), int(out.sol), int(out.best)) == \
           (13940189, 0, opt)


def test_prefilter_branch_matches_oracle():
    """The strong-pair prefilter only compiles in when
    P > 2*PAIR_PREFILTER pairs (=48: >= 11 machines) — which no
    small-class golden reaches (20x5 has P=10). This synthetic
    8-job x 15-machine instance (P=105) forces the prefilter path
    end-to-end on hardware and checks the full search against the
    sequential oracle."""
    from tpu_tree_search.engine import device, sequential as seq
    from tpu_tree_search.problems.pfsp import PFSPInstance

    rng = np.random.default_rng(42)
    p = rng.integers(1, 100, (15, 8)).astype(np.int32)
    inst = PFSPInstance(inst_id=0, jobs=8, machines=15, p_times=p)
    opt = seq.pfsp_search(inst, lb=2).best
    # UB=opt makes the explored set traversal-order-invariant, so the
    # oracle's totals must match exactly
    want = seq.pfsp_search(inst, lb=2, init_ub=opt)
    out = device.search(p, lb_kind=2, init_ub=opt, chunk=1024,
                        capacity=1 << 18)
    assert (out.explored_tree, out.explored_sol, out.best) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_lb2_kernel_matches_xla_fallback():
    """The TPU LB2 path (expand kernel for children/aux + the pair-sweep
    kernel for bounds) must equal the XLA fallback bit-for-bit."""
    import jax.numpy as jnp

    p = taillard.processing_times(21)
    tables = batched.make_tables(p)
    args = _random_parents(p, 2048, seed=11)
    eff = pallas_expand.effective_tile(20, 2048, 1024, 2)
    t = pallas_expand.expand(tables, *args, lb_kind=2, tile=eff)
    x = pallas_expand.expand_xla(tables, *args, lb_kind=2, tile=eff)
    for a, b, name in zip(t, x, ("children", "aux", "bounds")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_wide_class_two_phase_matches_oracle():
    """The 100-job route (pallas LB1 prefilter at the J>=64 tile floor of
    128 + XLA scan pair sweeps over survivor tiers — no pallas pair
    kernel, lb2_kernel_fits gates it off past J=64): the J=100/TB=128
    bounds kernel must match the XLA oracle bit-for-bit, and the
    two-phase engine route must run on a 100x20 instance (the round-3
    regression this guards was a hard compile OOM on this class)."""
    from tpu_tree_search.engine import device
    from tpu_tree_search.ops import batched as b

    rng = np.random.default_rng(7)
    p = rng.integers(1, 100, (20, 100)).astype(np.int32)
    tables = b.make_tables(p)

    tile = pallas_expand.effective_tile(100, 512, 1024, 1, machines=20)
    assert tile == 128  # the wide-class floor this test exists to pin
    args = _random_parents(p, 512, seed=3)
    bounds_t = pallas_expand.expand_bounds(tables, *args, lb_kind=1,
                                           tile=tile)
    bounds_x = pallas_expand.expand_bounds_xla(tables, *args, lb_kind=1,
                                               tile=tile)
    np.testing.assert_array_equal(np.asarray(bounds_t),
                                  np.asarray(bounds_x))

    # drive the full two-phase LB2 step on the class (compile + run;
    # a tight synthetic ub keeps the bounded window cheap, and hitting
    # the static pool ceiling early is fine — overflow is a clean,
    # recoverable exit, not a failure of the route)
    state = device.init_state(100, 1 << 19, 7000, p_times=p)
    out = device.run(tables, state, 2, 512, max_iters=40)
    assert int(out.iters) > 0
    assert int(out.tree) > 0


def test_j500_engine_matches_native():
    """The 500-job envelope: a full bounded-subtree
    solve at J=500 on chip — int32 pool aux (the aux_dtype fallback),
    16 bitmask words, the XLA LB2 route (every pallas tile cap is out
    of range at J=500) — against the native sequential oracle on the
    same seeds at the same fixed ub. Near-leaf seeds bound the subtree
    by construction (a root search at J=500 has no usable middle
    ground: ub = root-lb is empty, any useful bump explodes), and the
    fixed ub makes the explored set traversal-order invariant, so the
    counts must match exactly."""
    import jax.numpy as jnp

    from tpu_tree_search import native
    from tpu_tree_search.engine import device

    J, M, B = 500, 20, 32
    rng = np.random.default_rng(11)
    p = rng.integers(1, 100, (M, J)).astype(np.int32)
    assert device.aux_dtype(p) == np.dtype(np.int32)
    # r5: the dense-XLA route is gone — every class without the pallas
    # expand kernel now runs the prefilter STRUCTURE (LB1 pre-prune +
    # tiered sweeps) with XLA fallbacks per stage, and the sweeps ride
    # the streaming big-J pair kernel (lb2_bounds_bigj_tpu)
    route, _, pair_ok = device.lb2_route(J, M, 190, 64)
    assert route == "prefilter" and not pair_ok

    seeds = np.stack([rng.permutation(J) for _ in range(B)]) \
        .astype(np.int16)
    # staggered near-leaf depths: subtree sizes at J=500 are violently
    # depth-sensitive (one unlucky seed at depth 470 explodes past 10^8
    # while depth 480 averages ~30 nodes — measured), so many shallow
    # staggered seeds buy tree size safely
    depth = np.array([478 + (i % 8) for i in range(B)], np.int16)
    _, _, best0, _ = native.search_from(p, seeds, depth, lb_kind=2,
                                        init_ub=2**31 - 1)
    # Near-leaf bounds at J=500 are exactly tight (every seed's lb ==
    # its subtree optimum — measured: ub=best0 explores 0 nodes), so
    # NO ub both opens a nontrivial tree and keeps the incumbent
    # constant; exact count parity is structurally unavailable here and
    # the test follows the repo's ub=inf convention instead (the
    # discovered optimum must match; counts are traversal-order
    # sensitive — tests/test_engine_single.py): the engines must agree
    # on the proven subtree optimum through completely different
    # traversals of a >10^3-node J=500 tree. Bit-exact J=500 BOUND
    # parity is covered by tests/test_bounds.py::
    # test_lb2_j500_matches_scalar.
    ub = int(best0) + 200
    tree, sol, best, _ = native.search_from(p, seeds, depth, lb_kind=2,
                                            init_ub=ub)
    assert tree >= 500, tree
    assert best == best0

    tables = batched.make_tables(p)
    state = device.init_state(J, 1 << 17, ub, prmu0=seeds, depth0=depth,
                              p_times=p)
    out = device.run(tables, state, 2, 64)
    assert not bool(out.overflow) and int(jnp.asarray(out.size)) == 0
    assert int(out.best) == best0
    assert int(out.tree) >= 500 and int(out.sol) > 0


def test_lb2_bigj_kernel_matches_scan_on_hardware():
    """The COMPILED streaming big-J pair-sweep kernel
    (lb2_bounds_bigj_tpu: chain state in VMEM scratch across sequential
    j grid steps, streamed one-hot blocks) against the XLA bitmask scan,
    bit-exact, at the 200x20 campaign class and the 100x10 class. The
    interpret-mode parity lives in tests/test_bounds.py; this is the
    mosaic-legalization + memory-layout tripwire."""
    import jax.numpy as jnp

    for jobs, machines, seed in ((200, 20, 3), (100, 10, 5)):
        rng = np.random.default_rng(seed)
        p = rng.integers(1, 100, size=(machines, jobs)).astype(np.int32)
        tables = batched.make_tables(p)
        N = 4096
        cf = jnp.asarray(rng.integers(0, 3000, size=(machines, N)),
                         jnp.int32)
        unsched = rng.random((jobs, N)) < 0.5
        W = pallas_expand.sched_words(jobs)
        words = np.zeros((W, N), np.uint32)
        for v in range(jobs):
            words[v // 32] |= np.where(unsched[v], np.uint32(0),
                                       np.uint32(1 << (v % 32)))
        sched = jnp.asarray(words.view(np.int32))
        want = np.asarray(pallas_expand.lb2_cols(tables, sched, cf))
        nt = pallas_expand.lb2_bigj_tile(jobs, machines, N)
        assert nt > 0
        got = np.asarray(pallas_expand.lb2_bounds_bigj_tpu(
            tables, cf, jnp.asarray(unsched.astype(np.float32)),
            tile=nt))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{jobs}x{machines}")


def test_j200_two_phase_engine_runs_on_hardware():
    """The 200x20 campaign class end-to-end on chip: XLA LB1 expand (its
    TB=64 tile is refused by Mosaic, so the kernel is not admitted),
    LB1 pre-prune, streaming big-J pair sweeps over survivor tiers. A
    bounded window of the full engine must push nodes."""
    from tpu_tree_search.engine import device

    rng = np.random.default_rng(17)
    p = rng.integers(1, 100, (20, 200)).astype(np.int32)
    tables = batched.make_tables(p)

    tile = pallas_expand.effective_tile(200, 1024, 1024, 1, machines=20)
    assert tile == 64
    assert not pallas_expand.kernel_ok(200, tile, 1, machines=20)

    state = device.init_state(200, 1 << 19, 13000, p_times=p)
    out = device.run(tables, state, 2, 1024, max_iters=20)
    assert int(out.iters) > 0
    assert int(out.tree) > 0


def test_j200_seeded_matches_native():
    """J=200 bounded-subtree parity on chip — the big-J analogue of
    test_j500_engine_matches_native: XLA LB1 expand (TB=64 is not
    admitted), LB1 pre-prune, and the streaming big-J pair-sweep kernel
    over survivor tiers. Near-leaf
    bounds are exactly tight here too (ub=best0 explores 0 nodes —
    measured on the native oracle), so the invariant follows the repo's
    ub=inf convention: both engines must prove the same subtree optimum
    through completely different traversals."""
    import jax.numpy as jnp

    from tpu_tree_search import native
    from tpu_tree_search.engine import device

    J, M, B = 200, 20, 32
    rng = np.random.default_rng(19)
    p = rng.integers(1, 100, (M, J)).astype(np.int32)
    seeds = np.stack([rng.permutation(J) for _ in range(B)]) \
        .astype(np.int16)
    depth = np.array([186 + (i % 6) for i in range(B)], np.int16)
    _, _, best0, _ = native.search_from(p, seeds, depth, lb_kind=2,
                                        init_ub=2**31 - 1)
    ub = int(best0) + 150
    tree, sol, best, _ = native.search_from(p, seeds, depth, lb_kind=2,
                                            init_ub=ub)
    assert tree >= 200, tree
    assert best == best0

    tables = batched.make_tables(p)
    state = device.init_state(J, 1 << 17, ub, prmu0=seeds, depth0=depth,
                              p_times=p)
    out = device.run(tables, state, 2, 64)
    assert not bool(out.overflow) and int(jnp.asarray(out.size)) == 0
    assert int(out.best) == best0
    assert int(out.tree) >= 200 and int(out.sol) > 0
