"""The balance round's sliced exchange, its bounds, its donor threshold
and its defaults, on a 4-device CPU mesh.

`_gather_sort_round` below is the exchange the sliced one replaced: it
gathered each donor's rows with `jnp.take`, sent D fixed blocks, then
compacted the received rows with an `argsort` and wrote the whole
receive block. It stays here as the oracle: both must commit the same
pools, row for row, in every round.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpu_tree_search.engine import distributed, megabatch
from tpu_tree_search.engine.device import SearchState
from tpu_tree_search.parallel import balance as bal
from tpu_tree_search.parallel.mesh import WORKER_AXIS, shard_map, worker_mesh
from tpu_tree_search.problems import taillard

AX = WORKER_AXIS
D = 4


def _gather_sort_round(s, transfer_cap, min_transfer, limit):
    J, capacity = s.prmu.shape
    A = s.aux.shape[0]
    sizes = jax.lax.all_gather(s.size, AX)
    plan = bal.exchange_plan(sizes, transfer_cap, min_transfer)
    me = jax.lax.axis_index(AX)
    my_out = plan[me]
    total_out = my_out.sum(dtype=jnp.int32)
    total_in = plan[:, me].sum(dtype=jnp.int32)
    base = s.size - total_out
    n_recv = D * transfer_cap
    ovf = jax.lax.psum((base + total_in > limit).astype(jnp.int32), AX) > 0
    do_flow = (plan.sum() > 0) & ~ovf

    def do_exchange(_):
        offs = jnp.cumsum(my_out, dtype=jnp.int32) - my_out
        k = jnp.arange(transfer_cap, dtype=jnp.int32)
        rows = base + offs[:, None] + k[None, :]
        send_mask = k[None, :] < my_out[:, None]
        rows_c = jnp.clip(rows, 0, capacity - 1).reshape(-1)
        buf_prmu = jnp.take(s.prmu, rows_c, axis=1)
        buf_aux = jnp.take(s.aux, rows_c, axis=1)
        buf_depth = jnp.where(send_mask.reshape(-1),
                              s.depth[rows_c], -1)[None, :]

        def exchange(x):
            blocks = x.reshape(x.shape[0], D, transfer_cap)
            return jax.lax.all_to_all(blocks, AX, 1, 1).reshape(
                x.shape[0], n_recv)

        rbuf_prmu, rbuf_aux = exchange(buf_prmu), exchange(buf_aux)
        flat_depth = exchange(buf_depth).reshape(-1)
        push = flat_depth >= 0
        order = jnp.argsort(~push, stable=True)
        return (jnp.take(rbuf_prmu, order, axis=1),
                jnp.take(rbuf_aux, order, axis=1),
                jnp.take(flat_depth, order).astype(jnp.int16),
                push.sum(dtype=jnp.int32))

    def no_exchange(_):
        return (jnp.zeros((J, n_recv), s.prmu.dtype),
                jnp.zeros((A, n_recv), s.aux.dtype),
                jnp.full((n_recv,), -1, s.depth.dtype), jnp.int32(0))

    recv_prmu, recv_aux, recv_depth, n_push = jax.lax.cond(
        do_flow, do_exchange, no_exchange, 0)
    zero = jnp.zeros((), base.dtype)
    write_at = jnp.where(do_flow, base, jnp.asarray(limit, base.dtype))
    keep = lambda new, old: jnp.where(do_flow, new, old)  # noqa: E731
    return s._replace(
        prmu=jax.lax.dynamic_update_slice(s.prmu, recv_prmu,
                                          (zero, write_at)),
        depth=jax.lax.dynamic_update_slice(s.depth, recv_depth,
                                           (write_at,)),
        aux=jax.lax.dynamic_update_slice(s.aux, recv_aux, (zero, write_at)),
        size=keep(base + n_push, s.size),
        sent=keep(s.sent + total_out.astype(jnp.int64), s.sent),
        recv=keep(s.recv + n_push.astype(jnp.int64), s.recv),
        steals=keep(s.steals + (n_push > 0).astype(jnp.int64), s.steals),
        overflow=s.overflow | ovf)


def _state(sizes, capacity, seed, jobs=5, aux_rows=3):
    rng = np.random.default_rng(seed)
    zeros = np.zeros(D, np.int64)
    return SearchState(
        prmu=rng.integers(0, 1000, (D, jobs, capacity)).astype(np.int16),
        depth=rng.integers(0, 20, (D, capacity)).astype(np.int16),
        aux=rng.integers(-500, 500, (D, aux_rows, capacity)).astype(
            np.int16),
        size=np.asarray(sizes, np.int32),
        best=np.full(D, 999, np.int32),
        tree=zeros, sol=zeros, iters=zeros, evals=zeros,
        sent=zeros + 3, recv=zeros + 5, steals=zeros + 1,
        overflow=np.zeros(D, bool),
        telemetry=np.zeros((D, 0), np.int64))


def _run(round_fn, state, transfer_cap, min_transfer, limit):
    spec = tuple(P(AX) for _ in SearchState._fields)

    def body(*leaves):
        s = distributed._local_state(*leaves)
        return distributed._expand(
            round_fn(s, transfer_cap, min_transfer, limit))

    f = jax.jit(shard_map(body, worker_mesh(D), in_specs=spec,
                          out_specs=spec))
    return SearchState(*(np.asarray(x) for x in f(*state)))


def _assert_same_pools(got, want, limit):
    """Same counters, and the same live rows below the limit (an aborted
    round of the oracle wrote its zero block at the limit)."""
    for f in ("size", "sent", "recv", "steals", "overflow"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f),
                                      err_msg=f)
    for d in range(D):
        n = min(int(want.size[d]), limit)
        np.testing.assert_array_equal(got.prmu[d, :, :n], want.prmu[d, :, :n])
        np.testing.assert_array_equal(got.depth[d, :n], want.depth[d, :n])
        np.testing.assert_array_equal(got.aux[d, :, :n], want.aux[d, :, :n])


CAP = 16                       # transfer_cap of the exchange cases
CAPACITY = 256
LIMIT = CAPACITY - D * CAP     # the headroom _DistDriver.limit keeps


def _exchange_cases():
    rng = np.random.default_rng(7)
    cases = [pytest.param(list(rng.integers(0, LIMIT + 1, D)), 2,
                          id=f"random{i}") for i in range(6)]
    return cases + [
        # one donor feeds three receivers, up to the cap of each pair
        pytest.param([LIMIT - 40, 0, 0, 0], 2, id="donor-feeds-three"),
        pytest.param([150, 20, 3, 0], 2, id="donor-feeds-three-uneven"),
        # two donors share a receiver
        pytest.param([100, 90, 0, 30], 2, id="two-donors"),
        # a donor full to the limit: its blocks reach the headroom
        pytest.param([LIMIT, LIMIT - 60, 5, 0], 2, id="donor-at-limit"),
        # a donor above the limit would end above it: the round aborts
        # on every worker, the overflow flag is raised, nothing moves
        pytest.param([LIMIT + 60, 0, 0, 0], 2, id="would-overflow"),
        # balanced within the threshold: no flow
        pytest.param([50, 52, 49, 51], 8, id="no-flow"),
        # above the mean, but below the threshold
        pytest.param([60, 40, 40, 40], 16, id="below-threshold"),
    ]


@pytest.mark.parametrize("sizes,min_transfer", _exchange_cases())
def test_sliced_exchange_commits_the_oracle_pools(sizes, min_transfer):
    state = _state(sizes, CAPACITY, seed=sum(sizes))
    got = _run(distributed._balance_round, state, CAP, min_transfer, LIMIT)
    want = _run(_gather_sort_round, state, CAP, min_transfer, LIMIT)
    _assert_same_pools(got, want, LIMIT)
    moved = int(np.asarray(want.recv - state.recv).sum())
    if sizes == [LIMIT + 60, 0, 0, 0]:
        assert got.overflow.all() and moved == 0
        for f in ("prmu", "depth", "aux"):
            np.testing.assert_array_equal(getattr(got, f)[..., :LIMIT],
                                          getattr(state, f)[..., :LIMIT])
    elif min_transfer > 2:
        assert moved == 0
    else:
        assert moved > 0


def test_a_round_that_moves_nothing_writes_only_above_the_limit():
    state = _state([50, 52, 49, 51], CAPACITY, seed=1)
    got = _run(distributed._balance_round, state, CAP, 8, LIMIT)
    for f in ("prmu", "depth", "aux"):
        a, b = getattr(got, f), getattr(state, f)
        np.testing.assert_array_equal(a[..., :LIMIT], b[..., :LIMIT])
        np.testing.assert_array_equal(a[..., LIMIT + CAP:],
                                      b[..., LIMIT + CAP:])


def test_block_starts_stay_inside_the_pool_at_the_limit():
    """At limit = capacity - D*cap every block of `cap` rows starts at
    or below the limit, so none is clamped, in every round that
    commits. Sizes run over the whole range, the edges included."""
    rng = np.random.default_rng(3)
    sizes = np.concatenate([
        rng.integers(0, LIMIT + 1, (400, D)),
        [[LIMIT] * D, [LIMIT, 0, 0, 0], [LIMIT, LIMIT, 0, 0],
         [LIMIT, LIMIT, LIMIT, 0], [0] * D]]).astype(np.int32)

    @jax.jit
    def starts(sizes):
        plan = bal.exchange_plan(sizes, CAP, 2)
        send, recv = jax.vmap(
            lambda me: distributed.block_starts(plan, me, sizes[me]))(
                jnp.arange(D))
        total_in = plan.sum(axis=0)
        base = sizes - plan.sum(axis=1)
        return send, recv, plan, (base + total_in <= LIMIT).all()

    flows = 0
    for sz in sizes:
        send, recv, plan, fits = (np.asarray(x) for x in starts(sz))
        assert fits
        flows += int(plan.sum() > 0)
        for me in range(D):
            assert send[me].min() >= 0 and recv[me].min() >= 0
            assert send[me].max() + CAP <= CAPACITY
            assert recv[me].max() + CAP <= CAPACITY
            assert send[me].max() <= LIMIT and recv[me].max() <= LIMIT
            # the sent rows are the top of the live pool, in order
            assert send[me][-1] + plan[me, -1] == sz[me]
    assert flows > 300


def test_exchange_plan_steal_half_threshold():
    """The flagship's defaults (chunk 65536, 20x20, 4 chips, -m 25): a
    chip donates its whole surplus once it holds 2*m = 50 nodes above
    the mean (the reference's steal threshold), where the former 2*chunk
    threshold moved nothing."""
    cap, min_transfer = distributed.balance_defaults(
        65536, 20, 20, 4, 25, aux_itemsize=2)
    assert (cap, min_transfer) == (65536, 50)
    # mean 1000, one donor 150 above it, one receiver 150 below
    at = np.asarray(bal.exchange_plan(
        jnp.asarray([1150, 1000, 1000, 850], jnp.int32), cap, min_transfer))
    assert at[0].sum() == 150 and at[0, 3] == 150 and at.sum() == 150
    below = np.asarray(bal.exchange_plan(
        jnp.asarray([1049, 1000, 1000, 951], jnp.int32), cap, min_transfer))
    assert below.sum() == 0
    edge = np.asarray(bal.exchange_plan(
        jnp.asarray([1050, 1000, 1000, 950], jnp.int32), cap, min_transfer))
    assert edge[0, 3] == 50 and edge.sum() == 50
    old = np.asarray(bal.exchange_plan(
        jnp.asarray([1150, 1000, 1000, 850], jnp.int32), cap, 2 * 65536))
    assert old.sum() == 0


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_exchange_plan_fills_every_chip_to_the_mean(n_dev):
    """With the cap at least the largest deficit and no threshold, one
    round water-fills: receivers end at the mean, donors keep at most
    the remainder of the mean's division above it, no row is lost."""
    rng = np.random.default_rng(n_dev)
    plan_fn = jax.jit(lambda s, cap: bal.exchange_plan(s, cap, 0),
                      static_argnums=1)
    for hi in (8, 100, 5000) * 40:
        sizes = rng.integers(0, hi + 1, n_dev).astype(np.int32)
        total = int(sizes.sum())
        mean, rem = total // n_dev, total % n_dev
        cap = max(int((mean - sizes).max()), 1)
        plan = np.asarray(plan_fn(jnp.asarray(sizes), cap))
        assert (plan >= 0).all() and np.diag(plan).sum() == 0
        after = sizes - plan.sum(axis=1) + plan.sum(axis=0)
        assert after.sum() == total
        assert (after >= mean).all() and (after <= mean + rem).all()
        recv, donor = sizes < mean, sizes >= mean
        assert (after[recv] == mean).all()
        assert (plan[recv].sum() == 0) and (plan[:, donor].sum() == 0)
        assert (after[donor] <= sizes[donor]).all()


def test_transfer_cap_is_one_chunk_within_the_byte_budget():
    assert distributed.balance_defaults(64, 20, 5, 4, 32) == (64, 64)
    # a wide class at 8 workers: the byte budget binds
    cap, _ = distributed.balance_defaults(65536, 200, 20, 8, 25,
                                          aux_itemsize=2)
    assert cap == distributed.BALANCE_BYTE_BUDGET // ((400 + 40 + 2) * 8)
    assert cap < 65536


# ta003 LB2 at UB = opt on 4 workers with the default knobs: the largest
# worker's tree over the mean (1.23 on the CPU mesh; 1.33 when a donor
# gave half its surplus). Without balancing
# (an unreachable donor threshold) one worker explores 1.86x the mean.
SPREAD_BOUND = 1.4


def _tree_over_mean(res):
    tree = np.asarray(res.per_device["tree"], float)
    return tree.max() / tree.mean()


def test_default_knobs_spread_a_skewed_tree():
    p = taillard.processing_times(3)
    kw = dict(lb_kind=2, init_ub=taillard.optimal_makespan(3),
              n_devices=D, chunk=32, capacity=1 << 14, min_seed=8)
    res = distributed.search(p, **kw)
    assert (res.explored_tree, res.best) == (80062, 1081)
    assert _tree_over_mean(res) <= SPREAD_BOUND
    off = distributed.search(p, min_transfer=2**30, **kw)
    assert off.explored_tree == 80062
    assert _tree_over_mean(off) > 1.5


def test_whole_surplus_spreads_ta003():
    """ta003 LB2 at UB = opt, chunk 512, 4 workers, -m 8: the round
    fills every chip to the mean, so the largest chip's tree stays near
    the mean and the search ends in fewer iterations. Halving the
    surplus instead read 1.371 and 56 iterations here."""
    p = taillard.processing_times(3)
    res = distributed.search(p, lb_kind=2,
                             init_ub=taillard.optimal_makespan(3),
                             n_devices=D, chunk=512, capacity=1 << 16,
                             min_seed=8)
    assert (res.explored_tree, res.best) == (80062, 1081)
    assert _tree_over_mean(res) <= 1.2
    assert int(res.per_device["iters"].max()) <= 50


class _Captured(Exception):
    pass


def _capture(monkeypatch, target, name, got, argnames):
    """Replace target.name by a stub that records the balance knobs it
    is handed and stops the caller there."""
    import inspect
    sig = inspect.signature(getattr(target, name))

    def stub(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        got.append(tuple(int(bound.arguments[a]) for a in argnames))
        raise _Captured

    monkeypatch.setattr(target, name, stub)


class _PlainCache:
    def get_or_build(self, key, build):
        return build()


KNOBS = ("transfer_cap", "min_transfer")


def test_every_caller_takes_the_same_default_knobs(monkeypatch, tmp_path):
    from tpu_tree_search import cli
    from tpu_tree_search.engine import device
    from tpu_tree_search.tune import probe
    from tpu_tree_search.utils import csv_stats, phase_timing

    p = taillard.processing_times(1)           # 20 jobs x 5 machines
    jobs, machines = p.shape[1], p.shape[0]
    itemsize = device.aux_dtype(p).itemsize
    chunk, min_seed = 64, 25
    want = distributed.balance_defaults(chunk, jobs, machines, D, min_seed,
                                        aux_itemsize=itemsize)
    got = []
    _capture(monkeypatch, distributed, "build_dist_loop", got, KNOBS)
    common = dict(lb_kind=2, chunk=chunk, n_devices=D, min_seed=min_seed,
                  capacity=1 << 14, loop_cache=_PlainCache())
    with pytest.raises(_Captured):
        distributed.search(p, init_ub=taillard.optimal_makespan(1),
                           **common)
    with pytest.raises(_Captured):
        distributed.prewarm(p, **common)
    assert got == [want, want]

    # the chunk ladder: each rung's own defaults
    mesh = worker_mesh(D)
    rungs, drivers = distributed._ladder_plan(
        distributed._resolve_problem("pfsp"), mesh, None, p, 2, 1024, 4,
        None, None, min_seed, device.aux_dtype(p), None)
    assert len(rungs) >= 2
    for c, drv in drivers.items():
        assert (drv.transfer_cap, drv.min_transfer) == \
            distributed.balance_defaults(c, jobs, machines, D, min_seed,
                                         aux_itemsize=itemsize)

    # megabatch
    got.clear()
    _capture(monkeypatch, megabatch, "build_batched_loop", got, KNOBS)
    spec = megabatch.MemberSpec(table=p)
    with pytest.raises(_Captured):
        megabatch.serve_batch([spec], lb_kind=2, mesh=mesh, chunk=chunk,
                              balance_period=4, capacity=1 << 14,
                              min_seed=min_seed)
    assert got == [want]

    # the tuner's probe: one worker, the engine's default warm-up size
    got.clear()
    harness = probe.ProbeHarness(p, lb_kind=1, capacity=1 << 14,
                                 warm_iters=4, window_iters=2, repeats=1)
    with pytest.raises(_Captured):
        harness.measure(chunk, 4)
    assert got == [distributed.balance_defaults(
        chunk, jobs, machines, 1, distributed.MIN_SEED,
        aux_itemsize=itemsize)]

    # the CLI's phase profiler, at the run's -m
    got.clear()
    _capture(monkeypatch, phase_timing, "profile_balance", got, KNOBS)
    monkeypatch.setattr(phase_timing, "profile_phases",
                        lambda *a, **k: {})
    args = types.SimpleNamespace(
        chunk=chunk, capacity=1 << 14, lb=2, ws=1, L=1, balance_period=4,
        m=min_seed, M=50000, T=5000, C=0, inst=1,
        csv=str(tmp_path / "run.csv"), multihost=False)
    cli._write_csv_with_phases(args, p, None, D, 1.0, 10, 1, 1000,
                               {k: [1] * D for k in ("tree", "sol", "evals",
                                                     "iters", "steals",
                                                     "recv")},
                               csv_stats)
    assert got == [want]
