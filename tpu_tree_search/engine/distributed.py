"""Multi-device distributed PFSP engine: one SPMD program over the mesh.

The reference needs three nested runtimes for this — OpenMP threads per
node (pfsp_multigpu_cuda.c:143), MPI ranks across nodes with a dedicated
communicator thread (pfsp_dist_multigpu_cuda.c:283, 364-469), and CUDA
streams per GPU. Here the whole hierarchy is one `shard_map`ped program
over a 1-D worker mesh: every worker owns a private HBM pool and runs the
same compiled loop; every `balance_period` steps the workers

  - share the incumbent via `pmin` (the per-round Allreduce MIN of
    `best_l`, dist:369-374, and the intra-node `checkBest` CAS,
    pfsp_multigpu_cuda.c:30-50, in one op),
  - rebalance pools via all_gather + all_to_all (see parallel/balance.py),

and the loop predicate `psum(has_work) > 0` *is* the distributed
termination detection (`globalTermination`'s Allgather of has-work flags,
dist:69-88, moved on-device).

Phase schedule mirrors the reference's 3-step scheme (dist:193-205,
864-882): a replicated-cost host BFS warm-up generates a frontier of at
least `min_seed * workers` nodes (step 1), round-robin striding assigns
each worker its stripe (`roundRobin_distribution`, Pool_atom.c:14-36),
the SPMD loop explores (step 2), and exhaustion needs no step-3 drain
because the collective balance keeps feeding idle workers until the
global pool is empty.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs import audit as obs_audit
from ..obs import tracelog
from ..ops import pallas_fused
from ..ops import reference as ref
from ..ops.batched import BoundTables
from ..parallel import balance as bal
from ..parallel.mesh import WORKER_AXIS, shard_map, worker_mesh
from . import sequential as seq
from . import telemetry as tele
from .device import I32_MAX, SearchState

AX = WORKER_AXIS

# donation under shard_map is best-effort: a backend that cannot alias
# a given buffer falls back to a copy and warns per execution — noise,
# not an error, on the CPU test mesh (the overlapped driver still gets
# async dispatch; only the zero-copy carry is backend-dependent).
# run_async scopes the suppression to its own donating dispatch so
# importing this module never mutes the diagnostic for anyone else's
# donate_argnums code.
import warnings as _warnings  # noqa: E402

# per-worker byte budget for one balance round's all_to_all buffers
# (each way); caps the DEFAULT transfer_cap at production shapes — see
# default_transfer_cap() and tools/bench_balance.py for the measured
# tradeoff
BALANCE_BYTE_BUDGET = 64 << 20


def default_transfer_cap(chunk: int, jobs: int, machines: int,
                         n_dev: int, aux_itemsize: int = 4) -> int:
    """Default balance transfer cap: 4*chunk, byte-budgeted. The
    all_to_all moves (2J + aux_itemsize*A + 2) bytes per column over
    D*transfer_cap columns each way per worker; at production shapes
    (chunk 32768, 20x20, D=8) the uncapped default is ~122 MB of
    exchange buffer per worker per round — the cap bounds it to
    BALANCE_BYTE_BUDGET. `aux_itemsize` is the pool aux dtype's width
    (2 for the int16 classes, device.aux_dtype). SHARED by search() and
    the CSV phase profiler (cli) so the profiled exchange is the one
    production runs."""
    bytes_per_col = 2 * jobs + aux_itemsize * machines + 2
    budget_cols = BALANCE_BYTE_BUDGET // (bytes_per_col * max(n_dev, 1))
    return max(min(4 * chunk, budget_cols), 256)


# ---------------------------------------------------------------------------
# Step 1: host BFS warm-up (breadth generates parallelism; reference runs
# this replicated on every rank, dist:198-205 — here once on the host)

_native_warned = False


def _warn_native_unavailable(e: Exception) -> None:
    """A broken native toolchain must degrade LOUDLY, not silently — the
    pure-Python warm-up produces identical results but is orders of
    magnitude slower, which would otherwise look like a perf regression
    with no cause."""
    global _native_warned
    if not _native_warned:
        _native_warned = True
        import warnings
        warnings.warn(
            f"native host runtime unavailable ({e!r}); falling back to "
            "the pure-Python warm-up (identical results, much slower). "
            "Check `g++` and tpu_tree_search/native/__init__.py:build.",
            RuntimeWarning, stacklevel=3)


@dataclasses.dataclass
class Frontier:
    prmu: np.ndarray    # (n, jobs) int16
    depth: np.ndarray   # (n,) int16
    tree: int           # counters accumulated during warm-up
    sol: int
    best: int
    aux: np.ndarray | None = None  # (n, A) per-node pool tables, in the
                                   # pool's aux dtype (device.aux_dtype)


def bfs_warmup(p_times: np.ndarray, lb_kind: int, init_ub: int | None,
               target: int, use_native: bool = True) -> Frontier:
    """Pop-front BFS until the frontier holds >= target nodes (or the tree
    is exhausted). Same decompose semantics as the oracle, so warm-up
    counters + device counters add up to the sequential totals.

    Uses the native C++ runtime when available (tpu_tree_search/native);
    the pure-Python path below is the validated fallback/oracle.
    """
    if use_native:
        try:
            from .. import native
            prmu, depth, tree, sol, best = native.bfs_frontier(
                p_times, lb_kind, init_ub, target)
            return Frontier(prmu=prmu, depth=depth, tree=tree, sol=sol,
                            best=best)
        except Exception as e:
            _warn_native_unavailable(e)  # loud fallback, same results
    jobs = p_times.shape[1]
    lb1 = ref.make_lb1_data(p_times)
    lb2 = ref.make_lb2_data(lb1) if lb_kind == seq.LB2 else None
    best = seq.INT_MAX if init_ub is None else int(init_ub)
    tree = sol = 0

    from collections import deque
    frontier: deque[tuple[np.ndarray, int]] = deque(
        [(np.arange(jobs, dtype=np.int16), 0)]
    )
    while frontier and len(frontier) < target:
        prmu, depth = frontier.popleft()
        limit1 = depth - 1
        if lb_kind == seq.LB1_D:
            lb_begin = ref.lb1_children_bounds(lb1, prmu, limit1, jobs)
        for i in range(depth, jobs):
            child = prmu.copy()
            child[depth], child[i] = child[i], child[depth]
            if lb_kind == seq.LB1:
                bound = ref.lb1_bound(lb1, child, limit1 + 1, jobs)
            elif lb_kind == seq.LB1_D:
                bound = int(lb_begin[int(prmu[i])])
            else:
                bound = ref.lb2_bound(lb1, lb2, child, limit1 + 1, jobs, best)
            if depth + 1 == jobs:
                sol += 1
                if bound < best:
                    best = bound
            elif bound < best:
                frontier.append((child, depth + 1))
                tree += 1

    if frontier:
        prmu = np.stack([f[0] for f in frontier]).astype(np.int16)
        depth = np.array([f[1] for f in frontier], dtype=np.int16)
    else:
        prmu = np.zeros((0, jobs), np.int16)
        depth = np.zeros((0,), np.int16)
    return Frontier(prmu=prmu, depth=depth, tree=tree, sol=sol, best=best)


# ---------------------------------------------------------------------------
# Step 2: the SPMD search loop


def _balance_round(s: SearchState, transfer_cap: int,
                   min_transfer: int, limit: int) -> SearchState:
    """One collective steal-half exchange (see parallel/balance.py).

    `limit` is the usable-row bound every commit must respect; the loop
    builder reserves `D * transfer_cap` rows of headroom above it (and
    runs the local steps against the same tightened limit), so the
    receive block write is ALWAYS in bounds — an overflowing round never
    clamps onto live rows.

    The round is globally transactional: each worker's would-overflow
    flag (known before any data moves — a worker receives exactly
    plan[:, me].sum() nodes) is psum'd, and if any worker would
    overflow, no worker exchanges or commits. The loop then exits on the
    overflow flag and the driver grows every pool and RESUMES from this
    state, losing nothing.

    The pack/exchange/unpack (the gathers, the all_to_all, the sort) is
    cond-gated on the plan being non-empty and fitting — a balanced
    steady state pays one all_gather of the sizes, one tiny psum, and a
    zero-block scratch write.
    """
    J, capacity = s.prmu.shape
    A = s.aux.shape[0]
    D = jax.lax.psum(1, AX)
    sizes = jax.lax.all_gather(s.size, AX)                  # (D,)
    plan = bal.exchange_plan(sizes, transfer_cap, min_transfer)
    me = jax.lax.axis_index(AX)
    my_out = plan[me]                                       # (D,)
    total_out = my_out.sum(dtype=jnp.int32)
    total_in = plan[:, me].sum(dtype=jnp.int32)
    base = s.size - total_out
    n_recv = plan.shape[0] * transfer_cap
    # Would-overflow is known BEFORE the exchange (each worker receives
    # exactly plan[:, me].sum() nodes) and is decided globally: if ANY
    # worker would overflow, NO worker exchanges or commits — every node
    # keeps living in exactly one pool, the loop exits on the flag, and
    # the driver grows every pool and resumes losslessly (the round-1
    # design restarted from the warm-up frontier, discarding all
    # explored work).
    ovf = jax.lax.psum((base + total_in > limit).astype(jnp.int32), AX) > 0
    # identical on every worker (plan and ovf are pure functions of the
    # all_gathered sizes), so the cond below cannot diverge across the
    # mesh and the collectives inside it are safe
    do_flow = (plan.sum() > 0) & ~ovf

    def do_exchange(_):
        # pack donated nodes (from the stack top) into per-receiver blocks
        offs = jnp.cumsum(my_out, dtype=jnp.int32) - my_out
        k = jnp.arange(transfer_cap, dtype=jnp.int32)
        rows = base + offs[:, None] + k[None, :]            # (D, cap)
        send_mask = k[None, :] < my_out[:, None]
        rows_c = jnp.clip(rows, 0, capacity - 1).reshape(-1)
        buf_prmu = jnp.take(s.prmu, rows_c, axis=1)         # (J, D*cap)
        buf_aux = jnp.take(s.aux, rows_c, axis=1)           # (A, D*cap)
        buf_depth = jnp.where(send_mask.reshape(-1),
                              s.depth[rows_c], -1)[None, :]  # -1 = hole

        # all_to_all exchanges the per-receiver blocks (the D axis must
        # be the split axis exactly)
        def exchange(x):
            rows = x.shape[0]
            blocks = x.reshape(rows, D, transfer_cap)
            return jax.lax.all_to_all(blocks, AX, 1, 1) \
                .reshape(rows, D * transfer_cap)

        rbuf_prmu = exchange(buf_prmu)
        rbuf_aux = exchange(buf_aux)
        rbuf_depth = exchange(buf_depth)

        # compact received nodes to the front of the block (same
        # scatter-free scheme as device.step)
        flat_depth = rbuf_depth.reshape(-1)
        push = flat_depth >= 0
        order = jnp.argsort(~push, stable=True)
        return (jnp.take(rbuf_prmu, order, axis=1),
                jnp.take(rbuf_aux, order, axis=1),
                jnp.take(flat_depth, order).astype(jnp.int16),
                push.sum(dtype=jnp.int32))

    def no_exchange(_):
        return (jnp.zeros((J, n_recv), s.prmu.dtype),
                jnp.zeros((A, n_recv), s.aux.dtype),
                jnp.full((n_recv,), -1, s.depth.dtype),
                jnp.int32(0))

    recv_prmu, recv_aux, recv_depth, n_push = jax.lax.cond(
        do_flow, do_exchange, no_exchange, 0)

    # Commit (a skipped/aborted round routes its zero block to the
    # scratch rows above `limit` — in bounds by the loop builder's
    # headroom reservation, and never read because rows above the
    # cursor are garbage by the pool invariant).
    zero = jnp.zeros((), base.dtype)
    write_at = jnp.where(do_flow, base, jnp.asarray(limit, base.dtype))
    keep = lambda new, old: jnp.where(do_flow, new, old)  # noqa: E731
    telem = s.telemetry
    if telem.shape[-1] > 0:
        # steal-flow telemetry mirrors the sent/recv counters below,
        # under the same committed-round guard
        t = telem.at[tele.O_STEAL_SENT].add(total_out.astype(jnp.int64))
        t = t.at[tele.O_STEAL_RECV].add(n_push.astype(jnp.int64))
        telem = keep(t, telem)
    return s._replace(
        telemetry=telem,
        prmu=jax.lax.dynamic_update_slice(s.prmu, recv_prmu,
                                          (zero, write_at)),
        depth=jax.lax.dynamic_update_slice(s.depth, recv_depth,
                                           (write_at,)),
        aux=jax.lax.dynamic_update_slice(s.aux, recv_aux, (zero, write_at)),
        size=keep(base + n_push, s.size),
        sent=keep(s.sent + total_out.astype(jnp.int64), s.sent),
        recv=keep(s.recv + n_push.astype(jnp.int64), s.recv),
        steals=keep(s.steals + (n_push > 0).astype(jnp.int64), s.steals),
        overflow=s.overflow | ovf,
    )


def _local_state(*leaves):
    return SearchState(*(x[0] for x in leaves))


def _expand(s: SearchState):
    return tuple(x[None, ...] for x in s)


def member_body(tables, make_local_step, balance_period: int,
                transfer_cap: int, min_transfer: int, limit: int):
    """One macro-iteration of the SPMD loop for ONE instance:
    `balance_period` local steps, the pmin incumbent exchange, one
    balance round. Shared by :func:`build_dist_loop` (the solo loop)
    and engine/megabatch.build_batched_loop (the same body vmapped over
    a leading instance axis), so the batched member semantics can never
    drift from the solo loop — the bit-parity contract between a
    megabatched request and its solo run rests on this being ONE
    function."""
    local_step = make_local_step(tables, limit)

    def body(s: SearchState) -> SearchState:
        s = jax.lax.fori_loop(0, balance_period,
                              lambda _, x: local_step(x), s)
        s = s._replace(best=jax.lax.pmin(s.best, AX))
        return _balance_round(s, transfer_cap, min_transfer, limit)

    return body


def build_dist_loop(mesh, tables, make_local_step,
                    balance_period: int, transfer_cap: int,
                    min_transfer: int, limit: int,
                    donate_pools: bool = False):
    """Compile a distributed search loop for any problem: state sharded
    over the worker axis, problem tables replicated.

    `make_local_step(tables, limit)` returns the problem's
    SearchState -> SearchState step, bounded to `limit` usable rows —
    the SAME tightened limit the balance round commits against, chosen
    by the driver so both the step scratch block and the balance receive
    block fit above it (see _balance_round).

    The compiled function has signature
    `run(tables, max_iters, bound_cap, *state)` with `max_iters` a
    TRACED cumulative per-worker iteration ceiling (like device.run's)
    and `bound_cap` a TRACED pruning ceiling folded into the incumbent
    at loop entry (`min(best, bound_cap)` — pass I32_MAX for "no cap").
    The cap is how cross-request incumbent sharing reaches the compiled
    loop without a retrace (engine/incumbent.py); with the cap at
    I32_MAX the fold is the identity, so non-sharing runs are
    bit-identical to the pre-cap loop. Segmented drivers pass a new
    ceiling/cap every segment and hit the compile cache.

    `donate_pools=True` donates the pool leaves (prmu/depth/aux) to the
    XLA call, so the while-loop carry aliases the input buffers instead
    of copying them — the overlapped driver's dispatch
    (_DistDriver.run_async) requires it; the caller must treat the
    input state's pool arrays as CONSUMED."""

    def worker_loop(tables, max_iters, bound_cap, *state_leaves):
        s = _local_state(*state_leaves)
        s = s._replace(best=jnp.minimum(s.best, bound_cap))

        def cond(s: SearchState):
            has_work = jax.lax.psum(s.size, AX) > 0
            ok = jax.lax.psum(s.overflow.astype(jnp.int32), AX) == 0
            return has_work & ok & (s.iters < max_iters)

        body = member_body(tables, make_local_step, balance_period,
                           transfer_cap, min_transfer, limit)

        return _expand(jax.lax.while_loop(cond, body, s))

    spec_state = tuple(P(AX) for _ in SearchState._fields)
    spec_tables = jax.tree.map(lambda _: P(), tables)
    sharded = shard_map(
        worker_loop, mesh,
        in_specs=(spec_tables, P(), P()) + spec_state,
        out_specs=spec_state,
    )
    if donate_pools:
        # args: 0=tables, 1=max_iters, 2=bound_cap, 3=prmu, 4=depth,
        # 5=aux (SearchState field order), then the scalar leaves
        return jax.jit(sharded, donate_argnums=(3, 4, 5))
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Host entry point


class DistResult:
    def __init__(self, explored_tree, explored_sol, best, per_device,
                 warmup_tree, warmup_sol, complete=True, telemetry=None,
                 problem: str = "pfsp"):
        self.explored_tree = explored_tree
        self.explored_sol = explored_sol
        self.best = best
        self.per_device = per_device        # dict of (D,) arrays for stats
        self.warmup_tree = warmup_tree
        self.warmup_sol = warmup_sol
        self.complete = complete            # all pools drained
        self.telemetry = telemetry          # telemetry.summarize dict
                                            # (None when the block is off)
        self.problem = problem              # registry name; the audit
                                            # keys its conservation
                                            # identity off the plugin's
                                            # accounting semantics


def _shard_frontier(fr: Frontier, n_dev: int, capacity: int, jobs: int,
                    init_best: int, limit: int | None = None):
    """Round-robin stripe the frontier across workers
    (reference: roundRobin_distribution, Pool_atom.c:14-36). `limit`
    (device.row_limit) bounds each stripe so seeding respects the
    engine's usable-row invariant."""
    if limit is None:
        limit = capacity
    aux_w = 0 if fr.aux is None else fr.aux.shape[1]
    prmu = np.zeros((n_dev, jobs, capacity), np.int16)
    depth = np.zeros((n_dev, capacity), np.int16)
    aux = np.zeros((n_dev, aux_w, capacity),
                   fr.aux.dtype if aux_w else np.int32)
    sizes = np.zeros(n_dev, np.int32)
    for d in range(n_dev):
        stripe_p = fr.prmu[d::n_dev]
        stripe_d = fr.depth[d::n_dev]
        n = len(stripe_d)
        assert n <= limit
        prmu[d, :, :n] = stripe_p.T
        depth[d, :n] = stripe_d
        if aux_w:
            aux[d, :, :n] = fr.aux[d::n_dev].T
        sizes[d] = n
    return (
        jnp.asarray(prmu), jnp.asarray(depth), jnp.asarray(aux),
        jnp.asarray(sizes),
        jnp.full((n_dev,), init_best, jnp.int32),
        jnp.zeros(n_dev, jnp.int64), jnp.zeros(n_dev, jnp.int64),
        jnp.zeros(n_dev, jnp.int64), jnp.zeros(n_dev, jnp.int64),
        jnp.zeros(n_dev, jnp.int64), jnp.zeros(n_dev, jnp.int64),
        jnp.zeros(n_dev, jnp.int64),
        jnp.zeros(n_dev, bool),
        jnp.zeros((n_dev, tele.enabled_width()), jnp.int64),
    )


def _fetch(x) -> np.ndarray:
    """Bring a possibly globally-sharded per-device array to every host.

    Single-controller (the normal case): a plain fetch. Multi-controller
    (--multihost): the output spans non-addressable devices, so gather it
    with multihost_utils tiled=True (the array is already global (D,...);
    tiled=False would RE-STACK per-process and is rejected for
    non-addressable inputs). Every process ends up with the full array —
    the reference's stats Gather-to-rank-0 (dist:817-832) except every
    rank gets the totals."""
    from .checkpoint import _to_np
    return _to_np(x)


def _to_mesh(mesh, spec_leaf, x):
    """Commit one host-built state leaf to the mesh.

    Multi-controller JAX rejects plain host arrays as jit inputs over a
    global mesh; every process holds the identical global value (the
    warm-up is replicated, like the reference's step 1 on every rank,
    dist:198-205), so build the global array from per-shard callbacks."""
    if jax.process_count() > 1:
        from jax.sharding import NamedSharding
        sharding = NamedSharding(mesh, spec_leaf)
        return jax.make_array_from_callback(
            np.shape(x), sharding, lambda idx: np.asarray(x)[idx])
    if np.asarray(x).size == 0:
        # a zero-width leaf (the telemetry block with the flag off) is
        # DEAD in the loop body, so sharding propagation cannot pin it:
        # lowered from a plain host array it compiles REPLICATED, while
        # every later segment passes the loop's P(AX)-sharded output —
        # an AOT executable then rejects the second call and falls back
        # to jit (one hidden recompile per served shape). Commit it on
        # the worker axis explicitly, like abstract_state does for the
        # pre-warm lowering, so call 1 and call N agree.
        from jax.sharding import NamedSharding
        return jax.device_put(x, NamedSharding(mesh, spec_leaf))
    return x


def fetch_state(state: SearchState) -> SearchState:
    """Fetch every state leaf to host numpy (multihost: allgather the
    global value so every process holds it — needed for checkpointing
    and pool growth)."""
    return SearchState(*(_fetch(x) for x in state))


class _DistDriver:
    """Compiles/caches the SPMD loop per pool capacity and runs it with
    lossless overflow recovery: on overflow the stacked state is fetched,
    every pool re-homed into double the capacity (checkpoint.grow), the
    loop rebuilt for the new shapes, and the search RESUMED from exactly
    where it stopped — no explored work is ever discarded (the round-1
    design restarted overflowing runs from the warm-up frontier).

    `limit_fn(capacity)` is the problem's usable-row bound (e.g.
    device.row_limit); the driver tightens it so the balance receive
    block also fits above the limit (see _balance_round)."""

    def __init__(self, mesh, tables, make_local_step, balance_period: int,
                 transfer_cap: int, min_transfer: int, limit_fn,
                 loop_cache=None, loop_key: tuple = ()):
        self.mesh = mesh
        self.tables = tables
        self.make_local_step = make_local_step
        self.balance_period = balance_period
        self.transfer_cap = transfer_cap
        self.min_transfer = min_transfer
        self.limit_fn = limit_fn
        self.n_recv = mesh.devices.size * transfer_cap
        self._loops: dict[int, object] = {}
        self.spec_state = tuple(P(AX) for _ in SearchState._fields)
        # Cross-driver executable reuse: `loop_cache` is any object with
        # get_or_build(key, build) (service/executors.ExecutorCache).
        # The compiled loop takes the problem TABLES as a runtime
        # argument, so it depends only on shapes/specialization — two
        # same-shape instances (e.g. all ten Taillard ta021-030) at the
        # same lb/chunk on the same submesh share ONE trace + compile.
        # `loop_key` carries the caller-side specialization (problem
        # kind, jobs, machines, lb_kind, chunk, aux dtype); the driver
        # appends everything else the trace closes over (device
        # identities, capacity, balance knobs, row limit).
        self.loop_cache = loop_cache
        self.loop_key = tuple(loop_key) + tuple(
            int(d.id) for d in mesh.devices.flat)

    def limit(self, capacity: int) -> int:
        return min(self.limit_fn(capacity), capacity - self.n_recv)

    def _loop(self, capacity: int, donate: bool = False):
        memo_key = (capacity, donate)
        if memo_key not in self._loops:
            build = lambda: build_dist_loop(  # noqa: E731
                self.mesh, self.tables, self.make_local_step,
                self.balance_period, self.transfer_cap, self.min_transfer,
                limit=self.limit(capacity), donate_pools=donate)
            if self.loop_cache is not None:
                # consult the shared cache ONCE per driver+capacity (the
                # local memo absorbs the per-segment lookups), so its
                # hit/miss counters read as requests-that-reused /
                # actual-compiles
                key = self.loop_key + (capacity, self.balance_period,
                                       self.transfer_cap,
                                       self.min_transfer,
                                       self.limit(capacity))
                if donate:
                    # a donating executable has different buffer-alias
                    # semantics: it must never be handed to a caller
                    # that expects its inputs to survive
                    key = key + ("donate",)
                self._loops[memo_key] = self.loop_cache.get_or_build(
                    key, build)
            else:
                self._loops[memo_key] = build()
        return self._loops[memo_key]

    def commit(self, state: SearchState) -> SearchState:
        """Commit host-built state leaves to the mesh."""
        return SearchState(*(_to_mesh(self.mesh, s, x)
                             for s, x in zip(self.spec_state, state)))

    def _pin_empty(self, state: SearchState) -> SearchState:
        """Re-commit zero-size leaves (the telemetry block with the flag
        off) to the worker axis. The loop returns them replicated, and
        an AOT executable compiled for the axis-sharded signature
        rejects them on the next call. Pinning the loop's output
        shardings in the jit instead crashes the TPU compiler's Shardy
        import on a 4-chip mesh (seen compiling for a described v5e
        2x2, PR 21). Moving a zero-size array costs nothing. Multi-
        controller runs have no AOT executables and cannot reshard a
        global array this way, so they keep the leaves as they are."""
        from jax.sharding import NamedSharding
        if jax.process_count() > 1:
            return state
        return SearchState(*(
            jax.device_put(x, NamedSharding(self.mesh, s))
            if x.size == 0 else x
            for s, x in zip(self.spec_state, state)))

    @staticmethod
    def _cap(bound_cap) -> jnp.ndarray:
        return jnp.asarray(I32_MAX if bound_cap is None else bound_cap,
                           jnp.int32)

    def run(self, state: SearchState, max_iters=None,
            bound_cap=None) -> SearchState:
        """Run until exhaustion or the cumulative per-worker iteration
        ceiling, growing pools and resuming on overflow. `bound_cap`
        (optional) is folded into the incumbent at loop entry — the
        cross-request incumbent-sharing input (None = I32_MAX = the
        identity fold)."""
        from . import checkpoint

        ceiling = (np.iinfo(np.int64).max if max_iters is None
                   else int(max_iters))
        while True:
            capacity = state.prmu.shape[-1]
            out = self._pin_empty(SearchState(*self._loop(capacity)(
                self.tables, jnp.asarray(ceiling, jnp.int64),
                self._cap(bound_cap), *state)))
            # the overflow read is the host's wait on the loop
            with tracelog.span("segment.wait"):
                overflow = bool(_fetch(out.overflow).any())
            if not overflow:
                return out
            grown = checkpoint.grow(fetch_state(out), capacity * 2)
            state = self.commit(grown)

    def run_async(self, state: SearchState, max_iters,
                  bound_cap=None) -> SearchState:
        """Dispatch ONE compiled-loop invocation and return its output
        futures WITHOUT blocking — the overlapped segment driver's
        dispatch hook. The pool leaves of `state` are DONATED (the
        while-loop carry aliases them; zero copies in flight), so the
        caller must not touch state.prmu/depth/aux afterwards; the
        scalar counter leaves stay fetchable. Overflow is NOT checked
        here — the overlapped driver reads the flag from its async
        counter fetch and recovers via grow_fn."""
        capacity = state.prmu.shape[-1]
        with _warnings.catch_warnings():
            _warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return self._pin_empty(SearchState(
                *self._loop(capacity, donate=True)(
                    self.tables, jnp.asarray(int(max_iters), jnp.int64),
                    self._cap(bound_cap), *state)))

    def seed(self, frontier: Frontier, capacity: int, jobs: int,
             init_best: int) -> SearchState:
        """Stripe a warm-up frontier across the workers, pre-growing the
        pool until a stripe fits under the usable-row limit."""
        n_dev = self.mesh.devices.size
        stripe = -(-max(len(frontier.depth), 1) // n_dev)
        while self.limit(capacity) < max(stripe, 1):
            capacity *= 2
        state = _shard_frontier(frontier, n_dev, capacity, jobs, init_best,
                                limit=self.limit(capacity))
        return self.commit(SearchState(*state))

    # -------------------------------------------------- AOT pre-warm

    def abstract_state(self, jobs: int, aux_rows: int, aux_dtype,
                       capacity: int) -> SearchState:
        """The loop's state signature as jax.ShapeDtypeStructs — the
        serializable lowering inputs the boot pre-warm compiles from
        (no pool allocation, no search). Shardings are pinned to the
        worker axis explicitly: abstract lowering would otherwise pick
        a replicated sharding for zero-sized leaves (the telemetry
        block when the flag is off) and the executable would then
        reject the real, axis-sharded calls."""
        from jax.sharding import NamedSharding
        n_dev = self.mesh.devices.size
        shard = NamedSharding(self.mesh, P(AX))

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                        sharding=shard)

        # honor the x64 config the same way the real zeros do
        i64 = jnp.zeros((), jnp.int64).dtype
        counters = {f: sds((n_dev,), i64)
                    for f in ("tree", "sol", "iters", "evals", "sent",
                              "recv", "steals")}
        return SearchState(
            prmu=sds((n_dev, jobs, capacity), jnp.int16),
            depth=sds((n_dev, capacity), jnp.int16),
            aux=sds((n_dev, aux_rows, capacity), aux_dtype),
            size=sds((n_dev,), jnp.int32),
            best=sds((n_dev,), jnp.int32),
            overflow=sds((n_dev,), jnp.bool_),
            telemetry=sds((n_dev, tele.enabled_width()), i64),
            **counters)

    def warm(self, capacity: int, jobs: int, aux_rows: int, aux_dtype,
             donate: bool = False, via: str = "prewarm") -> str:
        """Ready the compiled loop for `capacity` WITHOUT running a
        search: disk-deserialize when the AOT cache holds the key, else
        compile from abstract shapes (and persist). Returns the
        executor entry's warm verdict ("warm"/"disk"/"compile"/
        "skipped"); "skipped" when no executor cache is injected (a
        plain jit build has nothing to pre-ready) or the AOT path
        rejects the program. `via` labels the ledger record ("prewarm"
        boot warms, "ladder" rung pre-readies) — both are PLANNED
        compiles the health layer's compile_storm must not count."""
        entry = self._loop(capacity, donate=donate)
        warm_fn = getattr(entry, "warm", None)
        if warm_fn is None:
            return "skipped"
        from jax.sharding import NamedSharding
        repl = NamedSharding(self.mesh, P())
        abs_tables = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           jnp.asarray(x).dtype,
                                           sharding=repl),
            self.tables)
        max_iters = jax.ShapeDtypeStruct(
            (), jnp.zeros((), jnp.int64).dtype, sharding=repl)
        bound_cap = jax.ShapeDtypeStruct((), jnp.dtype(jnp.int32),
                                         sharding=repl)
        state = self.abstract_state(jobs, aux_rows, aux_dtype, capacity)
        return warm_fn(abs_tables, max_iters, bound_cap, *state, via=via)


def _resolve_problem(problem):
    """Registry-name-or-plugin-object -> plugin object (lazy import:
    the problems package imports engine modules from inside methods)."""
    if isinstance(problem, str):
        from .. import problems as problems_pkg
        return problems_pkg.get(problem)
    return problem


def _problem_driver(problem, mesh, tables, table, lb_kind: int,
                    chunk: int, balance_period: int, transfer_cap: int,
                    min_transfer: int, adt, loop_cache,
                    limit_fn=None, fused: str = "off") -> "_DistDriver":
    """ONE construction shared by the serving path (search) and the
    boot pre-warm (prewarm), for ANY registered problem: the loop key
    and every trace-specializing knob come from here, so a pre-warmed
    executable is key-identical to the one a real request at the same
    knobs builds — a warm that readied a different key would be pure
    waste. The key leads with the problem's registry name plus the pool
    width and the table's leading dimension — together they pin the
    instance-table SHAPE (the trace specialization; values are runtime
    arguments) for every registered problem, and PFSP keys keep their
    pre-plugin ``pfsp/jobs/machines/...`` layout (test-pinned; persisted
    AOT entries stay addressable), so two problems can never alias one
    executable. `limit_fn` overrides the usable-row bound (the
    chunk-ladder passes the unified across-rung limit; None = this
    chunk's own row_limit)."""
    jobs = problem.slots(table)
    if not getattr(problem, "supports_fused", False):
        # a problem whose make_step IGNORES the mode must not key two
        # program-identical executables apart (or invalidate its warm
        # AOT entries when the knob flips between boots)
        fused = "off"

    def make_local_step(t, limit):
        return problem.make_step(t, lb_kind, chunk, 1024, limit,
                                 fused=fused)

    # the fused mode joins the key only when ON, so every persisted
    # AOT/executor entry of the unfused route keeps its exact pre-fused
    # identity (the same suffix discipline as the megabatch batch dim)
    return _DistDriver(
        mesh, tables, make_local_step, balance_period, transfer_cap,
        min_transfer,
        limit_fn=limit_fn or (lambda cap: problem.usable_rows(cap, chunk,
                                                              jobs)),
        loop_cache=loop_cache,
        loop_key=(problem.name, jobs, int(np.asarray(table).shape[0]),
                  lb_kind, chunk, str(adt))
        + (("fused", fused) if fused != "off" else ()))


def _ladder_plan(problem, mesh, tables, table, lb_kind: int, chunk: int,
                 balance_period: int, transfer_cap: int | None,
                 min_transfer: int | None, adt, loop_cache,
                 rung_profile=None, fused_mode: str = "off"
                 ) -> tuple[tuple, dict]:
    """One _DistDriver per chunk-ladder rung (engine/ladder.rungs_for),
    all built against a UNIFIED usable-row limit: the minimum over
    rungs of each rung's own scratch-margin + balance-headroom bound.
    A state committed by ANY rung is then in-bounds for every other
    rung, so the controller may switch in either direction at a
    segment boundary without an out-of-bounds block write ever being
    possible (the clamp of a dynamic_update_slice would corrupt live
    rows silently — this invariant is what makes switching safe, see
    engine/ladder.py).

    `transfer_cap` / `min_transfer` are the CALLER's explicit values
    (applied to every rung when given — a cap sized for the tuned
    chunk over-reserves for the small rungs, which is safe); None
    derives each rung's own (the byte-budget rule / 2*chunk).

    Shared by search() and prewarm() so a boot-warmed rung executable
    is key-identical to the one a ladder search builds.

    `rung_profile` (tune/defaults Params.rung_modes — the tuner's
    per-rung probe results) replaces the STATIC per-bound rung floor
    with measured admission (ladder.rungs_from_profile: a rung joins
    only when its probed ms/iter beats the tuned chunk's — subsuming
    the PR-9 LB2>=256 constant for probed shapes) and selects each
    rung's kernel-vs-matmul pipeline (ladder.fused_for) under the
    `fused_mode` master switch."""
    from .ladder import (fused_for, min_rung_for, rungs_for,
                         rungs_from_profile)

    jobs, aux_rows = problem.slots(table), problem.aux_rows(table)
    n_dev = mesh.devices.size
    rungs = rungs_from_profile(chunk, rung_profile,
                               fused_mode=fused_mode)
    if rungs is None:
        rungs = rungs_for(chunk, min_chunk=min_rung_for(lb_kind))
    cfgs = []
    for c in rungs:
        tc = (transfer_cap if transfer_cap is not None
              else default_transfer_cap(c, jobs, aux_rows, n_dev,
                                        aux_itemsize=adt.itemsize))
        mt = min_transfer if min_transfer is not None else 2 * c
        cfgs.append((c, tc, mt))

    def unified_limit(cap: int) -> int:
        return min(min(problem.usable_rows(cap, c, jobs),
                       cap - n_dev * tc)
                   for c, tc, _ in cfgs)

    drivers = {
        c: _problem_driver(problem, mesh, tables, table, lb_kind, c,
                           balance_period, tc, mt, adt, loop_cache,
                           limit_fn=unified_limit,
                           fused=fused_for(c, rung_profile, fused_mode))
        for c, tc, mt in cfgs}
    return tuple(sorted(drivers)), drivers


def prewarm(p_times: np.ndarray, lb_kind: int = 1, chunk: int = 64,
            capacity: int | None = None, balance_period: int = 4,
            min_seed: int = 32, n_devices: int | None = None,
            mesh=None, transfer_cap: int | None = None,
            min_transfer: int | None = None, loop_cache=None,
            donate: bool = False, ladder: bool | None = None,
            problem="pfsp", rung_profile=None) -> str:
    """Ready the distributed loop's executable for this shape WITHOUT
    running a search — the serve-boot pre-warm entry (cli `serve
    --prewarm` / SearchServer.prewarm_boot drive it per submesh and
    shape family). Only the SHAPE and dtypes of `p_times` matter (the
    tables are runtime arguments of the compiled loop): a synthetic
    table in the Taillard value range warms the executable every real
    instance of the class will reuse.

    Returns the warm verdict: "disk" (deserialized from the AOT cache,
    zero compiles), "compile" (fresh compile, persisted when an AOT
    cache rides the executor cache), "warm" (already ready —
    idempotent), or "skipped" (no executor cache / AOT path rejected /
    multi-controller).

    `ladder` (None = the TTS_LADDER env flag): when the chunk ladder is
    on, every rung's executable is warmed — key-identically to what a
    ladder search builds (_ladder_plan is shared) — so a served
    request's mid-search rung switch never stalls on a compile. The
    returned verdict is the tuned (top) rung's."""
    from ..utils import config as _cfg

    if jax.process_count() > 1:
        return "skipped"   # multi-controller warm needs rank
        # coordination (the pod-scale arc, ROADMAP item 1)
    if mesh is None:
        mesh = worker_mesh(n_devices)
    prob = _resolve_problem(problem)
    table = np.asarray(p_times)
    jobs, aux_rows = prob.slots(table), prob.aux_rows(table)
    if capacity is None:
        capacity = prob.default_capacity(table)
    tables = prob.make_tables(table)
    adt = prob.aux_dtype(table)
    if ladder is None:
        ladder = _cfg.env_flag(_cfg.LADDER_FLAG)
    # the fused-route mode joins the executable key (_problem_driver),
    # so the warm must resolve it exactly as a real request would —
    # warming the unfused key under TTS_FUSED=1 would be pure waste.
    # `rung_profile` (the tuned entry's rung_modes mask, when the
    # caller resolved one) must ride along for the same reason: a
    # profile changes both the rung SET (rungs_from_profile) and each
    # rung's fused suffix (fused_for), so warming without it would
    # build keys a tuned dispatch never asks for.
    fused_mode = pallas_fused.resolve_mode(None)
    drivers = None
    if ladder:
        rungs, drivers = _ladder_plan(
            prob, mesh, tables, table, lb_kind, chunk, balance_period,
            transfer_cap, min_transfer, adt, loop_cache,
            rung_profile=rung_profile, fused_mode=fused_mode)
        if len(rungs) < 2:
            drivers = None             # single rung: plain path
    if drivers is not None:
        driver = drivers[max(drivers)]
    else:
        from .ladder import fused_for
        if transfer_cap is None:
            transfer_cap = default_transfer_cap(
                chunk, jobs, aux_rows, mesh.devices.size,
                aux_itemsize=adt.itemsize)
        min_transfer = min_transfer or 2 * chunk
        driver = _problem_driver(prob, mesh, tables, table, lb_kind,
                                 chunk, balance_period, transfer_cap,
                                 min_transfer, adt, loop_cache,
                                 fused=fused_for(chunk, rung_profile,
                                                 fused_mode))
    # mirror seed()'s capacity pre-grow rule with the warm-up target as
    # the stripe estimate: at production capacities the loop never
    # fires (limit >> min_seed); at toy capacities it keeps the warmed
    # key aligned with what a fresh request would actually build
    while driver.limit(capacity) < max(min_seed, 1):
        capacity *= 2
    with tracelog.span("executor.prewarm", problem=prob.name, jobs=jobs,
                       machines=aux_rows, lb_kind=lb_kind, chunk=chunk,
                       capacity=capacity, donate=donate,
                       ladder=bool(drivers)) as sp:
        how = driver.warm(capacity, jobs, aux_rows, adt, donate=donate)
        if drivers is not None:
            for c, d in drivers.items():
                if d is not driver:
                    d.warm(capacity, jobs, aux_rows, adt,
                           donate=donate, via="ladder")
        sp.set(how=how)
    return how


def search(p_times: np.ndarray, lb_kind: int = 1, init_ub: int | None = None,
           n_devices: int | None = None, chunk: int | None = 64,
           capacity: int = 1 << 17, balance_period: int | None = 4,
           transfer_cap: int | None = None, min_transfer: int | None = None,
           min_seed: int = 32, max_rounds: int | None = None,
           tables: BoundTables | None = None, mesh=None,
           segment_iters: int | None = None,
           checkpoint_path: str | None = None,
           checkpoint_every: int = 1,
           heartbeat=None, host_fraction: int = 0,
           host_threads: int = 0,
           stop_event=None, should_stop=None,
           loop_cache=None, checkpoint_meta_extra=None,
           overlap: bool | None = None,
           incumbent_board=None, incumbent_key=None,
           ladder: bool | None = None, tuner=None,
           problem="pfsp") -> DistResult:
    """Distributed B&B over all available devices (the flagship engine;
    capability parity with pfsp_dist_multigpu_cuda.c's pfsp_search).

    `balance_period=4` was chosen on chip in round 4
    (tools/bench_balance_period.py found the cond-gated balance round's
    cost flat across periods; not measured on chip this round), so the
    period is chosen for SPREAD of per-worker trees. The CPU mesh's
    wall-clock preference for sparse periods is an artifact of
    host-serialized collectives; do not retune this knob on the
    virtual mesh.

    With `segment_iters`/`checkpoint_path` the loop runs in bounded
    segments with heartbeat + checkpoint/resume between them — the
    distributed durability layer the reference lacks entirely (its only
    stall tooling is a 10-second "Still Idle" print, dist:663-668). A
    checkpoint written here re-loads with its warm-up counters, so a
    resumed run's totals match an uninterrupted one exactly.

    `host_fraction > 0` runs the `-C` heterogeneous host tier BESIDE the
    device mesh (the reference's CPU workers inside the distributed
    flagship, dist:471-741): a native async session seeded with every
    host_fraction-th warm-up node (on resume: rows carved off the top of
    the checkpointed pools), incumbents merged both ways at every
    segment boundary — a host tier forces segmented execution so the
    exchange points exist.

    Resume is ELASTIC: a checkpoint written by an N-worker mesh loads
    on whatever mesh is available — the pools are resharded
    (checkpoint.reshard_state: concatenate + water-fill) when worker
    counts differ, so a preempted job restarts on a smaller or larger
    slice with no explored node lost. A torn/corrupt current snapshot
    rolls back to its rotating last-good sibling
    (checkpoint.load_resilient) instead of poisoning the run.

    Service hooks (service/server.py drives these): `stop_event` (any
    object with is_set()) and/or `should_stop(SegmentReport)` force
    segmented execution and stop the search cleanly at the next segment
    boundary — with a `checkpoint_path` the final state is saved first,
    so a preempted request later RESUMES (possibly on a different-sized
    submesh via the elastic reshard) instead of restarting.
    `loop_cache` (get_or_build(key, build)) shares the compiled SPMD
    loop across searches with identical specialization — the
    serve-many-compile-once path (service/executors.ExecutorCache).
    `checkpoint_meta_extra` (dict or callable returning one) is merged
    into every checkpoint's meta — the service rides its cumulative
    spent_s clock on it so compute budgets survive preempt/resume
    across server lifetimes.

    `overlap` (None = the TTS_OVERLAP env flag) pipelines segmented
    execution: the next segment is dispatched — donated pool carries —
    before the previous segment's counters are fetched, and checkpoint
    serialization moves to a writer thread, so the device never idles
    on the host between segments (checkpoint.run_segmented's overlap
    contract; bit-identical node accounting on or off). Forced off
    beside a `-C` host tier (its per-segment incumbent merge needs the
    synchronous boundary) and under multi-controller JAX.

    `incumbent_board` / `incumbent_key` (service-provided; see
    engine/incumbent.py) joins this search to the cross-request
    best-bound exchange: every segment boundary publishes the current
    best and folds the board's global best in as the next segment's
    pruning ceiling — a traced input, never a retrace, monotone-only
    by construction (and audited). `incumbent_key` defaults to the
    instance's content hash.

    `chunk=None` / `balance_period=None` defers the knob to ADAPTIVE
    resolution: a persisted tuned entry when a `tuner`
    (tune/tuner.Autotuner) is supplied, else the measured-defaults
    table (tune/defaults.py) — never a probe on this path (the tuner's
    request-time tier is cache-or-defaults; probing happens at
    boot/bench time).

    `ladder` (None = the TTS_LADDER env flag; default off) enables
    CHUNK-LADDER execution on the segmented path: 2-3 pre-built chunk
    rungs (engine/ladder.rungs_for — each its own ExecutorCache/AOT
    entry, no retrace at runtime) with the rung switched only at
    segment boundaries, driven by the per-segment pool-occupancy
    signal, so ramp-up and drain run small-chunk steps instead of
    underfilled tuned-chunk ones. Off is bit-identical to the
    pre-ladder driver (the flag never reaches this path); on, a
    fixed-incumbent run explores the identical node set and every
    audit invariant holds across switches (tests pin TTS_AUDIT_HARD).
    The live rung rides checkpoint meta (``ladder_rung``) so resume
    replays on the recorded rung. Ladder yields to a `-C` host tier
    and to multi-controller meshes (like overlap), and engages only
    when segmented execution runs — it switches at segment
    boundaries, and a one-shot exhaustion run has none. A rung's loop
    grown past its pre-warmed capacity (overflow recovery) recompiles
    lazily on its next use, booked as a normal unplanned compile.

    `problem` (registry name or plugin object, default "pfsp") selects
    the workload: `p_times` is then the problem's 2-D instance table
    (problems/base.py documents the per-problem format), the plugin
    supplies the step pipeline / warm-up / aux seeding, and every
    executable/tuning/checkpoint key carries the problem name. A
    checkpoint records its problem and a cross-problem resume is
    REFUSED — a pool of TSP tours re-homed under a PFSP step would be
    silent garbage. The `-C` host tier follows plugin opt-in
    (supports_host_tier): PFSP gets the native runtime, TSP/knapsack
    the generic host_children session (hybrid.PyHostSession);
    host_fraction > 0 for a problem without one raises the typed
    problems/base.HostTierUnsupported."""
    from ..utils import config as _cfg
    from . import checkpoint, hybrid, incumbent as inc_mod

    # the host's part before the first dispatch, one span: tuner
    # resolve, tables, driver and loop-cache lookup, state build and
    # shard (the segments that follow are spanned in run_segmented)
    with tracelog.span("request.prepare", lb_kind=lb_kind):
        prob = _resolve_problem(problem)
        table = np.asarray(p_times)
        if mesh is None:
            mesh = worker_mesh(n_devices)
        n_dev = mesh.devices.size
        jobs = prob.slots(table)
        if host_fraction > 0 and not prob.supports_host_tier:
            from ..problems.base import HostTierUnsupported
            raise HostTierUnsupported(prob.name)
        rung_profile = None
        fused_mode = pallas_fused.resolve_mode(None)
        if chunk is None or balance_period is None:
            # adaptive-dispatch resolution for the knobs the caller left
            # open: tuned cache entry (zero probes — the hot path must
            # never probe) else the measured-defaults table
            from ..tune import defaults as tune_defaults
            if tuner is not None:
                params = tuner.resolve(jobs, table.shape[0], lb_kind,
                                       n_workers=n_dev, allow_probe=False,
                                       problem=prob.name)
            else:
                params = tune_defaults.params_for("serving", jobs,
                                                  table.shape[0],
                                                  problem=prob.name)
            if chunk is None:
                chunk = params.chunk
                if transfer_cap is None and params.transfer_cap:
                    transfer_cap = params.transfer_cap
            if balance_period is None:
                balance_period = params.balance_period
            # the tuner's per-rung kernel-vs-matmul profitability mask
            # (Params.rung_modes) rides into rung construction below
            rung_profile = params.rung_modes
            tracelog.event("tuner.resolve", chunk=chunk,
                           balance_period=balance_period,
                           source=params.source,
                           evals_per_s=params.evals_per_s,
                           fused=fused_mode,
                           rung_profile=bool(rung_profile))
        if tables is None:
            tables = prob.make_tables(table)
        adt = prob.aux_dtype(table)
        resumed = None
        if checkpoint_path and checkpoint.resume_path(checkpoint_path):
            # load BEFORE sizing the balance buffers: resume keeps the
            # SAVED pools' aux dtype (an old int32-aux checkpoint stays
            # int32, and a pre-aux legacy file is RECONSTRUCTED as int32 by
            # checkpoint.load), so the byte budget must be priced off the
            # loaded state, not the fresh-run dtype
            resumed = checkpoint.load_resilient(
                checkpoint_path,
                p_times=table if prob.name == "pfsp" else None)[:2]
            # a snapshot records its problem (pre-stamp legacy snapshots
            # are all PFSP); a cross-problem resume is refused — the pool
            # rows only mean anything under the problem that wrote them
            saved_prob = resumed[1].get("problem")
            saved_prob = ("pfsp" if saved_prob is None
                          else str(np.asarray(saved_prob)))
            if saved_prob != prob.name:
                raise ValueError(
                    f"checkpoint {checkpoint_path} was written by problem "
                    f"{saved_prob!r}; refusing to resume it as "
                    f"{prob.name!r} (pick a fresh tag/checkpoint path)")
            adt = np.asarray(resumed[0].aux).dtype
        if ladder is None:
            ladder = _cfg.env_flag(_cfg.LADDER_FLAG)
        # the ladder switches at segment boundaries, so it engages only
        # when segmented execution will run; a host tier keeps the single
        # driver (its per-segment merge is enough moving parts) and
        # multi-controller stays on the one-loop path, like overlap
        use_ladder = (bool(ladder)
                      and (segment_iters is not None
                           or checkpoint_path is not None
                           or stop_event is not None
                           or should_stop is not None)
                      and host_fraction == 0
                      and jax.process_count() == 1)
        ladder_drivers = None
        if use_ladder:
            # rung drivers get the caller's EXPLICIT transfer knobs (None
            # derives per rung) and one unified limit — see _ladder_plan
            rungs, ladder_drivers = _ladder_plan(
                prob, mesh, tables, table, lb_kind, chunk, balance_period,
                transfer_cap, min_transfer, adt, loop_cache,
                rung_profile=rung_profile, fused_mode=fused_mode)
            if len(rungs) < 2:
                ladder_drivers = None      # chunk too small to ladder:
                #                            plain single-driver path
        if transfer_cap is None:
            transfer_cap = default_transfer_cap(chunk, jobs,
                                                prob.aux_rows(table),
                                                mesh.devices.size,
                                                aux_itemsize=adt.itemsize)
        min_transfer = min_transfer or 2 * chunk

        if ladder_drivers is not None:
            driver = ladder_drivers[chunk]   # the tuned top rung — also
            #   the seed/resume/commit driver (all rungs share its limit)
        else:
            from .ladder import fused_for
            driver = _problem_driver(prob, mesh, tables, table, lb_kind,
                                     chunk, balance_period, transfer_cap,
                                     min_transfer, adt, loop_cache,
                                     fused=fused_for(chunk, rung_profile,
                                                     fused_mode))

        session = None
        meta_rung = None          # the checkpoint's recorded ladder rung
        h_prmu = np.zeros((0, jobs), np.int16)
        h_depth = np.zeros(0, np.int16)
        if resumed is not None:
            host_state, meta = resumed
            if "ladder_rung" in meta:
                # resume replays on the rung the checkpoint recorded: the
                # pool snapshot alone would misread a mid-ramp save
                meta_rung = int(np.asarray(meta["ladder_rung"]))
            shape = np.asarray(host_state.prmu).shape
            if len(shape) != 3 or shape[0] != n_dev:
                # elastic resume: re-split the snapshot's pools across THIS
                # mesh (preemption rarely hands back the same topology)
                old_workers = shape[0] if len(shape) == 3 else 1
                import warnings
                warnings.warn(
                    f"resharding checkpoint {checkpoint_path} from "
                    f"{old_workers} to {n_dev} workers (elastic resume)",
                    RuntimeWarning, stacklevel=2)
                # audit hook: the elastic reshard must conserve every
                # summed counter, the pooled node count and the incumbent
                # (obs/audit — a drift here is silent wrong answers later)
                pre_sums = (obs_audit.state_sums(host_state)
                            if obs_audit.enabled() else None)
                host_state = checkpoint.reshard_state(host_state, n_dev)
                if pre_sums is not None:
                    obs_audit.check_reshard(pre_sums, host_state,
                                            edge="elastic_resume")
            # re-home into a capacity whose usable-row limit (scratch margin
            # + balance headroom) covers the fullest resharded pool
            cap0 = cap = host_state.prmu.shape[-1]
            need = int(np.asarray(host_state.size).max())
            while driver.limit(cap) < max(need, 1):
                cap *= 2
            if cap != cap0:
                host_state = checkpoint.grow(host_state, cap)
            # a checkpoint written by a -C run carries the host tier's seed
            # nodes (they were carved OUT of the pools): resume must either
            # re-seed the session from them or push them back — dropping
            # them would silently lose subtrees
            saved_p = np.asarray(meta.get("host_prmu",
                                          np.zeros((0, jobs))), np.int16)
            saved_d = np.asarray(meta.get("host_depth", np.zeros(0)),
                                 np.int16)
            if host_fraction > 0:
                if len(saved_d):
                    h_prmu, h_depth = saved_p, saved_d
                else:
                    host_state, h_prmu, h_depth = hybrid.pop_host_share(
                        host_state, host_fraction)
                if len(h_depth):
                    session = hybrid.make_session(
                        prob, table, h_prmu, h_depth, lb_kind,
                        int(np.asarray(host_state.best).min()),
                        n_threads=host_threads)
            elif len(saved_d):
                host_state = hybrid.restore_host_share(
                    host_state, saved_p, saved_d, table, problem=prob)
            fr = Frontier(prmu=np.zeros((0, jobs), np.int16),
                          depth=np.zeros(0, np.int16),
                          tree=int(meta.get("warmup_tree", 0)),
                          sol=int(meta.get("warmup_sol", 0)),
                          best=int(np.asarray(host_state.best).min()))
            state = driver.commit(host_state)
        else:
            with tracelog.span("bfs_warmup", problem=prob.name,
                               target=min_seed * n_dev) as ws:
                fr = prob.warmup(table, lb_kind, init_ub,
                                 target=min_seed * n_dev)
                ws.set(frontier=len(fr.depth), tree=fr.tree)
            init_best = (fr.best if init_ub is None
                         else min(fr.best, int(init_ub)))
            dmask, h_prmu, h_depth = hybrid.split_host_share(
                fr.prmu, fr.depth, host_fraction)
            if len(h_depth):
                session = hybrid.make_session(prob, table, h_prmu, h_depth,
                                              lb_kind, init_best,
                                              n_threads=host_threads)
                fr.prmu, fr.depth = fr.prmu[dmask], fr.depth[dmask]
            fr.aux = prob.seed_aux(table, fr.prmu, fr.depth)
            state = driver.seed(fr, capacity, jobs, init_best)

        if overlap is None:
            overlap = _cfg.env_flag(_cfg.OVERLAP_FLAG)
        # the host tier's per-segment incumbent merge (post_segment) needs
        # the synchronous boundary; overlap yields to it. Multi-controller
        # must also stay sync HERE, not only in run_segmented's own guard:
        # the choice of run_fn below follows use_overlap, and handing the
        # sync driver the donating non-growing run_async would turn every
        # overflow into a hard PoolOverflow instead of a lossless grow.
        use_overlap = (bool(overlap) and session is None
                       and jax.process_count() == 1)

        ladder_ctl = None
        if ladder_drivers is not None:
            from .ladder import RungController
            ladder_ctl = RungController(ladder_drivers, n_dev)
            ladder_ctl.start(int(np.atleast_1d(_fetch(state.size)).sum()),
                             meta_rung=meta_rung)
            # Pre-ready EVERY rung — the current one included — from
            # abstract shapes, so a mid-search switch never stalls on a
            # fresh trace+compile and all rung compiles are booked as
            # PLANNED (via="ladder": the compile_storm rule must not read
            # a ladder boot as executable-reuse breaking). Warming all
            # rungs is also a CORRECTNESS requirement on the AOT path, not
            # just a latency one: abstract warms pin every input/output to
            # the explicit worker-axis sharding (_DistDriver.
            # abstract_state), so any rung's output state feeds any other
            # rung's strict AOT executable; an entry compiled from REAL
            # first-call args instead infers a replicated sharding for the
            # zero-width telemetry leaf and then REJECTS the cross-rung
            # handoff ("input sharding does not match") — a booked jit
            # fallback, correct but a silent perf and accounting loss.
            cap_now = int(state.prmu.shape[-1])
            for c, d in ladder_drivers.items():
                d.warm(cap_now, jobs, prob.aux_rows(table), adt,
                       donate=use_overlap, via="ladder")

        client = None
        if incumbent_board is not None:
            client = inc_mod.BoardClient(
                incumbent_board,
                incumbent_key or inc_mod.share_key(table,
                                                   problem=prob.name))
            # seed the exchange with this search's starting incumbent (a
            # resumed checkpoint's best, or the warm-up/init_ub bound) so
            # same-instance peers tighten before our first segment lands
            client.publish(int(np.atleast_1d(_fetch(state.best)).min()))

        max_iters = (None if max_rounds is None
                     else max_rounds * balance_period)
        stop_fn = None
        if stop_event is not None or should_stop is not None:
            def stop_fn(rep):
                return ((stop_event is not None and stop_event.is_set())
                        or (should_stop is not None and should_stop(rep)))
        one_shot = (segment_iters is None and checkpoint_path is None
                    and session is None and stop_fn is None)
        if not one_shot:
            ckpt_meta = {"warmup_tree": fr.tree, "warmup_sol": fr.sol,
                         # the snapshot's problem stamp: resume refuses a
                         # cross-problem re-home (checked above)
                         "problem": prob.name,
                         # the host tier's seed rides every checkpoint so a
                         # killed -C run can be resumed without losing the
                         # carved subtrees (re-exploring the share from its
                         # seed is exactly-once: the killed session's work
                         # was never committed anywhere)
                         "host_prmu": (h_prmu if session else
                                       np.zeros((0, jobs), np.int16)),
                         "host_depth": (h_depth if session else
                                        np.zeros(0, np.int16))}
            if checkpoint_meta_extra is not None:
                base_meta = ckpt_meta

                def ckpt_meta():
                    extra = (checkpoint_meta_extra()
                             if callable(checkpoint_meta_extra)
                             else checkpoint_meta_extra)
                    return {**base_meta, **extra}

            if ladder_ctl is not None:
                # the rung for the NEXT segment was chosen at the last
                # boundary (hb's observe below); every rung driver shares
                # the unified limit, so switching never invalidates the
                # carried state
                base_meta0 = ckpt_meta

                def ckpt_meta():
                    base = (base_meta0() if callable(base_meta0)
                            else dict(base_meta0))
                    return {**base, "ladder_rung": ladder_ctl.current_chunk}

            grow_fn = stop_pending = None
            if use_overlap:
                # async dispatch with donated pool carries; overflow
                # recovery and exit draining live in the overlapped driver
                def run_fn(s, target):
                    drv = (ladder_ctl.driver() if ladder_ctl is not None
                           else driver)
                    return drv.run_async(
                        s, target, bound_cap=client.cap() if client else None)

                def grow_fn(s):
                    return driver.commit(checkpoint.grow(
                        fetch_state(s), s.prmu.shape[-1] * 2))

                if stop_event is not None:
                    stop_pending = stop_event.is_set
            else:
                def run_fn(s, target):
                    drv = (ladder_ctl.driver() if ladder_ctl is not None
                           else driver)
                    return drv.run(
                        s, max_iters=target,
                        bound_cap=client.cap() if client else None)

            def hb(rep):
                if ladder_ctl is not None:
                    # rung selection for the NEXT dispatch: this boundary's
                    # pool-occupancy signal (under overlap the next segment
                    # is already in flight, so the switch lands one
                    # boundary later — accounting is exact either way)
                    ladder_ctl.observe(rep.pool_size, segment=rep.segment)
                # resource-observability heartbeat hook: one device-memory
                # / host-RSS sweep per segment (obs/resource publishes the
                # tts_device_bytes_* gauges and a resource.sample trace
                # event, which Perfetto renders as memory lanes beside the
                # pool/steal counter lanes). Observation-only — a failed
                # sweep must never stop the search.
                try:
                    from ..obs import resource as obs_resource
                    obs_resource.sample_now()
                except Exception:  # noqa: BLE001
                    pass
                if client is not None:
                    # the cross-request exchange's publish half: fold this
                    # submesh's freshest best into the board every segment
                    client.publish(rep.best)
                if heartbeat is not None:
                    heartbeat(rep)

    if one_shot:
        # the segmented path below is spanned per segment inside
        # run_segmented; this is the only otherwise-unobserved run shape
        with tracelog.span("engine.run", workers=n_dev):
            out = driver.run(state, max_iters,
                             bound_cap=client.cap() if client else None)
    else:
        out = checkpoint.run_segmented(
            run_fn, state, segment_iters=segment_iters or 2048,
            checkpoint_path=checkpoint_path, heartbeat=hb,
            checkpoint_every=checkpoint_every,
            max_total_iters=max_iters, checkpoint_meta=ckpt_meta,
            post_segment=(session.post_segment if session else None),
            should_stop=stop_fn, overlap=use_overlap, grow_fn=grow_fn,
            stop_pending=stop_pending)

    h_tree = h_sol = h_expanded = 0
    host_stats = {}
    best = int(_fetch(out.best).min())
    if client is not None:
        client.publish(best)   # the final fold: peers prune against it
    if session is not None:
        session.offer(best)      # freshest device bound before the join
        h_tree, h_sol, h_best, h_expanded = session.join()
        best = min(best, h_best)
        host_stats = {
            "host_tree": [h_tree], "host_sol": [h_sol],
            "host_expanded": [h_expanded],
            "exchanges": [session.exchanges],
            "host_improved": [session.host_improved],
            "dev_improved": [session.dev_improved],
        }

    tree_dev = _fetch(out.tree)
    sol_dev = _fetch(out.sol)
    sizes = _fetch(out.size)
    iters_dev = _fetch(out.iters)
    steals_dev = _fetch(out.steals)
    tracelog.event(
        "engine.complete", workers=n_dev,
        tree=int(tree_dev.sum()) + fr.tree + h_tree, best=best,
        iters=int(iters_dev.max()),
        balance_rounds=int(iters_dev.max()) // max(balance_period, 1),
        steals=int(steals_dev.sum()),
        complete=int(sizes.sum()) == 0)
    telemetry = None
    if out.telemetry.shape[-1] > 0:
        telemetry = tele.summarize(_fetch(out.telemetry))
    res = DistResult(
        explored_tree=int(tree_dev.sum()) + fr.tree + h_tree,
        explored_sol=int(sol_dev.sum()) + fr.sol + h_sol,
        best=best,
        telemetry=telemetry,
        per_device={
            "tree": tree_dev, "sol": sol_dev,
            "iters": iters_dev,
            "evals": _fetch(out.evals),
            "sent": _fetch(out.sent),
            "recv": _fetch(out.recv),
            "steals": steals_dev,
            "final_size": sizes,
            **host_stats,
        },
        warmup_tree=fr.tree, warmup_sol=fr.sol,
        complete=int(sizes.sum()) == 0,
        problem=prob.name,
    )
    if obs_audit.enabled():
        # node-conservation audit on every result (host-side sums over
        # already-fetched counters — microseconds against a search);
        # failures surface as audit.fail events, tts_audit_failures
        # counters and the health layer's `audit` alert (or raise
        # under TTS_AUDIT_HARD=1)
        obs_audit.check_result(res)
    return res
