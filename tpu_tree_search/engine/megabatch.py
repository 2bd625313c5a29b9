"""Request megabatching: the compiled SPMD search loop vmapped over a
leading instance axis.

The reference engine's throughput move is bulk offload — amortize one
kernel launch over a chunk of nodes (`evaluate_gpu`, PAPER.md L3). This
module is the serving analog applied ACROSS requests instead of within
one: B same-shape-class instances are stacked into ONE compiled loop, so
one dispatch bounds children for hundreds of tenants and a traffic mix
dominated by small instances stops stranding the mesh (one request per
submesh regardless of size — ROADMAP item 3).

Layout: every `SearchState` leaf gains a batch dim right after the
worker axis — pools `(D, B, J, capacity)`, depth `(D, B, capacity)`,
counters/best/size `(D, B)`, telemetry `(D, B, WIDTH)` — sharded over
the worker axis exactly like the solo loop. Inside the shard_map the
per-worker leaves are `(B, ...)` and the loop body is
`jax.vmap(member_body)`: the SAME macro-iteration the solo loop runs
(`engine/distributed.member_body` — balance_period local steps, the
pmin incumbent exchange, one balance round), so a batched member's
explored tree is BIT-IDENTICAL to its solo run (test-pinned).

Per-instance semantics the batch preserves exactly:

- **termination masks**: the outer `lax.while_loop` carries every
  member; a member whose global pool drains (or that hits its own
  iteration target, or overflows) fails its per-member `active` mask
  and its lanes FREEZE — `jnp.where(mask, new, old)` keeps its state
  bit-stable while the rest of the batch keeps exploring. The loop
  exits when no member is active.
- **per-instance `bound_cap`**: a `(B,)` traced input folded into each
  member's incumbent at loop entry (`min(best, bound_cap[b])` — the
  IncumbentBoard's cross-request exchange, per member, no retrace).
- **per-instance budgets**: `max_iters` is a `(B,)` traced cumulative
  ceiling, so the segmented driver freezes a stopped member (its target
  stops advancing) without recompiling or stalling its batchmates.
- **exact accounting**: counters, telemetry blocks and the
  node-conservation audit are all per member (sliced off the batch
  axis); checkpoints are written per request by slicing the batch state
  down to the solo `(D, ...)` layout, so preempt/resume, crash replay
  and elastic reshard run through the UNMODIFIED checkpoint machinery
  — a batched member's snapshot is indistinguishable from a solo one.

What batching deliberately does NOT change: pool capacity is shared
(one compiled shape), so an overflowing member grows the whole batch;
execution is lockstep, so a batch's wall clock is its slowest member
(the batch-former keys on problem + shape class + lb to keep members
comparable); the overlap/donation pipeline and the `-C` host tier stay
solo-mode features.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs import audit as obs_audit
from ..obs import tracelog
from ..parallel.mesh import WORKER_AXIS, shard_map
from . import distributed as dist
from . import telemetry as tele
from .device import I32_MAX, SearchState
from .distributed import DistResult

AX = WORKER_AXIS


class MemberIncompatible(ValueError):
    """One member's RESUME STATE cannot join this batch (cross-problem
    checkpoint, legacy aux dtype, different telemetry width) — the
    batch key groups by request attributes and cannot see checkpoint
    contents. Typed, with the offending member index, so the service
    can demote THAT member to a solo dispatch and requeue its innocent
    batchmates instead of dead-lettering all of them on a batch-wide
    exception."""

    def __init__(self, member: int, reason: str):
        super().__init__(reason)
        self.member = member


# --------------------------------------------------------------- stacking


def stack_states(states: list, capacity: int | None = None
                 ) -> SearchState:
    """Stack B solo host states (leaves `(D, ...)`) into one batched
    state (leaves `(D, B, ...)`) at `capacity` pool rows (default: the
    widest member). Members at a smaller capacity are zero-padded on
    the row axis — exactly `checkpoint.grow`'s rule (rows above the
    cursor are garbage by the pool invariant) without materializing a
    grown copy per member: the batched leaves are allocated ONCE and
    each member writes its slice, so a B-member stack moves ~one batch
    of bytes instead of three (member grow + stack + commit)."""
    _POOL_LEAVES = ("prmu", "depth", "aux")
    D = np.asarray(states[0].prmu).shape[0]
    B = len(states)
    if capacity is None:
        capacity = max(np.asarray(s.prmu).shape[-1] for s in states)
    out = {}
    for name in SearchState._fields:
        leaves = [np.asarray(getattr(s, name)) for s in states]
        shape = list(leaves[0].shape)
        if name in _POOL_LEAVES:
            shape[-1] = int(capacity)
        arr = np.zeros([D, B] + shape[1:], leaves[0].dtype)
        for b, leaf in enumerate(leaves):
            if name in _POOL_LEAVES:
                arr[:, b, ..., :leaf.shape[-1]] = leaf
            else:
                arr[:, b] = leaf
        out[name] = arr
    return SearchState(**out)


def slice_member(state: SearchState, b: int) -> SearchState:
    """One member's solo-shaped view `(D, ...)` of a batched state —
    the per-request checkpoint/result extraction."""
    return SearchState(*(x[:, b] for x in state))


# ------------------------------------------------------------ the loop


def build_batched_loop(mesh, tables, make_local_step,
                       balance_period: int, transfer_cap: int,
                       min_transfer: int, limit: int, batch: int):
    """Compile the batched SPMD loop: signature
    `run(tables, max_iters, bound_cap, *state)` like the solo loop
    (engine/distributed.build_dist_loop) except `max_iters` and
    `bound_cap` are `(B,)` per-member vectors and every problem-table
    leaf and state leaf carries the batch dim. The member body is the
    SOLO body (distributed.member_body) under `jax.vmap` — shared code,
    not a reimplementation — with per-member activity masks supplying
    the batched termination semantics."""

    def worker_loop(tables, max_iters, bound_cap, *state_leaves):
        s = dist._local_state(*state_leaves)       # leaves (B, ...)
        # the per-member incumbent fold at loop entry, exactly where
        # the solo loop folds its scalar cap
        s = s._replace(best=jnp.minimum(s.best, bound_cap))

        def member(tables_b, *leaves):
            m = SearchState(*leaves)
            body = dist.member_body(tables_b, make_local_step,
                                    balance_period, transfer_cap,
                                    min_transfer, limit)
            return tuple(body(m))

        vbody = jax.vmap(member)

        def active(st: SearchState):
            # per-member (B,) activity: global work remains, no worker
            # of the member overflowed, own iteration target not hit —
            # the solo cond, vectorized over the batch
            has_work = jax.lax.psum(st.size, AX) > 0
            ok = jax.lax.psum(st.overflow.astype(jnp.int32), AX) == 0
            return has_work & ok & (st.iters < max_iters)

        def cond(st: SearchState):
            return active(st).any()

        def body(st: SearchState):
            mask = active(st)
            new = SearchState(*vbody(tables, *st))
            sel = lambda n, o: jnp.where(  # noqa: E731
                mask.reshape((batch,) + (1,) * (n.ndim - 1)), n, o)
            return SearchState(*(sel(n, o) for n, o in zip(new, st)))

        return dist._expand(jax.lax.while_loop(cond, body, s))

    spec_state = tuple(P(AX) for _ in SearchState._fields)
    spec_tables = jax.tree.map(lambda _: P(), tables)
    return jax.jit(shard_map(
        worker_loop, mesh,
        in_specs=(spec_tables, P(), P()) + spec_state,
        out_specs=spec_state))


class BatchedDriver:
    """Compiles/caches the batched loop per pool capacity (the solo
    `_DistDriver` shape, minus the donation/overlap tier). The executor
    key is the SOLO key plus a `("batch", B)` suffix, so the AOT disk
    tier persists/replays one batched compile fleet-wide and a batched
    executable can never alias a solo one."""

    def __init__(self, mesh, tables, make_local_step, balance_period: int,
                 transfer_cap: int, min_transfer: int, limit_fn,
                 batch: int, loop_cache=None, loop_key: tuple = ()):
        self.mesh = mesh
        self.tables = tables
        self.make_local_step = make_local_step
        self.balance_period = balance_period
        self.transfer_cap = transfer_cap
        self.min_transfer = min_transfer
        self.limit_fn = limit_fn
        self.batch = batch
        self.n_recv = mesh.devices.size * transfer_cap
        self._loops: dict[int, object] = {}
        self.spec_state = tuple(P(AX) for _ in SearchState._fields)
        self.loop_cache = loop_cache
        self.loop_key = tuple(loop_key) + ("batch", int(batch)) + tuple(
            int(d.id) for d in mesh.devices.flat)

    def limit(self, capacity: int) -> int:
        # the SAME tightened usable-row bound as the solo driver at
        # identical knobs — required for bit-parity (the balance
        # round's overflow predicate reads it)
        return min(self.limit_fn(capacity), capacity - self.n_recv)

    def _loop(self, capacity: int):
        if capacity not in self._loops:
            build = lambda: build_batched_loop(  # noqa: E731
                self.mesh, self.tables, self.make_local_step,
                self.balance_period, self.transfer_cap,
                self.min_transfer, limit=self.limit(capacity),
                batch=self.batch)
            if self.loop_cache is not None:
                key = self.loop_key + (capacity, self.balance_period,
                                       self.transfer_cap,
                                       self.min_transfer,
                                       self.limit(capacity))
                self._loops[capacity] = self.loop_cache.get_or_build(
                    key, build)
            else:
                self._loops[capacity] = build()
        return self._loops[capacity]

    def commit(self, state: SearchState) -> SearchState:
        return SearchState(*(dist._to_mesh(self.mesh, s, x)
                             for s, x in zip(self.spec_state, state)))

    def run_once(self, state: SearchState, max_iters_b,
                 bound_caps_b) -> SearchState:
        """ONE dispatch of the batched loop (no overflow recovery here:
        the segmented driver grows the whole batch and re-dispatches —
        the host-side half of the solo `run` loop)."""
        capacity = state.prmu.shape[-1]
        targets = jnp.asarray(np.asarray(max_iters_b),
                              state.iters.dtype)
        caps = jnp.asarray(
            np.asarray([I32_MAX if c is None else int(c)
                        for c in bound_caps_b]), jnp.int32)
        return SearchState(*self._loop(capacity)(
            self.tables, targets, caps, *state))


# ----------------------------------------------------------- host driver


@dataclasses.dataclass
class MemberSpec:
    """One request's slice of a batch dispatch. The engine knobs that
    must AGREE across the batch (problem, table shape, lb, chunk,
    capacity, balance knobs, segment geometry) live on `serve_batch`;
    everything per-request lives here."""

    table: np.ndarray
    init_ub: int | None = None
    checkpoint_path: str | None = None
    # dict or callable merged into every checkpoint meta this member
    # writes (the service rides its cumulative spent_s clock on it)
    checkpoint_meta_extra: object = None
    incumbent_key: str | None = None


class _Member:
    """Per-member host-side bookkeeping inside one batch dispatch."""

    def __init__(self, idx: int, spec: MemberSpec):
        self.idx = idx
        self.spec = spec
        self.warmup_tree = 0
        self.warmup_sol = 0
        self.start_iters = 0
        self.frozen_target: int | None = None   # set on stop: the
        #                                         member's lanes idle
        self.active = True
        self.stopped = False     # stop (vs drained) at deactivation
        self.folder = None       # checkpoint._ReportFolder
        self.client = None       # incumbent BoardClient
        self.result: DistResult | None = None
        self.last_saved_seg = -1


def _stack_tables(prob, tables_list):
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x)
                                               for x in xs]),
                        *tables_list)


def serve_batch(specs: list, problem="pfsp", lb_kind: int = 1,
                mesh=None, chunk: int | None = None,
                capacity: int | None = None,
                balance_period: int | None = None,
                transfer_cap: int | None = None,
                min_transfer: int | None = None,
                min_seed: int = dist.MIN_SEED,
                segment_iters: int = 512,
                checkpoint_every: int = 1,
                heartbeat=None, member_stop=None, on_member_done=None,
                on_member_stopped=None,
                stop_event=None, loop_cache=None,
                incumbent_board=None, tuner=None,
                stall_limit: int = 3) -> list:
    """Solve B same-shape-class instances in ONE compiled batched loop,
    segmented — the megabatch execution engine the service dispatches a
    formed batch to.

    Per-member hooks (all optional, `b` is the member index):
    `heartbeat(b, SegmentReport)` after every segment;
    `member_stop(b, SegmentReport) -> bool` asks whether to stop the
    member at this boundary (cancel/deadline/preempt — the member is
    checkpointed and its lanes freeze, batchmates continue);
    `on_member_done(b, DistResult)` fires the moment a member's pool
    drains (its terminal state need not wait for the batch);
    `on_member_stopped(b, DistResult)` fires the moment a stop takes
    effect, with the member's checkpointed partial result — the
    service finalizes a cancelled/deadline member THERE, at the
    boundary, instead of holding it RUNNING until the batch drains.
    `stop_event` stops the WHOLE batch at the next boundary (every
    active member checkpoints — the preempt/shutdown path).

    Returns the per-member DistResult list: `complete=True` members
    drained; others stopped with partial counters (their checkpoints
    resume — solo or in a later batch, bit-identically).

    `chunk=None`/`balance_period=None` resolve through the tuner's
    batched key (cache else the batched measured-defaults row — never
    a probe, and never the SOLO serving row silently: the batched
    fallback is its own explicit table row)."""
    from ..tune import defaults as tune_defaults
    from . import checkpoint, incumbent as inc_mod

    prob = dist._resolve_problem(problem)
    if not specs:
        raise ValueError("serve_batch needs at least one MemberSpec")
    if mesh is None:
        from ..parallel.mesh import worker_mesh
        mesh = worker_mesh(None)
    n_dev = mesh.devices.size
    B = len(specs)
    tables0 = np.asarray(specs[0].table)
    for sp in specs:
        if np.asarray(sp.table).shape != tables0.shape:
            raise ValueError(
                "all batch members must share one table shape, got "
                f"{np.asarray(sp.table).shape} vs {tables0.shape}")
    jobs = prob.slots(tables0)
    aux_rows = prob.aux_rows(tables0)
    adt = prob.aux_dtype(tables0)
    if chunk is None or balance_period is None:
        if tuner is not None:
            params = tuner.resolve(jobs, tables0.shape[0], lb_kind,
                                   n_workers=n_dev, allow_probe=False,
                                   problem=prob.name, batch=B)
        else:
            params = tune_defaults.params_for(
                "serving", jobs, tables0.shape[0], problem=prob.name,
                batch=B)
        if chunk is None:
            chunk = params.chunk
            if transfer_cap is None and params.transfer_cap:
                transfer_cap = params.transfer_cap
        if balance_period is None:
            balance_period = params.balance_period
        tracelog.event("tuner.resolve", chunk=chunk,
                       balance_period=balance_period,
                       source=params.source, batch=B)
    if capacity is None:
        capacity = prob.default_capacity(tables0)
    tc, mt = dist.balance_defaults(chunk, jobs, aux_rows, n_dev, min_seed,
                                   aux_itemsize=adt.itemsize)
    transfer_cap = tc if transfer_cap is None else transfer_cap
    min_transfer = mt if min_transfer is None else min_transfer

    def make_local_step(t, limit):
        return prob.make_step(t, lb_kind, chunk, 1024, limit)

    driver = BatchedDriver(
        mesh, _stack_tables(prob, [prob.make_tables(np.asarray(sp.table))
                                   for sp in specs]),
        make_local_step, balance_period, transfer_cap, min_transfer,
        limit_fn=lambda cap: prob.usable_rows(cap, chunk, jobs),
        batch=B, loop_cache=loop_cache,
        # the solo key prefix (problem, pool width, table lead dim, lb,
        # chunk, aux dtype) — _problem_driver's layout — so the
        # ("batch", B) suffix is the ONLY difference from a solo key
        loop_key=(prob.name, jobs, int(tables0.shape[0]), lb_kind,
                  chunk, str(adt)))

    members = [_Member(i, sp) for i, sp in enumerate(specs)]

    # ---- per-member seed-or-resume, to ONE common capacity.
    # Each member runs the SOLO rules (warmup target, init_best fold,
    # frontier striping, elastic reshard, capacity pre-grow) so its
    # state at segment 0 is bit-identical to what a solo dispatch at
    # the same knobs would build; the common capacity is the max over
    # members' solo requirements (growth is content-preserving).
    host_states: list[SearchState] = []
    need_caps: list[int] = []
    for m in members:
        sp = m.spec
        table = np.asarray(sp.table)
        resumed = None
        if sp.checkpoint_path and checkpoint.resume_path(
                sp.checkpoint_path):
            resumed = checkpoint.load_resilient(
                sp.checkpoint_path,
                p_times=table if prob.name == "pfsp" else None)[:2]
            saved_prob = resumed[1].get("problem")
            saved_prob = ("pfsp" if saved_prob is None
                          else str(np.asarray(saved_prob)))
            if saved_prob != prob.name:
                raise MemberIncompatible(
                    m.idx,
                    f"checkpoint {sp.checkpoint_path} was written by "
                    f"problem {saved_prob!r}; refusing to resume it as "
                    f"{prob.name!r}")
        if resumed is not None:
            host_state, meta = resumed
            if len(np.asarray(meta.get("host_depth", []))):
                # a -C host-tier checkpoint carries carved-out seed
                # nodes; the batched engine has no host tier — push
                # them back so no subtree is lost
                from . import hybrid
                host_state = hybrid.restore_host_share(
                    host_state,
                    np.asarray(meta["host_prmu"], np.int16),
                    np.asarray(meta["host_depth"], np.int16), table)
            shape = np.asarray(host_state.prmu).shape
            if len(shape) != 3 or shape[0] != n_dev:
                pre_sums = (obs_audit.state_sums(host_state)
                            if obs_audit.enabled() else None)
                host_state = checkpoint.reshard_state(host_state, n_dev)
                if pre_sums is not None:
                    obs_audit.check_reshard(pre_sums, host_state,
                                            edge="elastic_resume")
            m.warmup_tree = int(meta.get("warmup_tree", 0))
            m.warmup_sol = int(meta.get("warmup_sol", 0))
            cap = host_state.prmu.shape[-1]
            need = int(np.asarray(host_state.size).max())
            while driver.limit(cap) < max(need, 1):
                cap *= 2
            if cap != host_state.prmu.shape[-1]:
                host_state = checkpoint.grow(host_state, cap)
            host_states.append(host_state)
            need_caps.append(cap)
        else:
            with tracelog.span("bfs_warmup", problem=prob.name,
                               member=m.idx,
                               target=min_seed * n_dev) as ws:
                fr = prob.warmup(table, lb_kind, sp.init_ub,
                                 target=min_seed * n_dev)
                ws.set(frontier=len(fr.depth), tree=fr.tree)
            init_best = (fr.best if sp.init_ub is None
                         else min(fr.best, int(sp.init_ub)))
            fr.aux = prob.seed_aux(table, fr.prmu, fr.depth)
            m.warmup_tree, m.warmup_sol = fr.tree, fr.sol
            # the member RUNS at the common serving capacity (the solo
            # pre-grow rule decides need_caps), but its stripes are
            # BUILT at the smallest capacity that admits them —
            # striping is front-aligned, so the layout at any larger
            # capacity is this plus zero rows, which stack_states pads
            # without a per-member full-capacity allocation
            cap = capacity
            stripe = -(-max(len(fr.depth), 1) // n_dev)
            while driver.limit(cap) < max(stripe, 1):
                cap *= 2
            need_caps.append(cap)
            seed_cap = 256
            while (seed_cap < cap
                   and driver.limit(seed_cap) < max(stripe, 1)):
                seed_cap *= 2
            seed_cap = min(seed_cap, cap)
            leaves = dist._shard_frontier(
                fr, n_dev, seed_cap, jobs, init_best,
                limit=driver.limit(seed_cap))
            host_states.append(SearchState(*leaves))

    common_cap = max(need_caps)
    # resumed members may carry a different aux dtype (a legacy int32
    # snapshot) or telemetry width (a flag flip across lifetimes) — a
    # batch must be homogeneous to stack. Blame a member that differs
    # from the MAJORITY, typed so the service demotes it to solo
    def _homogeneous(values, what: str) -> None:
        if len(set(values)) <= 1:
            return
        modal = max(set(values), key=values.count)
        offender = next(i for i, v in enumerate(values) if v != modal)
        raise MemberIncompatible(
            offender,
            f"batch member {offender} carries {what} "
            f"{values[offender]!r} (batch majority: {modal!r}); "
            "re-serve the legacy-checkpoint request solo")

    _homogeneous([np.asarray(s.aux).dtype for s in host_states],
                 "pool aux dtype")
    _homogeneous([int(np.asarray(s.telemetry).shape[-1])
                  for s in host_states], "telemetry block width")

    t0 = time.perf_counter()
    for m, hs in zip(members, host_states):
        m.start_iters = int(np.asarray(hs.iters).max())
        m.folder = checkpoint._ReportFolder(hs, t0, stall_limit,
                                            m.start_iters)
        if incumbent_board is not None:
            m.client = inc_mod.BoardClient(
                incumbent_board,
                m.spec.incumbent_key
                or inc_mod.share_key(np.asarray(m.spec.table),
                                     problem=prob.name))
            m.client.publish(int(np.asarray(hs.best).min()))

    state = driver.commit(stack_states(host_states,
                                       capacity=common_cap))
    del host_states

    def member_meta(m: _Member) -> dict:
        extra = m.spec.checkpoint_meta_extra
        extra = (extra() if callable(extra) else dict(extra or {}))
        return {"warmup_tree": m.warmup_tree, "warmup_sol": m.warmup_sol,
                "problem": prob.name,
                "host_prmu": np.zeros((0, jobs), np.int16),
                "host_depth": np.zeros(0, np.int16), **extra}

    # ONE whole-batch host fetch per save boundary, shared by every
    # member saving at it: per-member device slicing + fetch costs
    # ~30 ms x B per boundary (measured: +0.6 s on a 16-member batch),
    # while one batched fetch plus numpy slicing is ~flat in B
    host_cache: dict = {"seg": -1, "state": None}

    def _host_state(st: SearchState, seg: int) -> SearchState:
        if host_cache["seg"] != seg:
            host_cache["seg"] = seg
            host_cache["state"] = dist.fetch_state(st)
        return host_cache["state"]

    def save_member(m: _Member, st: SearchState, seg: int) -> None:
        if not m.spec.checkpoint_path:
            return
        snap = slice_member(_host_state(st, seg), m.idx)
        checkpoint.save(m.spec.checkpoint_path, snap,
                        meta={**member_meta(m), "segment": seg})
        if obs_audit.roundtrip_enabled():
            obs_audit.check_checkpoint_roundtrip(
                m.spec.checkpoint_path, snap)
        m.last_saved_seg = seg

    def finish_member(m: _Member, st: SearchState, fetched,
                      complete: bool) -> DistResult:
        f = {k: (np.asarray(v)[:, m.idx] if v is not None else None)
             for k, v in fetched.items()}
        best = int(f["best"].min())
        if m.client is not None:
            m.client.publish(best)
        telemetry = None
        if f.get("telemetry") is not None and f["telemetry"].size:
            # summarize merges the (D, W) stack itself — merging here
            # first would replay the ring twice and drop same-iteration
            # non-monotone improvements the solo path keeps
            telemetry = tele.summarize(f["telemetry"])
        res = DistResult(
            explored_tree=int(f["tree"].sum()) + m.warmup_tree,
            explored_sol=int(f["sol"].sum()) + m.warmup_sol,
            best=best, telemetry=telemetry,
            per_device={
                "tree": f["tree"], "sol": f["sol"], "iters": f["iters"],
                "evals": f["evals"], "sent": f["sent"],
                "recv": f["recv"], "steals": f["steals"],
                "final_size": f["size"],
            },
            warmup_tree=m.warmup_tree, warmup_sol=m.warmup_sol,
            complete=complete, problem=prob.name)
        if obs_audit.enabled():
            obs_audit.check_result(res)
        m.result = res
        m.active = False
        return res

    seg = 0
    names = ("iters", "tree", "sol", "size", "best", "steals",
             "overflow", "evals", "sent", "recv")
    tele_on = int(state.telemetry.shape[-1]) > 0
    from ..utils import faults
    with tracelog.span("batch.execute", batch=B, problem=prob.name,
                       jobs=jobs, chunk=chunk) as bs:
        while any(m.active for m in members):
            # the same deterministic injection points run_segmented
            # fires, so the chaos/crash drill kinds (kill_server,
            # delay_segment, ...) cover batched execution too
            faults.fire("segment_start", segment=seg + 1)
            targets = []
            caps = []
            for m in members:
                if not m.active:
                    # frozen: the recorded iteration count — the cond
                    # is already false for this member
                    targets.append(m.frozen_target or m.start_iters)
                    caps.append(None)
                else:
                    targets.append(m.start_iters
                                   + (seg + 1) * segment_iters)
                    caps.append(m.client.cap() if m.client else None)
            out = driver.run_once(state, targets, caps)
            fetched_t = checkpoint._fetch_many(
                tuple(getattr(out, n) for n in names)
                + ((out.telemetry,) if tele_on else ()))
            fetched = dict(zip(names, fetched_t))
            fetched["telemetry"] = fetched_t[len(names)] if tele_on \
                else None
            if bool(np.asarray(fetched["overflow"]).any()):
                # lossless whole-batch growth, the solo driver.run
                # recovery at batch granularity: fetch, double, recommit,
                # re-dispatch the SAME targets (not a new segment)
                grown = checkpoint.grow(dist.fetch_state(out),
                                        out.prmu.shape[-1] * 2)
                state = driver.commit(grown)
                continue
            state = out
            seg += 1
            batch_stop = stop_event is not None and stop_event.is_set()
            for m in members:
                if not m.active:
                    continue
                rep = m.folder.fold(
                    tuple(np.asarray(fetched[n])[:, m.idx]
                          for n in ("iters", "tree", "sol", "size",
                                    "best", "steals", "overflow",
                                    "evals"))
                    + ((np.asarray(
                        fetched["telemetry"])[:, m.idx],)
                       if tele_on else ()), seg)
                if m.client is not None:
                    m.client.publish(rep.best)
                if heartbeat is not None:
                    heartbeat(m.idx, rep)
                if rep.pool_size == 0:
                    # no drain-save (checked BEFORE the periodic save:
                    # at checkpoint_every=1 the drain boundary would
                    # otherwise write a snapshot the DONE finalize
                    # unlinks moments later): a drained member's
                    # snapshot records an empty pool nobody will
                    # resume, and a crash between drain and the ledger
                    # terminal replays the request to the same
                    # bit-identical result. (The solo driver's
                    # exit-save predates serving and is kept there for
                    # the CLI resume contract.)
                    res = finish_member(m, state, fetched,
                                        complete=True)
                    if on_member_done is not None:
                        on_member_done(m.idx, res)
                    continue
                stop = batch_stop or (
                    member_stop is not None and member_stop(m.idx, rep))
                if stop:
                    save_member(m, state, seg)
                    m.frozen_target = rep.iters
                    m.stopped = True
                    res = finish_member(m, state, fetched,
                                        complete=False)
                    if on_member_stopped is not None:
                        on_member_stopped(m.idx, res)
                    continue
                if m.spec.checkpoint_path \
                        and seg % checkpoint_every == 0:
                    save_member(m, state, seg)
                m.folder.check_stall(rep)
            # after the boundary's heartbeats and saves, like
            # run_segmented's post-checkpoint injection point
            faults.fire("post_segment", segment=seg)
        bs.set(segments=seg,
               done=sum(1 for m in members
                        if m.result is not None and m.result.complete))
    return [m.result for m in members]
