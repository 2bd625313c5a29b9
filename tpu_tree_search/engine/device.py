"""Single-device PFSP B&B engine: HBM-resident pool + compiled search loop.

This replaces the reference's host-managed architecture — CPU deque
(Pool_atom.c), chunked H2D/D2H offload with `-m/-M` thresholds, CUDA bound
kernel, host-side prune+branch (`generate_children`, PFSP_lib.h:51-95) —
with a design where the node pool never leaves the device: the whole
pop -> bound -> prune -> branch cycle is one `lax.while_loop` inside `jit`
(reference hot loop: pfsp_multigpu_cuda.c:221-320 round-trips the host
every iteration; here the host only sees the final counters).

Pool layout (struct-of-arrays in HBM, replacing the reference's
array-of-struct deque, Pool_atom.h:23-30):
    prmu  int16[capacity, jobs]   permutations
    depth int16[capacity]         scheduled-prefix length
    size  int32                   stack cursor (rows [0, size) are live)

Each step pops a chunk of up to `chunk` parents off the top of the stack
(deepest-first => depth-first, preserving the pruning locality the
reference gets from popBackBulk, Pool_atom.c:154-178), evaluates the dense
(chunk, jobs) grid of child bounds with the batched kernels, and pushes
surviving children back with a masked compacting scatter — the on-device
equivalent of `generate_children` + `pushBackBulk`.

Unlike the reference's growable deque (realloc-on-push, Pool_atom.c:47-51),
the pool has static capacity; an `overflow` flag aborts the search cleanly
if it would be exceeded (callers then retry with a larger pool). DFS order
keeps the live size near (tree depth x branching x chunk), far below
capacity in practice.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import tracelog
from ..ops import batched, pallas_expand, pallas_fused, reference as ref
from ..ops.batched import BoundTables
from ..utils import config as _cfg
from . import telemetry as tele

I32_MAX = jnp.int32(2**31 - 1)

# read ONCE at import, never inside the traced step: an env read at
# trace time is a silent retrace/stale-value hazard (tts-lint
# trace_safety) — the executable keeps whatever the first trace saw
_DEBUG_STEP = _cfg.env_flag("TTS_DEBUG_STEP")

# default telemetry leaf for keyword-constructed states (numpy, not jnp:
# a module-import-time jnp array would force backend selection before
# the CLI's --platform override can run)
_NO_TELEMETRY = np.zeros(0, np.int64)


def aux_dtype(p_times: np.ndarray | None) -> np.dtype:
    """Narrowest safe dtype for the pool's per-node tables (front vectors)
    and their compaction traffic. Every value stored there is a machine
    completion time of some partial schedule, bounded by the critical-path
    bound: any C[k][i] in the flow-shop recurrence is a sum over one
    monotone lattice path from (0,0) to (k,i), at most (J + M - 1) cells
    of at most max(p) each. When that bound fits int16, halving the aux
    bytes roughly halves the byte-bound compaction gathers and block
    writes that dominate the step (a round-3 chip profile; not
    measured on chip this round). Every Taillard class through 200x20
    fits; 500-job instances fall back to int32 automatically.
    """
    if p_times is None:
        return np.dtype(np.int32)
    m, j = p_times.shape
    bound = (j + m - 1) * int(np.max(p_times))
    if bound <= int(np.iinfo(np.int16).max):
        return np.dtype(np.int16)
    return np.dtype(np.int32)


def row_limit(capacity: int, chunk: int, jobs: int) -> int:
    """Usable pool rows. The top `chunk*jobs` rows are a scratch margin:
    the push block-write always writes a full chunk*jobs block, and an
    overflowing step routes it there so the live region stays untouched.
    Every commit point (step, balance, seeding) must keep
    `size <= row_limit` — that invariant is what keeps the block write in
    bounds and overflow recovery lossless."""
    return max(capacity - chunk * jobs, 0)


class SearchState(NamedTuple):
    """Carried through the `lax.while_loop`; all arrays device-resident.

    Pool arrays are FEATURE-MAJOR — the row (node) axis is last, so it
    rides the 128-wide vector lanes. Row-major `(capacity, jobs)` pools
    put jobs~20 on the lanes (84% waste) and force layout conversions
    around every push/pop; feature-major matches the expand kernel's
    native layout (ops/pallas_expand.py) end to end."""

    prmu: jax.Array      # (jobs, capacity) int16
    depth: jax.Array     # (capacity,) int16
    aux: jax.Array       # (A, capacity) int32 per-node tables; PFSP stores
                         # the node's machine-completion vector `front`
                         # (A = machines) so bounds never rescan the
                         # prefix; problems without per-node tables
                         # (N-Queens) use A = 0
    size: jax.Array      # int32 live-row cursor
    best: jax.Array      # int32 incumbent makespan
    tree: jax.Array      # int64 explored (= pushed) internal nodes
    sol: jax.Array       # int64 evaluated leaf children
    iters: jax.Array     # int64 loop iterations (stats)
    evals: jax.Array     # int64 child bound evaluations (the bench metric)
    sent: jax.Array      # int64 nodes donated via balance exchanges
    recv: jax.Array      # int64 nodes received via balance exchanges
    steals: jax.Array    # int64 balance rounds that received > 0 nodes
    overflow: jax.Array  # bool: capacity would have been exceeded
    telemetry: jax.Array = _NO_TELEMETRY
                         # int64 (telemetry.WIDTH,) on-device search
                         # telemetry block (engine/telemetry.py layout);
                         # width 0 when TTS_SEARCH_TELEMETRY is off —
                         # the step then traces ZERO telemetry ops


@functools.partial(jax.jit, donate_argnums=0)
def _seed_update(buf, rows):
    """In-place (donated) write of the seed rows into the fresh pool
    buffer; module-level so the jit cache persists across init_state
    calls (a per-call wrapper would retrace every instance/segment)."""
    return jax.lax.dynamic_update_slice(buf, rows, (0,) * buf.ndim)


def init_state(jobs: int, capacity: int, init_ub: int | None,
               prmu0: np.ndarray | None = None,
               depth0: np.ndarray | None = None,
               p_times: np.ndarray | None = None,
               telemetry: bool | None = None,
               aux0: np.ndarray | None = None) -> SearchState:
    """Pool with the given seed nodes (default: the root at depth 0).

    `p_times` (PFSP) sizes and fills the per-node aux tables; `aux0`
    ((n, A) host rows, any problem) seeds them directly — the problem-
    plugin path (problems/base.Problem.seed_aux). Without either the
    aux width is 0 (problems like N-Queens that carry no per-node
    tables). `telemetry` compiles the on-device search-telemetry block
    into the state (None: the TTS_SEARCH_TELEMETRY env flag,
    engine/telemetry.py).
    """
    if prmu0 is None:
        prmu0 = np.arange(jobs, dtype=np.int16)[None, :]
        depth0 = np.zeros(1, dtype=np.int16)
    prmu0 = np.asarray(prmu0, dtype=np.int16).reshape(-1, jobs)
    depth0 = np.asarray(depth0, dtype=np.int16).reshape(-1)
    n = prmu0.shape[0]
    assert n <= capacity

    # Allocate the pool ON the device and ship only the seed rows: the
    # host-side np.zeros variant uploaded the full capacity through the
    # runtime (~350 MB at capacity 2^22 for 20x20, paid per instance by
    # campaign drivers). The
    # seeding update runs jitted with the zeros buffer DONATED so the
    # write is in place — eager dynamic_update_slice holds both the
    # zeros and the result at once, ~2x peak HBM per pool array at init
    # (enough to OOM capacities that fit once running).
    def seeded(shape, dtype, rows):
        return _seed_update(jnp.zeros(shape, dtype),
                            jnp.asarray(rows, dtype))

    prmu = seeded((jobs, capacity), jnp.int16, prmu0.T)
    depth = seeded((capacity,), jnp.int16, depth0)
    if p_times is not None:
        m = p_times.shape[0]
        aux = seeded((m, capacity), aux_dtype(p_times),
                     ref.prefix_front_remain(p_times, prmu0,
                                             depth0)[:, :m].T)
    elif aux0 is not None and aux0.shape[-1] > 0:
        aux0 = np.asarray(aux0).reshape(len(depth0), -1)
        aux = seeded((aux0.shape[1], capacity), aux0.dtype, aux0.T)
    else:
        aux = jnp.zeros((0, capacity), jnp.int32)
    best = 2**31 - 1 if init_ub is None else int(init_ub)
    return SearchState(
        prmu=prmu,
        depth=depth,
        aux=aux,
        size=jnp.int32(n),
        best=jnp.int32(best),
        tree=jnp.int64(0),
        sol=jnp.int64(0),
        iters=jnp.int64(0),
        evals=jnp.int64(0),
        sent=jnp.int64(0),
        recv=jnp.int64(0),
        steals=jnp.int64(0),
        overflow=jnp.asarray(False),
        telemetry=jnp.zeros(
            (tele.WIDTH if (tele.enabled() if telemetry is None
                            else telemetry) else 0,), jnp.int64),
    )


def make_children(prmu: jax.Array, depth: jax.Array) -> jax.Array:
    """Dense (B, J, J) child permutations: slot i swaps positions depth<->i
    (the prefix-swap branching of decompose, reference: PFSP_lib.c:13-16).

    Gather-free: the value swapped into position `depth` is just `prmu[b, i]`
    (= `prmu` itself along the slot axis), and the job swapped out to
    position i is extracted with a masked sum — per-element dynamic
    gathers cost ~ms at this batch size on TPU, pure vector ops don't."""
    B, J = prmu.shape
    pos = jnp.arange(J, dtype=jnp.int32)[None, None, :]     # permutation index
    slot = jnp.arange(J, dtype=jnp.int32)[None, :, None]    # which child
    d = depth[:, None, None].astype(jnp.int32)
    at_depth = jnp.sum(
        jnp.where(jnp.arange(J)[None, :] == depth[:, None].astype(jnp.int32),
                  prmu.astype(jnp.int32), 0),
        axis=1)                                              # (B,) prmu[b, depth]
    base = prmu[:, None, :]                                  # (B, 1, J)
    swapped_in = prmu[:, :, None]                            # (B, J, 1) prmu[b, i]
    child = jnp.where(pos == d, swapped_in,
                      jnp.where(pos == slot, at_depth[:, None, None], base))
    return child.astype(jnp.int16)


def _col_major(x, G: int, J: int, TB: int):
    """(1, B) per-parent row -> (1, N) per-child-slot row in the expand
    kernel's column order (c = (g*J + i)*TB + b)."""
    return jnp.broadcast_to(x.reshape(G, 1, TB), (G, J, TB)).reshape(1, -1)


def _child_masks(p_depth, valid, G: int, J: int, TB: int):
    """The (1, N) child-slot mask family in the expand kernel's column
    order — ONE construction shared by step()'s dense routes and the
    fused spill branch, so the two can never drift (the spill cond's
    bit-parity with the kernel path depends on it). Returns (depth_c,
    mask); leaves are ``(depth_c + 1) == J`` within mask."""
    depth_c = _col_major(p_depth, G, J, TB)
    valid_c = _col_major(valid[None, :], G, J, TB)
    slot_c = jnp.broadcast_to(
        jnp.arange(J, dtype=jnp.int32)[None, :, None], (G, J, TB)
    ).reshape(1, G * J * TB)
    return depth_c, (slot_c >= depth_c) & valid_c


def _partition(push: jax.Array) -> jax.Array:
    """Stable-partition permutation: indices of all True columns first (in
    order), then the False ones. One single-operand unstable sort of a
    packed u32 key — the flag rides bit 31, the column index the low bits,
    so every key is unique and the unstable sort is deterministic. ~4x
    cheaper than argsort on TPU (no hidden payload operands)."""
    n = push.shape[0]
    assert n < 2**31
    key = (jnp.where(push, jnp.uint32(0), jnp.uint32(1) << 31)
           | jnp.arange(n, dtype=jnp.uint32))
    return (jax.lax.sort(key, is_stable=False)
            & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def _regather(tables: BoundTables, p_prmu, p_depth2, p_aux, idx,
              TB: int, with_sched: bool = False):
    """Rebuild the first `t` compacted children directly from the popped
    parent arrays (sources are only `chunk` wide, so these gathers move a
    fraction of what gathering the dense (features, chunk*jobs) child
    block would; the children's permutations and front chains are
    recomputed — O(jobs + machines) vector ops per survivor, far cheaper
    on TPU than the avoided HBM traffic).

    `idx` (t,) are child-column indices in expand()'s slot-major order
    (c = (g*J + i)*TB + b). Returns (child (J,t) int16,
    caux (M+1,t) = [child front | depth+1] in the POOL's aux dtype
    (int16 when the instance's completion times fit it, see aux_dtype)
    [, sched (W,t) int32 multi-word scheduled-set bitmask,
    W = ceil(J/32)]). Keeping the child block int16 and SEPARATE from
    the wider aux rows measures faster than one combined i32 block
    (tried: +60% gather time per step — these gathers are byte-bound at
    40+ i32 rows; the narrow aux dtype attacks the same wall)."""
    J, B = p_prmu.shape
    M = p_aux.shape[0]
    adt = p_aux.dtype
    t = idx.shape[0]
    JTB = J * TB
    g = idx // JTB
    r = idx - g * JTB
    slot = r // TB
    b = r - slot * TB
    pcol = g * TB + b                               # parent column in [0, B)
    # barriers: without them XLA fuses the index arithmetic into the
    # gathers and the fused kernels run ~5x slower (measured on v5e)
    pcol, slot = jax.lax.optimization_barrier((pcol, slot))
    src = jnp.concatenate([p_aux, p_depth2.astype(adt)], axis=0)  # (M+1, B)
    pp = jnp.take(p_prmu, pcol, axis=1)                   # (J, t) int16
    pfd = jnp.take(src, pcol, axis=1)                     # (M+1, t) adt
    pp, pfd = jax.lax.optimization_barrier((pp, pfd))
    pfd = pfd.astype(jnp.int32)   # chain math in i32; stores back in adt
    pf = pfd[:M]
    pd = pfd[M:]                                          # (1, t) depth

    ppi = pp.astype(jnp.int32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (J, t), 0)
    appended = jnp.sum(jnp.where(rows == slot[None, :], ppi, 0),
                       axis=0, dtype=jnp.int32)[None, :]  # prmu[slot]
    at_depth = jnp.sum(jnp.where(rows == pd, ppi, 0),
                       axis=0, dtype=jnp.int32)[None, :]  # prmu[depth]
    child = jnp.where(rows == pd, appended,
                      jnp.where(rows == slot[None, :], at_depth,
                                ppi)).astype(jnp.int16)

    # child_p[k] = p[k, appended] (J-step select: dynamic column gathers
    # of the tiny (M, J) table serialize on TPU, selects vectorize)
    cp = jnp.zeros((M, t), jnp.int32)
    for j in range(J):
        cp = jnp.where(appended == j, tables.p[:, j:j + 1], cp)

    # add_forward chain (c_bound_simple.c:31-38) from the parent front
    cf = pf[0:1] + cp[0:1]
    cf_rows = [cf]
    for k in range(1, M):
        cf = jnp.maximum(cf, pf[k:k + 1]) + cp[k:k + 1]
        cf_rows.append(cf)
    caux = jnp.concatenate(cf_rows + [pd + 1], axis=0).astype(adt)  # (M+1,t)

    if not with_sched:
        return child, caux
    one = jnp.int32(1)
    words = []
    for w in range(pallas_expand.sched_words(J)):
        inw = (ppi >= 32 * w) & (ppi < 32 * (w + 1))
        bit = one << jnp.where(inw, ppi - 32 * w, 0)
        pmask = jnp.sum(jnp.where((rows < pd) & inw, bit, 0),
                        axis=0, dtype=jnp.int32)[None, :]
        ainw = (appended >= 32 * w) & (appended < 32 * (w + 1))
        abit = jnp.where(
            ainw, one << jnp.where(ainw, appended - 32 * w, 0), 0)
        words.append(pmask | abit)
    return child, caux, jnp.concatenate(words, axis=0)


def _compact_tiers(N: int, two_phase: bool = False,
                   cap: int | None = None) -> list[int]:
    """Compaction tier widths. Few and carefully placed: every extra
    lax.switch branch costs a copy of the (rows, N) output blocks
    (measured: a 9-rung ladder cost LB1 14% of its step rate). The LB1
    ladder holds its two steady-state occupancies (final push in N//16,
    candidates in N//4); the two-phase LB2 ladder adds 3N//32 for the
    post-prefilter survivors, which sit just above N//16 — a pow2-only
    ladder would round them to N//4, 4x the gather+pad width (measured
    on ta021: ncand~152k -> N//4, nkeep~43k -> 3N//32).

    `cap` truncates the ladder AND the frame: every block is padded to
    `cap` instead of N (the steady branch of the two-phase route runs
    its whole post-LB1 pipeline in N//4-wide frames — see step())."""
    steps = ((N // 16, 3 * N // 32, N // 4) if two_phase
             else (N // 16, N // 4))
    cap = N if cap is None else cap
    return [t for t in steps if 128 <= t < cap] + [cap]


def _tier_switch(tiers: list[int], count, make_branch):
    """Dispatch to the smallest tier covering `count` via ONE lax.switch
    (a nested cond ladder copies its result at every level).
    `make_branch(width) -> (_ -> result)` builds each branch; the last
    tier must cover every possible count."""
    if len(tiers) == 1:
        return make_branch(tiers[0])(0)
    sel = sum((count > t).astype(jnp.int32) for t in tiers[:-1])
    return jax.lax.switch(sel, [make_branch(t) for t in tiers], 0)


def _partition_prefix(push: jax.Array, live, N: int,
                      two_phase: bool = False,
                      cap: int | None = None) -> jax.Array:
    """_partition when every True column is known to sit below `live`
    (a traced count): sort only the smallest compaction tier covering
    `live` instead of all N keys (~3x of the two-phase step's sort cost
    was full-width sorts whose tails were all-False). Entries past the
    sorted prefix are filled with their own index — valid garbage that
    downstream tier gathers may read into pad columns, which land above
    the pool cursor and are never read (the consuming compact's tier is
    chosen by n_push <= live, so its prefix always lies inside the
    sorted region)."""
    tiers = _compact_tiers(N, two_phase, cap)
    frame = push.shape[0]

    def branch(t):
        def f(_):
            srt = _partition(push[:t])
            if t < frame:
                srt = jnp.concatenate(
                    [srt, jnp.arange(t, frame, dtype=jnp.int32)])
            return srt
        return f

    return _tier_switch(tiers, live, branch)


def _tiered_compact(gather, perm, n_keep, N: int, two_phase: bool = False,
                    cap: int | None = None):
    """Frame-width compacted block (frame = `cap` or N), built by the
    smallest tier that covers the `n_keep` survivors: a switch branch
    gathers only its tier's prefix via `gather(idx) -> tuple of
    (rows, len(idx)) blocks` and zero-pads the rest (a cheap sequential
    write; the garbage columns land above the pool cursor and are never
    read). The switch carries only these blocks — threading the HBM
    pools through conditional branches copies them (measured: ~4x step
    cost), which is why the caller writes the block into the pool
    outside."""
    tiers = _compact_tiers(N, two_phase, cap)
    frame = tiers[-1]

    def branch(t):
        def f(_):
            out = gather(jax.lax.slice(perm, (0,), (t,)))
            if t < frame:
                out = tuple(jnp.concatenate(
                    [o, jnp.zeros(o.shape[:-1] + (frame - t,), o.dtype)],
                    axis=-1) for o in out)
            return out
        return f

    return _tier_switch(tiers, n_keep, branch)


def _compact_from_parents(tables: BoundTables, p_prmu, p_depth2, p_aux,
                          perm, n_keep, TB: int, N: int,
                          with_sched: bool = False,
                          two_phase: bool = False,
                          cap: int | None = None):
    """Compacted child block rebuilt from the popped parents (see
    _regather), tiered by survivor count (see _tiered_compact)."""
    def gather(idx):
        return _regather(tables, p_prmu, p_depth2, p_aux, idx, TB,
                         with_sched)
    return _tiered_compact(gather, perm, n_keep, N, two_phase, cap)


def lb2_route(jobs: int, machines: int, pairs: int, chunk: int,
              tile: int = 1024) -> tuple[str, int, bool]:
    """THE LB2 routing decision at these shapes: returns
    (route, TB, pair_kernel_ok), route in {'dense', 'prefilter'} —
    pair_kernel_ok says whether the small-J register pair-sweep kernel
    runs (the prefilter route sweeps via it when True, else via the
    streaming big-J kernel or the XLA scan, lb2_sweep_tile). Shared by
    step() and the phase-attribution profiler (utils/phase_timing) so
    the attribution can never price a path or an implementation the
    engine does not use.

    - 'dense': one-shot dense pair sweep — needs the pallas pair kernel
      (lb2_kernel_fits) at the LB2-capped tile AND a few-pair class.
    - 'prefilter': LB1 pre-prune + pair sweeps over survivor tiers.
      Every stage degrades independently to its XLA fallback (the LB1
      bounds via expand_bounds' own dispatch, the sweeps via
      lb2_bounds'/sweep_tiers'), so this route covers EVERY class —
      including the 200/500-job classes whose expand kernel misses the
      scoped-VMEM cap: sweeping only survivor tiers beats the dense
      all-children XLA sweep ~10x there (the pair scan is the dominant
      cost and LB1 removes most of the grid first). When the pair
      kernel cannot run anyway, the LB2 tile cap's halving is moot and
      the tile retries at the LB1 cap (the 100-job classes).
    """
    TB = pallas_expand.effective_tile(jobs, chunk, tile, 2,
                                      machines=machines)
    pair_ok = (pallas_expand.kernel_ok(jobs, TB, 2, machines=machines)
               and pallas_expand.lb2_kernel_fits(jobs, pairs))
    if not pair_ok:
        TB1 = pallas_expand.effective_tile(jobs, chunk, tile, 1,
                                           machines=machines)
        if pallas_expand.kernel_ok(jobs, TB1, 1, machines=machines):
            TB = TB1
    if pair_ok and pairs <= 2 * batched.PAIR_PREFILTER:
        return "dense", TB, pair_ok
    return "prefilter", TB, pair_ok


def pop_chunk(state: SearchState, B: int, M: int):
    """Pop window of up to B parents off the stack top (no commit; the
    caller owns the cursor): the popBackBulk analogue. The window
    [start, start+B) is contiguous, so dynamic_slice beats a gather.
    Returns (p_prmu (J,B) i16, p_depth (1,B) i32, p_aux (M,B) in the
    POOL's aux dtype (aux_dtype — int16 on most classes; widen to i32
    before doing chain arithmetic on it), n, start, valid)."""
    J, capacity = state.prmu.shape
    n = jnp.minimum(state.size, B)
    start = state.size - n
    valid = jnp.arange(B) < n
    zero = jnp.zeros((), start.dtype)
    p_prmu = jax.lax.dynamic_slice(state.prmu, (zero, start), (J, B))
    p_depth = jax.lax.dynamic_slice(state.depth, (start,), (B,)) \
        .astype(jnp.int32)
    p_depth = jnp.where(valid, p_depth, 0)[None, :]            # (1, B)
    p_aux = jax.lax.dynamic_slice(state.aux, (zero, start), (M, B))
    return p_prmu, p_depth, p_aux, n, start, valid


def _write_block(state: SearchState, children, child_depth, child_aux,
                 start, n_push, limit):
    """Write the compacted child block at the cursor — or, when the step
    overflows, into the scratch margin at `limit` (rows
    [limit, limit + B*J) hold no live data by the size <= limit
    invariant), so an overflowing step's pool is untouched in its live
    region. Uses the same `start + n_push > limit` predicate as
    _commit's scalar guards — keep via this one helper."""
    M = child_aux.shape[0] - 1
    zero = jnp.zeros((), start.dtype)
    write_at = jnp.where(start + n_push > limit,
                         jnp.asarray(limit, start.dtype), start)
    prmu = jax.lax.dynamic_update_slice(state.prmu, children,
                                        (zero, write_at))
    depth = jax.lax.dynamic_update_slice(state.depth, child_depth,
                                         (write_at,))
    aux = jax.lax.dynamic_update_slice(
        state.aux, child_aux[:M].astype(state.aux.dtype), (zero, write_at))
    return prmu, depth, aux


def _commit(state: SearchState, prmu, depth, aux, n_push, best, sol, mask,
            limit, start, tele_delta=None) -> SearchState:
    """THE no-commit overflow contract, shared by every route: an
    overflowing step must NOT commit — advancing the cursor past the
    limit would lose subtrees (and make the overflow checkpoint
    unrecoverable). The state is left exactly as before the step with
    only the flag set: the caller routes the block write to the scratch
    margin (rows [limit, limit + B*J) hold no live data by the
    size <= limit invariant — `write_at` at the call sites uses this
    same `start + n_push > limit` condition) and the scalars here are
    guarded with selects, so grow-capacity + resume continues the
    search losslessly.

    `tele_delta` (telemetry.step_delta, or None when telemetry is off)
    folds the step's masked telemetry counts in under the SAME guard,
    plus the non-additive slots owned here: pool high-water max and the
    incumbent-improvement ring (telemetry.commit)."""
    new_size = start + n_push
    overflow = new_size > limit
    keep = lambda new, old: jnp.where(overflow, old, new)  # noqa: E731
    telem = state.telemetry
    if tele_delta is not None:
        telem = keep(tele.commit(telem, tele_delta, new_size, best,
                                 state.best, state.iters), telem)
    return state._replace(
        prmu=prmu,
        depth=depth,
        aux=aux,
        size=keep(new_size, state.size),
        best=keep(best, state.best),
        tree=keep(state.tree + n_push.astype(jnp.int64), state.tree),
        sol=keep(sol, state.sol),
        iters=state.iters + 1,
        evals=keep(state.evals + mask.sum(dtype=jnp.int64), state.evals),
        overflow=state.overflow | overflow,
        telemetry=telem)


def _sweep_tiers(tbl, cf_cols, sched_cols, count, N: int, J: int,
                 M: int):
    """Pair sweep over the smallest prefix tier covering `count` live
    columns; columns past the tier read I32_MAX. Finer ladder than the
    compaction's (its branches carry only a (1, frame) row, so extra
    rungs are nearly free) with 3/2^k rungs for the same occupancy
    reason (_compact_tiers). When the sweep runs as the pallas kernel,
    each rung must satisfy its tile rule (lb2_tile — lane alignment
    AND the scoped-VMEM model) or lb2_bounds would silently take its
    XLA fallback there; when the class is outside the pair kernel
    anyway (lb2_kernel_fits false — the J>64 classes), the XLA scan
    has no tile constraint and every rung is admitted, keeping the
    swept prefix snug around small survivor sets."""
    PT = int(tbl.ma0.shape[0])
    frame = cf_cols.shape[1]
    on_tpu = jax.default_backend() == "tpu"

    def rung_ok(t):
        # a rung is admitted when the sweep at that width runs a
        # pallas kernel — lb2_sweep_tile is THE shared dispatch
        # predicate (register kernel or streaming big-J), so admission
        # cannot diverge from lb2_bounds. On CPU every rung is fine
        # (the XLA scan has no tile rule).
        return (not on_tpu
                or pallas_expand.lb2_sweep_tile(J, PT, M, t) > 0)

    # finer than the compaction ladder (rungs here carry only a
    # (1, frame) row): the tail sweep's survivor count sits wherever
    # the head prune left it, and a coarse ladder over-sweeps it by up
    # to 50% (nkeep~43k rode the 61440 rung — measured, 166 pairs x
    # 18k wasted columns/step)
    tiers = [t for t in (k * N // 64 for k in
                         (1, 2, 3, 4, 5, 6, 8, 10, 12, 14, 16,
                          20, 24, 32))
             if 0 < t < frame and rung_ok(t)]
    if on_tpu and not rung_ok(frame):
        # the frame rung is appended unconditionally (it must cover
        # every count), but if it misses the tile rule lb2_bounds
        # takes its XLA fallback there — on the WIDEST (most
        # expensive) rung. Loud, not silent.
        import warnings
        warnings.warn(
            f"lb2 sweep frame rung {frame} (J={J}, P={PT}) fails "
            "the pallas tile rule; the widest sweep tier will run "
            "the XLA scan fallback", stacklevel=2)
    tiers.append(frame)

    def prefix(width):
        def f(_):
            b = pallas_expand.lb2_bounds(
                tbl, cf_cols[:, :width], sched_cols[:, :width])
            if width < frame:
                b = jnp.concatenate(
                    [b, jnp.full((1, frame - width), I32_MAX,
                                 jnp.int32)], axis=1)
            return b
        return f

    return _tier_switch(tiers, count, prefix)


def _take_block(*rows_arrays):
    """prefix-gather closure over the given (rows, frame) arrays."""
    def take(idx):
        idx = jax.lax.optimization_barrier(idx)
        out = tuple(jnp.take(a, idx, axis=1) for a in rows_arrays)
        return jax.lax.optimization_barrier(out)
    return take


def _lb2_tail(tables: BoundTables, state: SearchState, children, caux,
              sched, ncand, W_: int, N: int, best, start, limit,
              debug_tap: bool, TELE: bool):
    """Everything after the LB1 prune of the two-phase LB2 route, in
    W_-wide frames: the strong-pair head sweep, the mid prune+compact,
    the tail sweep, the final prune+compact and the pool block write.
    Extracted to module level so the UNFUSED prefilter branches (which
    regather survivors from their parents) and the FUSED route (whose
    kernel emits the compacted survivor block directly,
    ops/pallas_fused) run the exact same ops on the compacted block —
    the two can never drift. Inputs: children (J, W_) i16, caux
    (M+1, W_) i32, sched (SW, W_) i32, `ncand` live survivors in the
    leading columns (the rest unread garbage — the scratch-margin
    contract covers the pool write). Returns
    (prmu, depth, aux, n_push, hsum, tsum[, tele_tail])."""
    J = children.shape[0]
    M = tables.p.shape[0]
    P = int(tables.ma0.shape[0])
    KH = batched.PAIR_PREFILTER

    if P <= KH:
        # Few pairs but outside the dense route (the wide few-pair
        # classes, e.g. 100x5: the pallas pair kernel is gated off
        # past J=64): no prefilter tail exists — pair_split would
        # return an empty tail table whose (0, frame) pair-max has no
        # identity — so ONE full sweep over the LB1 survivors is the
        # whole LB2.
        with jax.named_scope("bound"):
            lb2b = _sweep_tiers(tables, caux[:M], sched, ncand, N, J, M)
        live = ncand
        if TELE:
            head_hp = jnp.zeros(tele.BOUND_BINS, jnp.int64)
    else:
        # Strong-pair prefilter (the reference's unimplemented
        # LB2_LEARN, c_bound_johnson.h:29): sweep only the
        # PAIR_PREFILTER strongest pairs (tables store pairs
        # strongest-first), prune on that partial max (partial max <=
        # LB2, so pruning on it is sound), and pay for the remaining
        # pairs only on the children the prefix failed to prune (<10%
        # on the 20x20 class). The total bound stays exactly
        # max(head, tail) = full LB2, so explored trees are
        # bit-identical to the single-sweep path.
        SW = pallas_expand.sched_words(J)
        with jax.named_scope("bound"):
            head_t, tail_t = batched.pair_split(tables, KH)
            lb2h = _sweep_tiers(head_t, caux[:M], sched, ncand, N, J, M)
        with jax.named_scope("prune"):
            keep = ((jnp.arange(W_) < ncand)
                    & (lb2h.reshape(-1) < best))
        if TELE:
            # pruned by the strong-pair head sweep: binned at the
            # partial bound that pruned them (a sound lower bound —
            # partial max <= LB2)
            head_hp = tele.bound_hist(
                lb2h, (jnp.arange(W_) < ncand) & ~keep, best)
        with jax.named_scope("prune"):
            nkeep = keep.sum(dtype=jnp.int32)
        with jax.named_scope("compact"):
            permh = _partition_prefix(keep, ncand, N, two_phase=True,
                                      cap=W_)
            # the partial bound rides the compaction as an extra row
            # (three structural variants were tried and measured WORSE:
            # an index-composed final gather that skips re-gathering
            # children — the composing (N,) take lowers to a ~4.7 ms
            # serialized gather; one combined i32 block per compaction
            # — +60% gather time, byte-bound at 40+ rows; and gathering
            # these blocks in the pool's int16 aux dtype — TPU column
            # gathers are element/latency-bound, i16 made them SLOWER
            # (+18%), so the narrow dtype lives only at the pool
            # boundary, see step())
            aux_plus = jnp.concatenate([caux, sched, lb2h], axis=0)
            children, aux_plus = _tiered_compact(
                _take_block(children, aux_plus), permh, nkeep, N,
                two_phase=True, cap=W_)
            # barrier: the tail sweep's pallas call must see the
            # mid-compaction's switch outputs materialized — without
            # this, XLA's fusion of the slice chain miscompiles the
            # compiled (jitted) step on TPU and the tail sweep reads
            # stale columns, silently over-pruning (eager and
            # debug-tapped traces are correct — caught by
            # test_prefilter_branch_matches_oracle on hardware)
            aux_plus = jax.lax.optimization_barrier(aux_plus)
            caux = aux_plus[:M + 1]
            sched = aux_plus[M + 1:M + 1 + SW]
            lb2h_c = aux_plus[M + 1 + SW:M + 2 + SW]
        with jax.named_scope("bound"):
            lb2t = _sweep_tiers(tail_t, caux[:M], sched, nkeep, N, J, M)
            lb2b = jnp.maximum(lb2h_c, lb2t)
        live = nkeep

    with jax.named_scope("prune"):
        push = ((jnp.arange(W_) < live)
                & (lb2b.reshape(-1) < best))
        n_push = push.sum(dtype=jnp.int32)
    if TELE:
        # branched buckets + bound histograms, computed while caux
        # still aligns column-for-column with push/lb2b (the final
        # compaction reorders)
        pb = tele.depth_bucket(
            caux[M].astype(jnp.int32).reshape(-1) - 1, J)
        live_m = jnp.arange(W_) < live
        tele_tail = jnp.concatenate([
            tele.bucket_counts(pb, push),
            head_hp + tele.bound_hist(lb2b, live_m & ~push, best),
            tele.bound_hist(lb2b, push, best)])
    if debug_tap:
        # smuggle intermediates out via the balance counters
        lv = jnp.arange(W_) < live
        hsum = jnp.where(lv, lb2h_c.reshape(-1),
                         0).sum(dtype=jnp.int64)
        tsum = jnp.where(lv, lb2t.reshape(-1),
                         0).sum(dtype=jnp.int64)
    else:
        hsum = tsum = jnp.int64(0)

    # final compaction: direct prefix gather of the already-built
    # block (sources are the compacted (features, W_) arrays)
    with jax.named_scope("compact"):
        perm2 = _partition_prefix(push, live, N, two_phase=True, cap=W_)
        children, child_aux = _tiered_compact(
            _take_block(children, caux), perm2, n_push, N,
            two_phase=True, cap=W_)
        child_depth = child_aux[M].astype(jnp.int16)

    # pool write inside the branch: the written block is W_-wide, so
    # the steady branch moves a quarter of the bytes (_write_block
    # owns the overflow scratch-margin routing, shared with the common
    # path)
    with jax.named_scope("push"):
        prmu, depth, aux = _write_block(
            state, children, child_depth, child_aux, start, n_push,
            limit)
    out = (prmu, depth, aux, n_push, hsum, tsum)
    if TELE:
        out += (tele_tail,)
    return out


def _leaf_scan(tables: BoundTables, p_prmu, p_depth, p_aux, valid):
    """Parent-level leaf/eval statistics of one popped chunk — the
    dense-grid quantities the unfused routes read off the (1, N) child
    masks, computed in O(M*B) without materializing them (the fused
    route's whole point is that the dense grid never exists in HBM).

    A parent at depth J-1 has exactly ONE valid child (slot J-1), a
    complete schedule; its LB1 as the kernels compute it is the chain
    max_k(tmp_k + min_tails[k]) with every child-remain term zero —
    replicated here term for term so `leaf_best` is bit-identical to
    the dense route's masked min over leaf columns. Parents below J-1
    contribute J - depth evaluated (all non-leaf) children; a parent
    at J-1 contributes its one leaf. Returns
    (leaf_best i32, n_leaf i64, evals i64)."""
    J, B = p_prmu.shape
    M = p_aux.shape[0]
    d = p_depth.reshape(-1)                        # (B,) i32
    leafp = (d == J - 1) & valid
    # the lone unscheduled job of a depth-(J-1) parent sits at
    # position J-1; its processing column via the J-step select
    # (_regather's gather-free idiom)
    a = p_prmu[J - 1:J, :].astype(jnp.int32)       # (1, B)
    cp = jnp.zeros((M, B), jnp.int32)
    for j in range(J):
        cp = jnp.where(a == j, tables.p[:, j:j + 1], cp)
    cf = p_aux[0:1] + cp[0:1]
    tmp = cf
    lb = tmp + tables.min_tails[0]
    for k in range(1, M):
        cf = jnp.maximum(cf, p_aux[k:k + 1]) + cp[k:k + 1]
        tmp = jnp.maximum(tmp, cf)
        lb = jnp.maximum(lb, tmp + tables.min_tails[k])
    leaf_best = jnp.where(leafp, lb.reshape(-1), I32_MAX).min()
    n_leaf = leafp.sum(dtype=jnp.int64)
    evals = jnp.where(valid, (J - d).astype(jnp.int64), 0).sum()
    return leaf_best, n_leaf, evals


def _fused_step(tables: BoundTables, lb_kind: int, route, chunk: int,
                TB: int, state: SearchState, p_prmu, p_depth, p_aux,
                n, start, valid, limit, mode: str) -> SearchState:
    """The fused bound+prune+compact route (ops/pallas_fused): the
    dense child grid, its (1, N) bound row, the (N,) prune mask and
    the (N,) partition keys never exist in HBM. The kernel emits the
    compacted survivors (capped at the steady W = N/4 frame) plus a
    count; leaves and eval totals come from the parent-level O(M*B)
    scan (_leaf_scan); a rare survivor-overflow step (count > W) takes
    the unfused pipeline via ONE lax.cond on bit-identical bound math,
    so the explored set cannot depend on which branch ran. For LB2 the
    kernel is the fused LB1 prefilter (also emitting the survivors'
    scheduled-set bitmask) and the shared _lb2_tail runs the pair
    sweeps over the compacted block — op-identical to the unfused
    two-phase route. Telemetry: popped/evaluated buckets are
    parent-level, branched buckets and the surviving-bound histogram
    come off the compacted block, and the PRUNED-bound histogram is
    the kernel's per-tile masked-add output — bound_hist_exact holds
    without the pruned bounds ever touching HBM."""
    J, capacity = state.prmu.shape
    M = tables.p.shape[0]
    B = chunk
    G = B // TB
    N = B * J
    TELE = state.telemetry.shape[-1] > 0

    with jax.named_scope("prune"):
        leaf_best, n_leaf, evals_cnt = _leaf_scan(tables, p_prmu, p_depth,
                                                  p_aux, valid)
        best = jnp.minimum(state.best, leaf_best)
        sol = state.sol + n_leaf
    if TELE:
        d = p_depth.reshape(-1)
        wb = tele.depth_bucket(d, J)
        popped_b = tele.bucket_counts(wb, valid)
        # evaluated non-leaf children bucket by PARENT depth: J - d of
        # them per valid parent below J-1, none at J-1 (its one child
        # is the leaf) — the dense route's bucket_counts(child_b,
        # mask & ~leaf) collapsed to parent-level weighted sums
        w = jnp.where(valid & (d < J - 1), (J - d).astype(jnp.int64), 0)
        evalnl_b = jnp.stack([jnp.sum(jnp.where(wb == k, w, 0))
                              for k in range(tele.DEPTH_BUCKETS)])

    # Survivor-cap width: the LB2 route caps at the steady N/4 frame
    # (matching the unfused tail's steady branch; the rare overflow
    # takes the spill cond below). The LB1 route runs uncapped — its
    # unfused pipeline block-writes a full-N frame anyway, so a narrow
    # cap would buy no frame bytes while costing a whole duplicated
    # spill pipeline in the compiled program (MEASURED: capping LB1 at
    # N/4 was a net LOSS, -8% vs +17% step-temp — the spill branch's
    # dense pipeline and the kernel outputs are live across the cond
    # boundary, so buffer assignment cannot overlay them).
    if lb_kind == 2:
        W = max(N // 4, 128)
        narrow = W < N
        if not narrow:
            W = N
    else:
        W = N
        narrow = False
    # survivors-only frames as narrow as their consumers allow: the
    # bound row only feeds the LB1 telemetry histogram (the LB2 tail
    # re-bounds survivors with the pair sweeps), and the LB1 caux
    # block can ride the pool's own narrow aux dtype — every output
    # byte of the kernel is the fused route's whole HBM footprint
    with jax.named_scope("bound"):
        kch, kaux, kbnd, ksched, n_surv, khist = pallas_fused.fused_expand(
            tables, p_prmu, p_depth, p_aux, n, best, lb_kind=1, tile=TB,
            cap_width=W, with_sched=(route == "prefilter"),
            tele_bins=tele.BOUND_BINS if TELE else 0,
            with_bounds=(lb_kind != 2 and TELE),
            aux_i16=(lb_kind != 2 and state.aux.dtype == jnp.int16),
            interpret=(mode == "interpret"))
    if limit is None:
        limit = row_limit(capacity, B, J)

    def dense_masks():
        """The unfused routes' mask family (_child_masks — the same
        ops step() traces) — built ONLY inside the rare spill
        branches."""
        depth_c, mask = _child_masks(p_depth, valid, G, J, TB)
        is_leaf = ((depth_c + 1) == J) & mask
        return depth_c, mask, is_leaf

    def narrow_to_W(a, rows):
        """The kernel block at frame width W. The kernel's frame is
        always WPAD = W + store_sub(J*tile): the count-gated tail
        stores carry one sub-block of slack past the survivor cap, so
        every fused step pays this slice — a copy of each output at
        width W. That cost is priced in (the measured HBM wins
        include it); store_sub exists precisely to keep the slack —
        and therefore this copy's source frame — one ~N/8 sub-block
        instead of a whole tile. Clamping the kernel's final stores
        to land the frame at exactly W would retire the copy; that is
        hardware-round work (the cursor stores are being relowered
        through Mosaic anyway, ROADMAP item 4)."""
        if a.shape[1] == W:
            return a
        return jax.lax.slice(a, (0, 0), (rows, W))

    if lb_kind != 2:
        def fused_fit(_):
            with jax.named_scope("push"):
                children = narrow_to_W(kch, J)
                caux = narrow_to_W(kaux, M + 1)
                child_depth = caux[M].astype(jnp.int16)
                prmu, depth, aux = _write_block(
                    state, children, child_depth, caux, start, n_surv,
                    limit)
            out = (prmu, depth, aux, n_surv)
            if TELE:
                bnd = narrow_to_W(kbnd, 1)
                livem = jnp.arange(W) < n_surv
                pb = tele.depth_bucket(
                    caux[M].astype(jnp.int32).reshape(-1) - 1, J)
                out += (jnp.concatenate(
                    [tele.bucket_counts(pb, livem),
                     tele.bound_hist(bnd, livem, best)]),)
            return out

        # LB1 runs uncapped (W == N, see the cap comment above):
        # n_surv can never exceed the frame, so there is no spill
        # branch to trace — only the LB2 route carries one
        outs = fused_fit(0)
        prmu, depth, aux, n_push = outs[:4]
        delta = None
        if TELE:
            DB = tele.DEPTH_BUCKETS
            bh = outs[4]
            delta = tele.step_delta(popped_b, bh[:DB],
                                    evalnl_b - bh[:DB],
                                    khist, bh[DB:])
        with jax.named_scope("push"):
            return _commit(state, prmu, depth, aux, n_push, best, sol,
                           jnp.asarray(evals_cnt), limit, start,
                           tele_delta=delta)

    # --- route == "prefilter": the kernel was the fused LB1 prefilter
    P = int(tables.ma0.shape[0])
    KH = batched.PAIR_PREFILTER
    SW = pallas_expand.sched_words(J)
    debug_tap = bool(__debug__ and P > KH and _DEBUG_STEP)
    ncand = n_surv

    def fused_tail(_):
        with jax.named_scope("compact"):
            children = narrow_to_W(kch, J)
            caux = narrow_to_W(kaux, M + 1)
            sched = narrow_to_W(ksched, SW)
        return _lb2_tail(tables, state, children, caux, sched, ncand,
                         W, N, best, start, limit, debug_tap, TELE)

    def spill_tail(_):
        with jax.named_scope("bound"):
            lb1b = pallas_expand.expand_bounds(
                tables, p_prmu, p_depth, p_aux, lb_kind=1, tile=TB)
        with jax.named_scope("prune"):
            _, mask, is_leaf = dense_masks()
            cand = (mask & ~is_leaf & (lb1b < best)).reshape(-1)
        with jax.named_scope("compact"):
            perm1 = _partition(cand)
            children, caux, sched = _compact_from_parents(
                tables, p_prmu, p_depth, p_aux, perm1, ncand, TB, N,
                with_sched=True, two_phase=True, cap=N)
        return _lb2_tail(tables, state, children, caux, sched, ncand,
                         N, N, best, start, limit, debug_tap, TELE)

    if narrow:
        outs = jax.lax.cond(ncand <= W, fused_tail, spill_tail, 0)
    else:
        outs = fused_tail(0)
    prmu, depth, aux, n_push, hsum, tsum = outs[:6]
    if debug_tap:
        state = state._replace(sent=hsum, recv=tsum,
                               steals=n_push.astype(jnp.int64))
    delta = None
    if TELE:
        DB, BB = tele.DEPTH_BUCKETS, tele.BOUND_BINS
        branched_b = outs[6][:DB]
        delta = tele.step_delta(
            popped_b, branched_b, evalnl_b - branched_b,
            khist + outs[6][DB:DB + BB], outs[6][DB + BB:])
    with jax.named_scope("push"):
        return _commit(state, prmu, depth, aux, n_push, best, sol,
                       jnp.asarray(evals_cnt), limit, start,
                       tele_delta=delta)


def step(tables: BoundTables, lb_kind: int, chunk: int,
         state: SearchState, tile: int = 1024,
         limit: int | None = None, fused: str = "off") -> SearchState:
    """One pop->bound->prune->branch cycle (the compiled analogue of the
    reference per-thread hot loop, pfsp_multigpu_cuda.c:221-320).

    `limit` tightens the usable-row bound below the default
    row_limit(capacity, chunk, jobs) — the distributed loop reserves
    extra headroom above it so balance-round block writes stay in bounds
    (engine/distributed._balance_round)."""
    J, capacity = state.prmu.shape
    B = chunk
    assert capacity >= B, f"pool capacity {capacity} < chunk {B}"
    M = tables.p.shape[0]
    assert state.aux.shape[0] == M, (
        f"pool aux width {state.aux.shape[0]} != machines {M}: "
        "seed the state with init_state(..., p_times=...) so it carries "
        "the per-node front tables")
    # the tile ALSO defines the expand outputs' column order — derived
    # through the same single functions expand() uses; lb2_route owns
    # the LB2 route/tile choice (dense vs prefilter, including the
    # LB1-tile retry for the 100-job classes whose register pair kernel
    # is gated off — measured on ta071/ta081 in round 4)
    if lb_kind == 2:
        route, TB, _ = lb2_route(J, M, int(tables.ma0.shape[0]), B, tile)
    else:
        route = None
        TB = pallas_expand.effective_tile(J, B, tile, lb_kind, machines=M)
    G = B // TB
    N = B * J

    with jax.named_scope("pop"):
        p_prmu, p_depth, p_aux, n, start, valid = pop_chunk(state, B, M)
        # The pool stores aux in the narrow per-instance dtype
        # (aux_dtype: int16 for every class whose completion times
        # fit); intra-step blocks are all i32 — measured on v5e: TPU
        # column gathers are element/latency-bound, so narrow GATHERS
        # buy nothing (+18% step time when tried), while the sequential
        # push block-write IS byte-bound and pays half, and the balance
        # all_to_all + checkpoint + pool HBM footprint halve too. The
        # cast back happens at the write below.
        p_aux = p_aux.astype(jnp.int32)

    # --- fused bound+prune+compact route (ops/pallas_fused): STATIC
    # gate — `fused` is a static argument threaded from the host-side
    # mode resolution (never an env read at trace time); fused_ok
    # admits only the interpreter route (no hardware route lowers
    # yet). LB2's dense (few-pair) route and LB1_d stay unfused.
    if (fused != "off"
            and pallas_fused.fused_ok(fused, lb_kind)
            and (lb_kind == 1 or route == "prefilter")):
        return _fused_step(tables, lb_kind, route, B, TB, state,
                           p_prmu, p_depth, p_aux, n, start, valid,
                           limit, fused)

    # --- masks in the kernel's child-slot column order (shared with
    # the fused spill branches — _child_masks)
    with jax.named_scope("bound"):
        depth_c, mask = _child_masks(p_depth, valid, G, J, TB)  # (1, N)

    # --- search telemetry (STATIC Python branch: with the block off the
    # traced program contains zero telemetry ops). Common inputs shared
    # by every route: popped parents and evaluated non-leaf children by
    # relative-depth bucket; each route supplies its branched buckets
    # and bound histograms, pruned = evaluated - branched by exactness
    # of the per-route accounting (tests pin the bucket sums).
    TELE = state.telemetry.shape[-1] > 0
    if TELE:
        is_leaf_c = ((depth_c + 1) == J) & mask
        child_b = tele.depth_bucket(depth_c.reshape(-1), J)
        popped_b = tele.bucket_counts(
            tele.depth_bucket(p_depth.reshape(-1), J), valid)
        evalnl_b = tele.bucket_counts(
            child_b, (mask & ~is_leaf_c).reshape(-1))

    P = int(tables.ma0.shape[0]) if lb_kind == 2 else 0
    KH = batched.PAIR_PREFILTER
    if route == "dense":
        # One-shot dense LB2 for the FEW-PAIR classes (P <= 2*KH — no
        # prefilter tier exists): sweep all P pairs over the dense child
        # grid and compact ONCE. The two-phase detour assumes the LB1
        # pre-prune removes most of the grid; in the weak-bound regimes
        # these classes live in (ta031: 50x5, LB1 removes only ~27%) it
        # removed almost nothing while its full-width regather+sort ran
        # anyway — measured 10x slower per pushed node than ta021. With
        # P this small the dense sweep costs less than the detour even
        # when LB1 WOULD have pruned well (20x5: a wash), so the route
        # is static. The explored set is identical either way (the final
        # prune uses the same exact LB2 values), matching the
        # reference's single code path (bounds_gpu.cu:252-316).
        with jax.named_scope("bound"):
            _, _, lb2b = pallas_expand.expand(
                tables, p_prmu, p_depth, p_aux, lb_kind=2, tile=TB)

        with jax.named_scope("prune"):
            is_leaf = ((depth_c + 1) == J) & mask
            sol = state.sol + is_leaf.sum(dtype=jnp.int64)
            # a complete schedule's LB2 == its makespan
            leaf_best = jnp.where(is_leaf, lb2b, I32_MAX).min()
            best = jnp.minimum(state.best, leaf_best)

            push = (mask & ~is_leaf
                    & (lb2b.reshape(1, -1) < best)).reshape(-1)
            n_push = push.sum(dtype=jnp.int32)
        if TELE:
            branched_b = tele.bucket_counts(child_b, push)
            hist_surv = tele.bound_hist(lb2b, push, best)
            hist_pruned = tele.bound_hist(
                lb2b, (mask & ~is_leaf).reshape(-1) & ~push, best)

        # Compaction rebuilds survivors from the CHUNK-WIDE parents
        # (_compact_from_parents) rather than gathering the dense
        # (rows, N) child blocks the kernel materialized: at the wide
        # classes this route serves (50x5: N = 1.64M at chunk 32768)
        # the dense frame sits far past the v5e source-width gather
        # cliff (tools/bench_gather.py), while the parent sources stay
        # 32k wide. The expand kernel's children/aux outputs are dead
        # here (lb2 sweeps run on the kernel's internal fronts) — their
        # materialization is cheap relative to the cliff-priced dense
        # gathers this replaces (measured: ta033 1.21M -> 1.65M
        # pushed/s).
        with jax.named_scope("compact"):
            perm = _partition(push)
            children, child_aux = _compact_from_parents(
                tables, p_prmu, p_depth, p_aux, perm, n_push, TB, N,
                two_phase=True)
            child_depth = child_aux[M].astype(jnp.int16)
    elif route == "prefilter":
        # Two-phase LB2 (TPU): bound every child with the near-free LB1
        # first (LB1 <= LB2, so LB1-pruning is sound and the explored
        # set stays the exact LB2 set), rebuild only the survivors from
        # their parents (regather), and run the expensive pair-sweep
        # kernel only over the smallest prefix tier that covers them. At
        # UB=opt LB1 removes ~85% of the child grid. The reference gets
        # its version of this saving from the per-child early exit the
        # vector unit cannot take (c_bound_johnson.c:231-233).
        with jax.named_scope("bound"):
            lb1b = pallas_expand.expand_bounds(
                tables, p_prmu, p_depth, p_aux, lb_kind=1, tile=TB)

        with jax.named_scope("prune"):
            is_leaf = ((depth_c + 1) == J) & mask
            sol = state.sol + is_leaf.sum(dtype=jnp.int64)
            # a complete schedule's LB1 == LB2 == its makespan
            leaf_best = jnp.where(is_leaf, lb1b, I32_MAX).min()
            best = jnp.minimum(state.best, leaf_best)

            cand = (mask & ~is_leaf & (lb1b < best)).reshape(-1)
            ncand = cand.sum(dtype=jnp.int32)
        if TELE:
            # children the LB1 prefilter pruned, binned at the bound
            # that pruned them (the tail sweep's prunes bin at their
            # exact LB2 inside the pipeline)
            hist_lb1_pruned = tele.bound_hist(
                lb1b, (mask & ~is_leaf).reshape(-1) & ~cand, best)

        with jax.named_scope("compact"):
            perm1 = _partition(cand)
        debug_tap = bool(__debug__ and P > KH and _DEBUG_STEP)
        if limit is None:
            limit = row_limit(capacity, B, J)

        def tail_pipeline(W_):
            """Everything after the LB1 prune, in W_-wide frames
            (_lb2_tail — shared with the fused route so the two cannot
            drift).

            Run twice as the two branches of ONE lax.cond: the steady
            branch at W_ = N//4 (taken whenever ncand fits, ~93% of
            ta021 steady-state iterations) and the safe branch at
            W_ = N. On v5e the gather cost cliff sits on the SOURCE
            width (tools/bench_gather.py: t=61440 costs 0.69 ms from a
            164k-wide source vs 4.0 ms from a 655k-wide one), so the
            steady branch's blocks are BORN narrow — its compaction
            gathers read N//4-wide sources, its pads/copies and the
            final pool block write shrink 4x. Slicing the sources of a
            full-width pipeline instead was measured WORSE than the
            round-3 baseline (the slice ops break XLA's gather+pad
            fusions and re-materialize every block: 43.6M -> 34.0M
            evals/s), which is why the narrow width is threaded through
            the whole pipeline rather than applied at the gathers."""
            def f(_):
                with jax.named_scope("compact"):
                    children, caux, sched = _compact_from_parents(
                        tables, p_prmu, p_depth, p_aux, perm1, ncand, TB,
                        N, with_sched=True, two_phase=True, cap=W_)
                return _lb2_tail(tables, state, children, caux, sched,
                                 ncand, W_, N, best, start, limit,
                                 debug_tap, TELE)
            return f

        # N/4 cap: ncand hovers just under it on the 20x20 class
        # (~0.93 N/4 steady state; ~7% of iterations exceed it and take
        # a wider branch). A 5N/16 cap was measured very slightly
        # WORSE (47.4M vs 47.9M): widening every steady-branch frame
        # costs more than the rare safe branch saves. Instead the
        # overflow iterations get a MIDDLE 3N/8 frame (a lax.switch
        # rung): they ran the full-N pipeline at ~2x the steady cost,
        # and nearly all of them fit 3N/8 — the steady branch stays
        # untouched (measured on ta021: 48.7 -> 51.0M evals/s).
        W = max(N // 4, 128)
        W2 = 3 * N // 8
        if W >= N:  # toy shapes: no narrow branch exists
            outs = tail_pipeline(N)(0)
        elif W2 <= W or W2 >= N or W2 % 128 != 0:
            outs = jax.lax.cond(
                ncand <= W, tail_pipeline(W), tail_pipeline(N), 0)
        else:
            sel = ((ncand > W).astype(jnp.int32)
                   + (ncand > W2).astype(jnp.int32))
            outs = jax.lax.switch(
                sel, [tail_pipeline(W), tail_pipeline(W2),
                      tail_pipeline(N)], 0)
        prmu, depth, aux, n_push, hsum, tsum = outs[:6]

        if debug_tap:
            state = state._replace(sent=hsum, recv=tsum,
                                   steals=n_push.astype(jnp.int64))
        delta = None
        if TELE:
            DB, BB = tele.DEPTH_BUCKETS, tele.BOUND_BINS
            branched_b = outs[6][:DB]
            delta = tele.step_delta(
                popped_b, branched_b, evalnl_b - branched_b,
                hist_lb1_pruned + outs[6][DB:DB + BB],
                outs[6][DB + BB:])
        with jax.named_scope("push"):
            return _commit(state, prmu, depth, aux, n_push, best, sol,
                           mask, limit, start, tele_delta=delta)
    else:
        # --- bounds of the dense child grid (Pallas on TPU; the children
        # themselves are never materialized — survivors are rebuilt from
        # their parents below)
        with jax.named_scope("bound"):
            bounds = pallas_expand.expand_bounds(
                tables, p_prmu, p_depth, p_aux, lb_kind=lb_kind, tile=TB)

        with jax.named_scope("prune"):
            # --- leaves: complete schedules; count + tighten incumbent
            # (reference: the depth==jobs branch of decompose,
            # PFSP_lib.c:24-32)
            is_leaf = ((depth_c + 1) == J) & mask
            sol = state.sol + is_leaf.sum(dtype=jnp.int64)
            leaf_best = jnp.where(is_leaf, bounds, I32_MAX).min()
            best = jnp.minimum(state.best, leaf_best)

            # --- prune + push surviving internal children
            push = (mask & ~is_leaf & (bounds < best)).reshape(-1)
            n_push = push.sum(dtype=jnp.int32)
        if TELE:
            branched_b = tele.bucket_counts(child_b, push)
            hist_surv = tele.bound_hist(bounds, push, best)
            hist_pruned = tele.bound_hist(
                bounds, (mask & ~is_leaf).reshape(-1) & ~push, best)

        # Compaction: stable-partition the surviving column indices to
        # the front (_partition), rebuild those children from their
        # parents (_compact_from_parents), then write the whole block
        # contiguously at `start`. A per-node compacting scatter costs
        # ~100x more on TPU (it serializes row updates); the garbage
        # columns past n_push land above the cursor and are never read.
        # The top chunk*J rows of the pool are a scratch margin (see
        # row_limit) so the block write stays in bounds even when the
        # live region is full.
        with jax.named_scope("compact"):
            perm = _partition(push)
            children, child_aux = _compact_from_parents(
                tables, p_prmu, p_depth, p_aux, perm, n_push, TB, N)
            child_depth = child_aux[M].astype(jnp.int16)

    if limit is None:
        limit = row_limit(capacity, B, J)
    with jax.named_scope("push"):
        prmu, depth, aux = _write_block(state, children, child_depth,
                                        child_aux, start, n_push, limit)
    delta = (tele.step_delta(popped_b, branched_b,
                             evalnl_b - branched_b,
                             hist_pruned, hist_surv)
             if TELE else None)
    with jax.named_scope("push"):
        return _commit(state, prmu, depth, aux, n_push, best, sol, mask,
                       limit, start, tele_delta=delta)


@functools.partial(jax.jit,
                   static_argnames=("lb_kind", "chunk", "tile", "fused"))
def _run(tables: BoundTables, state: SearchState, lb_kind: int, chunk: int,
         max_iters: jax.Array, drain_min: jax.Array,
         tile: int = 1024, fused: str = "off") -> SearchState:
    def cond(s: SearchState):
        return (s.size >= drain_min) & ~s.overflow & (s.iters < max_iters)

    body = functools.partial(step, tables, lb_kind, chunk, tile=tile,
                             fused=fused)
    return jax.lax.while_loop(cond, lambda s: body(state=s), state)


def run(tables: BoundTables, state: SearchState, lb_kind: int, chunk: int,
        max_iters: int | None = None, tile: int = 1024,
        drain_min: int = 1, fused=None) -> SearchState:
    """Run the search to exhaustion (or up to a cumulative `max_iters`) in
    one compiled loop (the analogue of pfsp_c.c:55-63's while(1)
    pop+decompose). `max_iters` is a traced scalar, NOT a static argument:
    segmented drivers pass a new ceiling every segment and must hit the
    compile cache. `fused` (None = the TTS_FUSED env resolution,
    ops/pallas_fused.resolve_mode) is resolved HERE, host-side, and rides
    the jit key as a static mode string — flipping the knob retraces
    instead of reusing a stale executable."""
    jobs, capacity = state.prmu.shape[-2:]
    if int(np.asarray(state.size).max()) > row_limit(capacity, chunk, jobs):
        # Pool already fuller than the usable limit (e.g. capacity < the
        # chunk*jobs scratch margin): report overflow without touching
        # anything — the caller grows the pool and resumes losslessly.
        return state._replace(overflow=jnp.asarray(True))
    ceiling = (jnp.iinfo(state.iters.dtype).max if max_iters is None
               else max_iters)
    return _run(tables, state, lb_kind, chunk,
                jnp.asarray(ceiling, dtype=state.iters.dtype),
                jnp.asarray(max(drain_min, 1), dtype=jnp.int32), tile=tile,
                fused=pallas_fused.resolve_mode(fused))


def generic_step(problem, tables, lb_kind: int, chunk: int,
                 state: SearchState, tile: int = 1024,
                 limit: int | None = None) -> SearchState:
    """One problem-generic pop -> branch -> bound -> prune -> compact
    cycle, parameterized by the plugin protocol (problems/base.Problem):
    the plugin supplies the dense child grid (`branch`) and the child
    bound values (`bound`); everything else — pool pop, incumbent and
    solution accounting, stable-partition compaction, the scratch-margin
    overflow contract and the telemetry block — is shared engine code.

    This is the default `Problem.make_step` pipeline (N-Queens, TSP,
    knapsack); PFSP overrides the hook with the specialized two-phase
    Pallas pipeline above (`step`). The N-Queens instantiation is
    op-for-op the pipeline the deleted `engine/nqueens_device.nq_step`
    ran (same pop, same stable argsort partition, same block write and
    overflow guard), so node/sol/evals counts are bit-identical to the
    pre-refactor fork — pinned by the parity suite.

    `tile` is accepted for signature parity with the fast-path hook and
    ignored (the generic pipeline has no kernel tiling)."""
    del tile
    J, capacity = state.prmu.shape
    A = state.aux.shape[0]
    B = chunk

    n_pop = jnp.minimum(state.size, B)
    start = state.size - n_pop
    valid = jnp.arange(B) < n_pop
    zero = jnp.zeros((), start.dtype)
    p_prmu = jax.lax.dynamic_slice(state.prmu, (zero, start), (J, B))
    depth = jnp.where(
        valid,
        jax.lax.dynamic_slice(state.depth, (start,), (B,)).astype(jnp.int32),
        0)
    p_aux = jax.lax.dynamic_slice(state.aux, (zero, start), (A, B)) \
        .astype(jnp.int32)

    sol = state.sol
    if not problem.leaf_in_evals:
        # N-Queens-style accounting: a popped complete node is a
        # solution (reference: nqueens_c.c:104-106); children at full
        # depth are pushed like any survivor
        sol = sol + ((depth == J) & valid).sum(dtype=jnp.int64)

    br = problem.branch(tables, p_prmu, depth, p_aux, valid)
    C = br.children.shape[1]
    assert C <= B * (problem.branch_factor or J), (
        f"branch grid {C} wider than the chunk*branching scratch "
        f"margin {B * (problem.branch_factor or J)}: the overflow "
        "block write would run out of bounds")
    bounds = problem.bound(tables, lb_kind, br, state.best).reshape(-1)
    evaluated = br.evaluated.reshape(-1)
    if problem.leaf_in_evals:
        # PFSP-style: every evaluated leaf child counts, the incumbent
        # tightens from leaf bounds (bound == objective at leaves), and
        # leaves are never pushed
        is_leaf = evaluated & problem.is_leaf_cols(tables, br).reshape(-1)
        sol = sol + is_leaf.sum(dtype=jnp.int64)
        leaf_best = jnp.where(is_leaf, bounds, I32_MAX).min()
        best = jnp.minimum(state.best, leaf_best)
        push = evaluated & ~is_leaf & (bounds < best)
    else:
        is_leaf = jnp.zeros_like(evaluated)
        best = state.best
        push = evaluated & (bounds < best)
    n_push = push.sum(dtype=jnp.int32)
    tree = state.tree + n_push.astype(jnp.int64)

    # stable-partition survivors to the front, block-write at the
    # cursor (scatter-free push; the same scheme as step/nq_step)
    order = jnp.argsort(~push, stable=True)
    children = jnp.take(br.children, order, axis=1)
    child_depth = jnp.take(br.child_depth, order)
    child_aux = jnp.take(br.child_aux, order, axis=1)

    if limit is None:
        limit = problem.usable_rows(capacity, B, J)
    new_size = start + n_push
    overflow = new_size > limit
    write_at = jnp.where(overflow, jnp.asarray(limit, start.dtype), start)
    keep = lambda new, old: jnp.where(overflow, old, new)  # noqa: E731
    evals = state.evals + evaluated.sum(dtype=jnp.int64)
    telem = state.telemetry
    if telem.shape[-1] > 0:
        # child buckets bin by PARENT depth (= child_depth - 1), the
        # same convention as step()/the deleted nq_step; the bound
        # histograms bin every pruned/surviving child so the audit's
        # bound_hist_exact invariant holds for every problem (unbounded
        # problems' 0 / I32_MAX sentinel bounds land in fixed bins)
        cb = tele.depth_bucket(br.child_depth.astype(jnp.int32) - 1, J)
        pruned_m = evaluated & ~is_leaf & ~push
        delta = tele.step_delta(
            tele.bucket_counts(tele.depth_bucket(depth, J), valid),
            tele.bucket_counts(cb, push),
            tele.bucket_counts(cb, pruned_m),
            tele.bound_hist(bounds, pruned_m, best),
            tele.bound_hist(bounds, push, best))
        telem = keep(tele.commit(telem, delta, new_size, best,
                                 state.best, state.iters), telem)
    return state._replace(
        prmu=jax.lax.dynamic_update_slice(state.prmu, children,
                                          (zero, write_at)),
        depth=jax.lax.dynamic_update_slice(state.depth, child_depth,
                                           (write_at,)),
        aux=jax.lax.dynamic_update_slice(
            state.aux, child_aux.astype(state.aux.dtype),
            (zero, write_at)),
        size=keep(new_size, state.size),
        best=keep(best, state.best),
        tree=keep(tree, state.tree),
        sol=keep(sol, state.sol),
        iters=state.iters + 1,
        evals=keep(evals, state.evals),
        overflow=state.overflow | overflow,
        telemetry=telem,
    )


@functools.partial(jax.jit,
                   static_argnames=("problem", "lb_kind", "chunk", "tile",
                                    "fused"))
def _run_problem(tables, state: SearchState, problem, lb_kind: int,
                 chunk: int, max_iters: jax.Array, drain_min: jax.Array,
                 tile: int = 1024, fused: str = "off") -> SearchState:
    def cond(s: SearchState):
        return (s.size >= drain_min) & ~s.overflow & (s.iters < max_iters)

    body = problem.make_step(tables, lb_kind, chunk, tile, None,
                             fused=fused)
    return jax.lax.while_loop(cond, lambda s: body(s), state)


def run_problem(problem, tables, state: SearchState, lb_kind: int,
                chunk: int, max_iters: int | None = None,
                tile: int = 1024, drain_min: int = 1,
                fused=None) -> SearchState:
    """Problem-generic `run`: the plugin's step (fast-path hook or
    generic_step) to exhaustion in one compiled loop. `max_iters` is a
    traced scalar like run()'s — segmented drivers hit the compile
    cache across ceilings. `fused` resolves like run()'s (host-side,
    static on the jit key); plugins without a fused fast path ignore
    it."""
    jobs, capacity = state.prmu.shape[-2:]
    if int(np.asarray(state.size).max()) > \
            problem.usable_rows(capacity, chunk, jobs):
        # as in run(): flag overflow without touching anything — the
        # caller grows the pool and resumes losslessly (same margin
        # rule as generic_step's default limit: the two must agree, or
        # a seeded state could sit past the scratch rows a step writes)
        return state._replace(overflow=jnp.asarray(True))
    ceiling = (jnp.iinfo(state.iters.dtype).max if max_iters is None
               else max_iters)
    return _run_problem(tables, state, problem, lb_kind, chunk,
                        jnp.asarray(ceiling, dtype=state.iters.dtype),
                        jnp.asarray(max(drain_min, 1), dtype=jnp.int32),
                        tile=tile, fused=pallas_fused.resolve_mode(fused))


def solve(problem, table: np.ndarray, lb_kind: int | None = None,
          init_ub: int | None = None, chunk: int = 64,
          capacity: int | None = None, max_iters: int | None = None,
          tile: int = 1024) -> SearchResult:
    """Single-device host entry for ANY registered problem: build the
    plugin's tables, seed the pool from its root, run to exhaustion
    with lossless grow-on-overflow (checkpoint.grow — the same recovery
    path search() uses). `problem` is a plugin object or a registry
    name."""
    from . import checkpoint

    if isinstance(problem, str):
        from .. import problems as problems_pkg
        problem = problems_pkg.get(problem)
    table = np.asarray(table)
    if lb_kind is None:
        lb_kind = problem.default_lb
    tables = problem.make_tables(table)
    jobs = problem.slots(table)
    if capacity is None:
        capacity = problem.default_capacity(table)
    prmu0, depth0 = problem.root(table)
    state = init_state(jobs, capacity, init_ub, prmu0=prmu0,
                       depth0=depth0,
                       aux0=problem.seed_aux(table, prmu0, depth0))
    while True:
        out = run_problem(problem, tables, state, lb_kind, chunk,
                          max_iters, tile=tile)
        if not bool(out.overflow):
            return SearchResult(
                explored_tree=int(out.tree), explored_sol=int(out.sol),
                best=int(out.best), iters=int(out.iters),
                evals=int(out.evals), overflow=False,
                complete=int(out.size) == 0,
            )
        capacity *= 2
        state = checkpoint.grow(out, capacity)


def default_capacity(jobs: int, machines: int, floor: int = 1 << 18) -> int:
    """Pool-capacity pre-sizing by instance class. The weak-bound
    few-machine classes (ta031-class 50x5) hold ~11M live rows at their
    peak (measured in round 2); starting at the generic default
    costs six doubling cycles, each a fetch + re-home + recompile.
    Large-but-strong classes get one free doubling step instead."""
    if jobs >= 40 and machines <= 8:
        return max(1 << 24, floor)
    if jobs >= 40 or machines <= 8:
        return max(1 << 20, floor)
    return floor


class SearchResult(NamedTuple):
    explored_tree: int
    explored_sol: int
    best: int
    iters: int
    evals: int
    overflow: bool
    complete: bool = True  # pool drained (False: max_iters truncation)


def search(p_times: np.ndarray, lb_kind: int = 1, init_ub: int | None = None,
           chunk: int = 64, capacity: int = 1 << 18,
           max_iters: int | None = None,
           tables: BoundTables | None = None,
           tile: int = 1024) -> SearchResult:
    """Host entry point: build tables, run, fetch counters.

    On overflow the pool is re-homed into double the capacity and the
    search RESUMES from exactly where it stopped (checkpoint.grow) — the
    lossless static-shape replacement for the reference's
    realloc-on-push (round 1 restarted from scratch here).

    Flight-recorded as a `search` span whose children split the host's
    part from the wait on the chip: `search.prepare` (tables, seeded
    state), `search.dispatch` (run's pool check and the jitted call,
    with any trace, lowering and compile), `search.wait` (the first
    host read of the result, the loop's one sync), `search.fetch` (the
    counters) and, on overflow, `search.grow`.
    """
    from . import checkpoint

    jobs = p_times.shape[1]
    with tracelog.span("search", jobs=jobs, machines=p_times.shape[0],
                       lb_kind=lb_kind, chunk=chunk, capacity=capacity):
        with tracelog.span("search.prepare"):
            if tables is None:
                tables = batched.make_tables(p_times)
            state = init_state(jobs, capacity, init_ub, p_times=p_times)
        while True:
            with tracelog.span("search.dispatch"):
                out = run(tables, state, lb_kind, chunk, max_iters,
                          tile=tile)
            with tracelog.span("search.wait"):
                overflow = bool(out.overflow)
            if not overflow:
                with tracelog.span("search.fetch"):
                    return SearchResult(
                        explored_tree=int(out.tree),
                        explored_sol=int(out.sol),
                        best=int(out.best), iters=int(out.iters),
                        evals=int(out.evals), overflow=False,
                        complete=int(out.size) == 0,
                    )
            capacity *= 2
            with tracelog.span("search.grow", capacity=capacity):
                state = checkpoint.grow(out, capacity)
