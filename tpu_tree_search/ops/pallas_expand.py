"""Pallas TPU kernel for the B&B expand step: parents -> bounded children.

This is the hand-scheduled replacement for the XLA elementwise pipeline in
`ops/batched.py` (itself the TPU re-expression of the reference's CUDA
bound kernels, pfsp/lib/bounds_gpu.cu:174-248 and PFSP_gpu_lib.cu:43-102).
Two observations motivate hand-scheduling:

1. **Lane utilization.** The natural `(batch, jobs)` arrays put jobs=20
   on the 128-wide lane axis — 84% of every vector register wasted. The
   kernel works feature-major: the batch rides the lanes, features ride
   the sublanes, every register full.
2. **Fusion boundaries.** Compiled as one XLA graph, the expand step's
   producers/consumers force layout conversions (reshapes/copies) that
   cost more than the math. A pallas_call is an opaque fusion barrier
   with exactly the layouts we choose.

Contract (all feature-major, `c = i*TB + b` columns within a grid tile —
slot-major within a tile of TB parents):

    expand(tables, lb_kind, prmu_T (J,B) i16, depth (1,B) i32,
           front_T (M,B) i32)
      -> children_T (J, B*J) i16     child permutations
         aux_T (M+1, B*J) i32       [child front | depth+1]
         bounds (1, B*J) i32        LB of every child slot (garbage on
                                     masked slots — caller masks)

The per-machine unscheduled work (`remain`) is reconstructed inside the
kernel from the permutation with a masked one-hot matmul, so the pool
only carries each node's front vector.

The caller derives masks/pruning/compaction from `bounds` plus the parent
depths; the kernel is pure expand+bound math (the reference splits this
the same way: evaluate_gpu writes bounds[], generate_children prunes,
PFSP_gpu_lib.cu:129-152 / PFSP_lib.h:51-95).

On non-TPU backends the same math runs as the `expand_xla` fallback
(also used for LB2 until its pair-sweep kernel lands).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .batched import BoundTables


def _x64_off():
    """Scope a trace to x32 (see the load-bearing comment at the LB2
    pallas call)."""
    return jax.enable_x64(False)

I32_MAX = jnp.int32(2**31 - 1)


def _tile_lanes(x: jax.Array, reps: int) -> jax.Array:
    """(R, T) -> (R, reps*T) by concatenation along lanes (jnp.tile)."""
    return jnp.concatenate([x] * reps, axis=1)




def _expand_kernel(lb_kind: int, J: int, M: int, TB: int,
                   p_ref, tails_ref, prmu_ref, depth_ref, front_ref,
                   children_ref, aux_ref, bounds_ref):
    """One tile: TB parents -> J*TB dense child slots (slot-major)."""
    _expand_math(lb_kind, J, M, TB, p_ref, tails_ref, prmu_ref, depth_ref,
                 front_ref, children_ref, aux_ref, bounds_ref)


def _bounds_kernel(lb_kind: int, J: int, M: int, TB: int,
                   p_ref, tails_ref, prmu_ref, depth_ref, front_ref,
                   bounds_ref):
    """Bounds-only variant: same math, no children/aux materialization.

    The regather step architecture (engine/device.step) only consumes the
    bound of every child slot here; surviving children are rebuilt from
    their parents after pruning, so writing the full (J+M+2, N) child
    block from the kernel would be pure wasted HBM traffic."""
    _expand_math(lb_kind, J, M, TB, p_ref, tails_ref, prmu_ref, depth_ref,
                 front_ref, None, None, bounds_ref)


def _expand_math(lb_kind: int, J: int, M: int, TB: int,
                 p_ref, tails_ref, prmu_ref, depth_ref, front_ref,
                 children_ref, aux_ref, bounds_ref):
    emit = children_ref is not None
    N = J * TB
    prmu = prmu_ref[:].astype(jnp.int32)          # (J, TB)
    depth = depth_ref[:]                          # (1, TB)

    # --- flat views over the child axis: column c = i*TB + b
    prmu_flat = prmu.reshape(1, N)                # value prmu[i, b] at c
    depth_flat = _tile_lanes(depth, J)            # depth[b] at c

    # --- child processing times via one-hot matmul on the MXU:
    # child_p[k, c] = p[k, prmu_flat[c]]
    onehot = (prmu_flat == jax.lax.broadcasted_iota(
        jnp.int32, (J, 1), 0)).astype(jnp.float32)             # (J, N)
    child_p = jax.lax.dot_general(
        p_ref[:], onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,   # default rounds via bf16,
        preferred_element_type=jnp.float32,    # corrupting p_times > 256
    ).astype(jnp.int32)                                        # (M, N)

    # --- parent remain (unscheduled work per machine) reconstructed from
    # the permutation: remain[k, b] = sum_{i >= depth_b} p[k, prmu[i, b]]
    # as one masked one-hot matmul — the pool does not store remain (it
    # would double the aux traffic through compaction; the reference
    # recomputes it per bound too, c_bound_simple.c:108-124)
    iota_v = jax.lax.broadcasted_iota(jnp.int32, (J, 1), 0)    # values
    mh = jnp.zeros((J, TB), jnp.float32)
    zero_f = jnp.zeros((), jnp.float32)   # explicit f32: a python-float
    for i in range(J):                    # literal is weak f64 under x64
        sched = (depth <= i).astype(jnp.float32)               # (1, TB)
        mh = mh + jnp.where(prmu[i:i + 1, :] == iota_v,
                            sched, zero_f)                     # (J, TB)
    remain = jax.lax.dot_general(
        p_ref[:], mh, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)                                        # (M, TB)

    # --- child front chain (add_forward, c_bound_simple.c:31-38)
    front_rep = _tile_lanes(front_ref[:], J)      # (M, N)
    remain_rep = _tile_lanes(remain, J)

    cf = front_rep[0:1] + child_p[0:1]
    cf_rows = [cf]
    for k in range(1, M):
        cf = jnp.maximum(cf, front_rep[k:k + 1]) + child_p[k:k + 1]
        cf_rows.append(cf)

    if emit:
        # --- children permutations: position row by position row
        # child(i, b)[pos] = prmu[i,b] if pos==depth[b]; prmu[depth[b],b]
        # if pos==i; else prmu[pos,b] (prefix-swap, PFSP_lib.c:13-16)
        # at_depth[b] = prmu[depth[b], b] (the job being displaced)
        at_depth = prmu[0:1, :]
        for pos in range(1, J):
            at_depth = jnp.where(depth == pos, prmu[pos:pos + 1, :],
                                 at_depth)
        # slot index i at column c = i*TB + b, as a concat of constants
        # (NOT `lane // TB` — a python-int divisor becomes a weak i64
        # under x64 and mosaic's i32<->i64 convert recurses; NOT a
        # reshaped sublane iota — mosaic fails to legalize the
        # sublane->lane iota relayout)
        slot_flat = jnp.concatenate(
            [jnp.full((1, TB), i, jnp.int32) for i in range(J)], axis=1)
        at_depth_flat = _tile_lanes(at_depth, J)
        for pos in range(J):
            base = _tile_lanes(prmu[pos:pos + 1, :], J)
            row = jnp.where(depth_flat == pos, prmu_flat,
                            jnp.where(slot_flat == pos, at_depth_flat,
                                      base))
            children_ref[pos:pos + 1, :] = row.astype(jnp.int16)

        # --- child pool tables [front | depth+1]
        for k in range(M):
            aux_ref[k:k + 1, :] = cf_rows[k]
        aux_ref[M:M + 1, :] = depth_flat + 1

    # --- bound chains last (write order matters to mosaic's scheduler:
    # bounds-first failed to legalize, see module docstring)
    if lb_kind == 1:
        # machine_bound_from_parts on the child (c_bound_simple.c:126-141)
        cr = remain_rep[0:1] - child_p[0:1]
        tmp0 = cf_rows[0] + cr
        lb = tmp0 + tails_ref[0, 0]
        for k in range(1, M):
            crk = remain_rep[k:k + 1] - child_p[k:k + 1]
            tmp1 = jnp.maximum(tmp0, cf_rows[k] + crk)
            lb = jnp.maximum(lb, tmp1 + tails_ref[0, k])
            tmp0 = tmp1
    else:
        # add_front_and_bound from the parent (c_bound_simple.c:218-244)
        lb = front_rep[0:1] + remain_rep[0:1] + tails_ref[0, 0]
        tmp0 = front_rep[0:1] + child_p[0:1]
        for k in range(1, M):
            tmp1 = jnp.maximum(tmp0, front_rep[k:k + 1])
            lb = jnp.maximum(
                lb, tmp1 + remain_rep[k:k + 1] + tails_ref[0, k])
            tmp0 = tmp1 + child_p[k:k + 1]
    bounds_ref[:] = lb


@functools.partial(jax.jit, static_argnames=("lb_kind", "tile"))
def expand_tpu(tables: BoundTables, prmu_T, depth2, front_T,
               lb_kind: int = 1, tile: int = 1024):
    """Pallas path (TPU). Shapes: prmu_T (J,B) i16, depth2 (1,B) i32,
    front_T (M,B) i32; B must be a multiple of `tile`.

    One grid-free pallas_call per tile, inputs statically sliced and
    outputs concatenated in XLA. A gridded kernel would be the natural
    shape, but under 64-bit mode (which the package enables for its tree
    counters) mosaic fails to legalize ANY grid index_map on this JAX
    version — grid-free full-block kernels compile fine, and at ~20
    vector ops per tile the per-call overhead is noise.
    """
    J, B = prmu_T.shape
    M = front_T.shape[0]
    TB = tile
    assert B % TB == 0, (B, TB)
    G = B // TB

    p_f32 = tables.p.astype(jnp.float32)           # (M, J)
    tails = tables.min_tails.reshape(1, M)

    kernel = functools.partial(_expand_kernel, lb_kind, J, M, TB)
    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((J, J * TB), jnp.int16),
            jax.ShapeDtypeStruct((M + 1, J * TB), jnp.int32),
            jax.ShapeDtypeStruct((1, J * TB), jnp.int32),
        ],
    )
    pieces = []
    for g in range(G):
        sl = slice(g * TB, (g + 1) * TB)
        pieces.append(call(p_f32, tails, prmu_T[:, sl], depth2[:, sl],
                           front_T[:, sl]))
    if G == 1:
        return pieces[0]
    return tuple(jnp.concatenate([p[k] for p in pieces], axis=1)
                 for k in range(3))


@functools.partial(jax.jit, static_argnames=("lb_kind", "tile"))
def expand_bounds_tpu(tables: BoundTables, prmu_T, depth2, front_T,
                      lb_kind: int = 1, tile: int = 1024):
    """Pallas bounds-only expand: (1, B*J) int32 child bounds in the same
    slot-major column order as expand_tpu, without materializing the
    children (see _bounds_kernel)."""
    J, B = prmu_T.shape
    M = front_T.shape[0]
    TB = tile
    assert B % TB == 0, (B, TB)
    G = B // TB

    p_f32 = tables.p.astype(jnp.float32)
    tails = tables.min_tails.reshape(1, M)
    kernel = functools.partial(_bounds_kernel, lb_kind, J, M, TB)
    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, J * TB), jnp.int32),
    )
    pieces = []
    for g in range(G):
        sl = slice(g * TB, (g + 1) * TB)
        pieces.append(call(p_f32, tails, prmu_T[:, sl], depth2[:, sl],
                           front_T[:, sl]))
    return pieces[0] if G == 1 else jnp.concatenate(pieces, axis=1)


def kernel_ok(jobs: int, eff_tile: int, lb_kind: int,
              machines: int | None = None) -> bool:
    """THE eligibility rule for the Pallas expand kernels — shared by
    expand(), expand_bounds() and device.step's two-phase gate so the
    dispatch can never diverge between them. The scheduled-set bitmask is
    multi-word (ceil(jobs/32) int32 rows) so LB2 has no job-count cliff;
    whether the pair sweep itself runs as the Pallas kernel or the XLA
    bitmask path is lb2_bounds' own VMEM decision (lb2_kernel_fits).
    When `machines` is given, the expand kernel's scoped-VMEM unit cap
    (EXPAND_TILE_UNITS) is enforced too — a trusted caller-supplied tile
    over the cap must fall back to XLA rather than compile-OOM."""
    if jax.default_backend() != "tpu":
        return False
    return kernel_shape_ok(jobs, eff_tile, lb_kind, machines=machines)


def kernel_shape_ok(jobs: int, eff_tile: int, lb_kind: int,
                    machines: int | None = None) -> bool:
    """The backend-independent SHAPE half of :func:`kernel_ok` — the
    tile-family rule plus the lane and scoped-VMEM caps. Split out so
    the rule can be checked without a chip (tests/test_tpu_compile.py)."""
    lane_cap = MAX_TILE_LANES // 2 if lb_kind == 2 else MAX_TILE_LANES
    return (eff_tile >= min_tile(jobs)
            # lane-aligned reshapes: the kernel's (J, TB) -> (1, J*TB)
            # flattening needs TB 128-aligned. The TB=64 family at
            # jobs >= 128 (min_tile's floor) ran on an older jax; the
            # installed Mosaic refuses its reshape ("unsupported shape
            # cast", tests/test_tpu_compile.py), so those shapes take
            # the XLA fallback like every other unaligned tile.
            and eff_tile % 128 == 0
            and jobs * eff_tile <= lane_cap
            and (machines is None
                 or jobs * machines * eff_tile <= EXPAND_TILE_UNITS))


def sched_words(jobs: int) -> int:
    """Rows of the scheduled-set bitmask: one int32 word per 32 jobs."""
    return (jobs + 31) // 32


LB2_ONEHOT_VMEM = 4 << 20

# pair-sweep kernel tuning knobs (see lb2_bounds_tpu): sublane block of
# pair rows, and the column-tile cap
LB2_PB = 64
LB2_TILE = 4096


# Wider tiles were tried for the few-pair classes (50x5: P=10 uses 10
# of 64 sublanes, so the J=50 step chain is per-step-latency-bound and
# wider NT would amortize it) and OOM the scoped-VMEM stack: mosaic
# materializes the per-unrolled-step activation temporaries without
# stack reuse, so scoped usage scales with (pair-block rows x NT x J)
# (measured: 17.76 MB at J=50/P=10/NT=8192; 18.18 MB at
# J=20/P=190/NT=8192; 18.09 MB at J=50/P=166/NT=4096 — the last one a
# round-3 REGRESSION: KH 32->24 grew the 50x20 tail block enough to
# blow the 16 MB limit at the fixed 4096 tile, caught by re-measuring
# ta056). lb2_tile() sizes NT against that model instead of trusting
# one constant.

# Scoped-VMEM model for lb2_tile: bytes ~= (rows*J + 2048) * NT — an
# affine fit with a row-independent term, deliberately CONSERVATIVE over
# all three measured points (predicts 21.5/20.9/27.0 MB for the
# 18.09/17.76/18.18 MB measurements, so every configuration that
# measured over the limit is rejected, including J=50/P=10/NT=8192,
# which a pure rows*NT*J model would wrongly approve), while keeping
# the proven production tiles: 20x20 -> 4096 (13.6 MB model), 50x20
# tail -> 2048 (10.7 MB), 50x5 dense -> 4096 (10.4 MB).
_LB2_SCOPED_BASE = 2048
_LB2_SCOPED_BUDGET = 15e6


def lb2_tile(jobs: int, pairs: int, width: int) -> int:
    """Largest legal pallas column tile for a pair sweep over `width`
    columns: divides width (power-of-two factor), caps at LB2_TILE, and
    respects the scoped-VMEM model above. Returns 0 when no tile
    >= MIN_PALLAS_TILE exists (callers then take the XLA path)."""
    rows = min(LB2_PB, pairs)
    nt = min(LB2_TILE, width & -width)
    while nt >= MIN_PALLAS_TILE and (
            (rows * jobs + _LB2_SCOPED_BASE) * nt > _LB2_SCOPED_BUDGET):
        nt //= 2
    return nt if nt >= MIN_PALLAS_TILE else 0


def lb2_sweep_tile(jobs: int, pairs: int, machines: int,
                   width: int) -> int:
    """THE single which-pallas-pair-kernel predicate: the column tile
    the LB2 sweep at `width` will actually run with — the register
    kernel's tile (lb2_tile) when lb2_kernel_fits, else the streaming
    big-J kernel's (lb2_bigj_tile). 0 means the sweep takes the XLA
    scan. Shared by lb2_bounds' dispatch and device.step's sweep-rung
    admission so tier admission can never diverge from the dispatch."""
    if lb2_kernel_fits(jobs, pairs):
        return lb2_tile(jobs, pairs, width)
    return lb2_bigj_tile(jobs, machines, width)


def lb2_kernel_fits(jobs: int, pairs: int) -> bool:
    """The pair-sweep kernel keeps its (J, P, J) bf16 per-step job
    one-hot resident in VMEM; past ~4 MB it cannot share VMEM with the
    column tiles. Jobs are additionally capped at 64: mosaic's
    scoped-VMEM stack behavior changes qualitatively past the validated
    classes (measured: J=100/P=24/NT=512 allocates 24.8 MB where the
    J<=50 model predicts 2.3 MB — the J-step unrolled temporaries stop
    being reused). Classes outside either cap take the XLA bitmask path
    (lb2_cols, a lax.scan), which the two-phase route still runs only
    over survivor tiers."""
    return jobs <= 64 and jobs * pairs * jobs * 2 <= LB2_ONEHOT_VMEM


def expand_bounds(tables: BoundTables, prmu_T, depth2, front_T,
                  lb_kind: int = 1, tile: int = 1024):
    """Bounds of every child slot, (1, B*J) int32, slot-major columns:
    the Pallas bounds kernel on TPU for LB1/LB1_d when the tile is legal,
    the XLA fallback otherwise — including ALL of LB2, whose TPU fast
    path needs the child fronts this function never materializes
    (device.step's two-phase route owns that case: LB1 kernel for the
    pre-prune, then lb2_bounds over the regathered survivors). The column
    order is identical to expand()'s for the same tile.

    front_T may arrive in the pool's narrow aux dtype (device.aux_dtype);
    the kernels' chain arithmetic needs i32."""
    front_T = front_T.astype(jnp.int32)
    J, B = prmu_T.shape
    eff_tile = (tile if B % tile == 0
                else effective_tile(J, B, tile, lb_kind,
                                    machines=front_T.shape[0]))
    if kernel_ok(J, eff_tile, lb_kind,
                 machines=front_T.shape[0]) and lb_kind in (0, 1):
        return expand_bounds_tpu(tables, prmu_T, depth2, front_T,
                                 lb_kind=lb_kind, tile=eff_tile)
    return expand_bounds_xla(tables, prmu_T, depth2, front_T,
                             lb_kind=lb_kind, tile=eff_tile)


def lb2_cols(tables: BoundTables, sched_mask, child_front_cols):
    """Feature-major LB2: the Johnson all-pairs sweep on (P, N) lanes.

    The reference's per-child pair loop with early exit
    (c_bound_johnson.c:211-237) becomes an unrolled J-step chain over all
    P = M(M-1)/2 pairs at once — children on the lanes, pairs on the
    sublanes, so every register is full (the row-major scan fallback in
    batched.lb2_from_parts leaves most lanes idle and materializes its
    scan carries every step).

    The child-unscheduled test is one shift of a multi-word scheduled-set
    bitmask (ceil(J/32) int32 words per child), so no job-position
    gathers are needed — one word row covers every 20-job class, two the
    50-job north-star classes.

    sched_mask: (W, N) int32, bit (v % 32) of word (v // 32) set iff job
    v is scheduled in the child (parent prefix + appended job);
    child_front_cols: (M, N) int32. Returns (1, N) int32 bounds.
    """
    t = tables
    J = t.js.shape[1]
    W = sched_mask.shape[0]
    one = jnp.int32(1)

    # pair-machine selection as one-hot matmuls (dynamic row gathers of
    # (P, N) from (M, N) serialize on TPU; the MXU does this in microseconds)
    M = t.p.shape[0]
    sel0 = (t.ma0[:, None] == jnp.arange(M)).astype(jnp.float32)  # (P, M)
    sel1 = (t.ma1[:, None] == jnp.arange(M)).astype(jnp.float32)
    cf_f = child_front_cols.astype(jnp.float32)
    tmp0 = jnp.dot(sel0, cf_f, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    tmp1 = jnp.dot(sel1, cf_f, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)

    # The J-step chain runs as a lax.scan, NOT an unrolled python loop:
    # unrolled, XLA keeps O(J) of the (P, N) step temporaries live at
    # once — at 100 jobs x 190 pairs x 409600 children that is ~28 GB
    # of HBM (measured compile OOM on ta081-class); the scan carries
    # exactly two (P, N) buffers. Bit-identical math either way.
    def chain(carry, xs):
        t0, t1 = carry
        jsj, pt0j, pt1j, lagj = xs                      # (P,) each
        jsc = jsj[:, None]                              # (P, 1)
        if W == 1:
            active = ((sched_mask >> jsc) & one) == 0   # (P, N)
        else:
            word = jnp.take(sched_mask, jsj // 32, axis=0)        # (P, N)
            active = ((word >> (jsc % 32)) & one) == 0
        new0 = t0 + pt0j[:, None]
        new1 = jnp.maximum(t1, new0 + lagj[:, None]) + pt1j[:, None]
        return (jnp.where(active, new0, t0),
                jnp.where(active, new1, t1)), None

    (tmp0, tmp1), _ = jax.lax.scan(
        chain, (tmp0, tmp1),
        (t.js.T, t.ptm0_js.T, t.ptm1_js.T, t.lag_js.T))
    back0 = jnp.take(t.min_tails, t.ma0)[:, None]       # (P, 1)
    back1 = jnp.take(t.min_tails, t.ma1)[:, None]
    per_pair = jnp.maximum(tmp1 + back1, tmp0 + back0)
    return per_pair.max(axis=0, keepdims=True)          # (1, N)


def _lb2_kernel(J: int, M: int, P: int, PB: int,
                sel0_ref, sel1_ref, js1h_ref, pt0_ref, pt1_ref, lag_ref,
                tails0_ref, tails1_ref, cf_ref, unsched_ref, bounds_ref):
    """All-pairs Johnson sweep for one column tile: pairs ride the
    sublanes in blocks of PB, children ride the lanes. Machine selection
    and the per-step active test are one-hot matmuls on the MXU (dynamic
    row indexing inside mosaic is either unsupported or serializes).

    cf_ref (M, NT) child fronts; unsched_ref (J, NT) bf16 0/1 per job;
    tables: sel0/sel1 (P, M) f32 pair-machine one-hots, js1h (J, P, J)
    bf16 per-step job one-hots, pt0/pt1/lag (P, J) f32, tails (P, 1)
    f32. Output bounds (1, NT) i32.
    """
    cf_f = cf_ref[:].astype(jnp.float32)            # (M, NT)
    unsched = unsched_ref[:]                        # (J, NT) bf16
    hi = jax.lax.Precision.HIGHEST
    lb = None
    # All values are small non-negative integers (completion times
    # < 2^24), so f32 arithmetic is EXACT and the active-select chain
    # becomes mul/max forms the VPU executes with fewer ops than
    # compare+select: t0 update is one fma (act is exactly 0/1 from the
    # one-hot matmul), and the t1 select is max(t1, act*cand) — valid
    # because cand >= t1 whenever act == 1 and everything is >= 0.
    #
    # The ACT matmul runs in bf16: both operands are exactly-
    # representable 0/1 one-hots and the J-wide dot accumulates to at
    # most J <= 64 in f32 — bit-exact, and the MXU takes one pass where
    # an f32 HIGHEST dot decomposes into several. The VALUE matmuls
    # (sel @ cf: completion times in the thousands, > bf16's 256-exact
    # integer range) stay f32/HIGHEST.
    for lo in range(0, P, PB):
        nrows = min(PB, P - lo)
        sl = slice(lo, lo + nrows)
        t0 = jnp.dot(sel0_ref[sl, :], cf_f, precision=hi,
                     preferred_element_type=jnp.float32)
        t1 = jnp.dot(sel1_ref[sl, :], cf_f, precision=hi,
                     preferred_element_type=jnp.float32)
        for j in range(J):
            act = jnp.dot(js1h_ref[j, sl, :], unsched,
                          preferred_element_type=jnp.float32)
            t0 = t0 + act * pt0_ref[sl, j:j + 1]
            cand = jnp.maximum(t1, t0 + lag_ref[sl, j:j + 1]) \
                + pt1_ref[sl, j:j + 1]
            t1 = jnp.maximum(t1, act * cand)
        per_pair = jnp.maximum(t1 + tails1_ref[sl, :], t0 + tails0_ref[sl, :])
        blk = jnp.max(per_pair, axis=0, keepdims=True)
        lb = blk if lb is None else jnp.maximum(lb, blk)
    bounds_ref[:] = lb.astype(jnp.int32)


def lb2_bounds(tables: BoundTables, child_front_cols, sched_mask):
    """LB2 over child columns from the scheduled-set bitmask: Pallas
    pair-sweep kernel when a legal column tile exists and the pair tables
    fit VMEM, the XLA bitmask path (lb2_cols) otherwise.
    child_front_cols (M, N) i32, sched_mask (W, N) i32 -> (1, N) i32.

    THE single entry point for column-major LB2 — both device.step's
    two-phase tiers and expand()'s one-shot path go through here, so the
    tile rule and the fallback cannot diverge.

    Accepts the pool's narrow aux dtype (engine/device.aux_dtype) for
    child_front_cols; widened to i32 here at entry (full width — a no-op
    for the i32 blocks the engine's compaction path passes)."""
    child_front_cols = child_front_cols.astype(jnp.int32)
    M, N = child_front_cols.shape
    J = tables.js.shape[1]
    P = int(tables.ma0.shape[0])
    nt = lb2_sweep_tile(J, P, M, N)
    if jax.default_backend() != "tpu" or nt == 0:
        return lb2_cols(tables, sched_mask, child_front_cols)
    vj = jnp.arange(J, dtype=jnp.int32)
    word = (sched_mask if sched_mask.shape[0] == 1
            else jnp.take(sched_mask, vj // 32, axis=0))       # (J|1, N)
    unsched = (((word >> (vj % 32)[:, None]) & jnp.int32(1)) == 0) \
        .astype(jnp.bfloat16)                   # (J, N) 0/1: bf16-exact
    if lb2_kernel_fits(J, P):
        return lb2_bounds_tpu(tables, child_front_cols, unsched, tile=nt)
    return lb2_bounds_bigj_tpu(tables, child_front_cols, unsched,
                               tile=nt)


@functools.partial(jax.jit, static_argnames=("tile",))
def lb2_bounds_tpu(tables: BoundTables, child_front_cols, unsched_cols,
                   tile: int = LB2_TILE):
    """Pallas LB2 over child columns: child_front_cols (M, N) i32,
    unsched_cols (J, N) bf16 0/1 — returns (1, N) i32 bounds."""
    M, N = child_front_cols.shape
    J = unsched_cols.shape[0]
    P = tables.ma0.shape[0]
    PB = LB2_PB
    NT = tile
    assert N % NT == 0, (N, NT)

    sel0 = (tables.ma0[:, None] == jnp.arange(M)).astype(jnp.float32)
    sel1 = (tables.ma1[:, None] == jnp.arange(M)).astype(jnp.float32)
    js1h = (tables.js.T[:, :, None]
            == jnp.arange(J)).astype(jnp.bfloat16)      # (J, P, J) one-hot
    # f32 tables: the kernel's whole chain runs in (exact) f32
    pt0 = tables.ptm0_js.astype(jnp.float32)
    pt1 = tables.ptm1_js.astype(jnp.float32)
    lag = tables.lag_js.astype(jnp.float32)
    tails0 = jnp.take(tables.min_tails, tables.ma0)[:, None] \
        .astype(jnp.float32)
    tails1 = jnp.take(tables.min_tails, tables.ma1)[:, None] \
        .astype(jnp.float32)

    kernel = functools.partial(_lb2_kernel, J, M, P, PB)
    # ONE pallas_call with a grid over column tiles (round 2 issued one
    # call per tile: at production shapes that is ~55 dispatches/step,
    # each re-fetching every pair table into VMEM — measured 27% of the
    # two-phase step). Constant index_maps keep the tables resident
    # across grid steps while the column blocks double-buffer.
    # The x64-off scope is load-bearing: the package enables x64 globally
    # (engine counters are int64), and under x64 the grid index maps
    # trace their constants as i64 — mosaic then fails to legalize the
    # index-map function ("failed to legalize operation 'func.return'").
    # Nothing in this call touches 64-bit data, so scoping the trace to
    # x32 is semantics-preserving.
    with _x64_off():
        call = pl.pallas_call(
            kernel,
            grid=(N // NT,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 8 + [
                pl.BlockSpec((M, NT), lambda g: (0, g)),
                pl.BlockSpec((J, NT), lambda g: (0, g)),
            ],
            out_specs=pl.BlockSpec((1, NT), lambda g: (0, g)),
            out_shape=jax.ShapeDtypeStruct((1, N), jnp.int32),
        )
        return call(sel0, sel1, js1h, pt0, pt1, lag, tails0, tails1,
                    child_front_cols, unsched_cols)


LB2_BIGJ_MIN_TILE = 512


def lb2_bigj_tile(jobs: int, machines: int, width: int) -> int:
    """Column tile for the STREAMING big-J pair sweep
    (lb2_bounds_bigj_tpu): a power-of-two divisor of `width`, sized so
    the per-tile VMEM residents — unsched (J, NT) bf16, cf (M, NT) f32,
    two (PB, NT) f32 chain scratches, the (1, NT) output and the
    double-buffered per-step blocks — fit the scoped budget. Returns 0
    when no tile >= LB2_BIGJ_MIN_TILE exists (callers then take the XLA
    scan)."""
    nt = min(LB2_TILE, width & -width)
    per_col = 2 * jobs + 4 * machines + 8 * LB2_PB + 16
    while nt >= LB2_BIGJ_MIN_TILE and nt * per_col > 12e6:
        nt //= 2
    return nt if nt >= LB2_BIGJ_MIN_TILE else 0


def _lb2_bigj_kernel(J, P, PB,
                     sel0_ref, sel1_ref, tails0_ref, tails1_ref,
                     js_ref, pt0_ref, pt1_ref, lag_ref,
                     cf_ref, unsched_ref, bounds_ref, t0_ref, t1_ref):
    """Streaming all-pairs Johnson sweep for J > 64: one grid step per
    (column tile, pair block, JOB step). The J-step chain that the
    small-J kernel unrolls in registers (and whose (J, P, J) one-hot
    must sit whole in VMEM — both walls cap it at J <= 64,
    lb2_kernel_fits) here carries (PB, NT) f32 chain state in VMEM
    scratch across sequential j grid steps, while the per-step one-hot
    block (1, PB, J) bf16 and the (1, PB, 1) pt/lag columns STREAM from
    HBM. Init (pair-machine selection matmul) and the final
    per-pair/tails reduction run under pl.when at the chain's
    endpoints; the output block is revisited across pair blocks with a
    running max. Same mul/max active-select math as _lb2_kernel —
    bit-exact f32, bf16 act matmul (0/1 one-hots)."""
    pb = pl.program_id(1)
    j = pl.program_id(2)
    hi = jax.lax.Precision.HIGHEST

    @pl.when(j == 0)
    def _init():
        cf = cf_ref[:]
        t0_ref[:] = jnp.dot(sel0_ref[:], cf, precision=hi,
                            preferred_element_type=jnp.float32)
        t1_ref[:] = jnp.dot(sel1_ref[:], cf, precision=hi,
                            preferred_element_type=jnp.float32)

    act = jnp.dot(js_ref[0], unsched_ref[:],
                  preferred_element_type=jnp.float32)       # (PB, NT)
    pt0j = pt0_ref[0]                                       # (PB, 1)
    pt1j = pt1_ref[0]
    lagj = lag_ref[0]
    t0 = t0_ref[:] + act * pt0j
    cand = jnp.maximum(t1_ref[:], t0 + lagj) + pt1j
    t1 = jnp.maximum(t1_ref[:], act * cand)
    t0_ref[:] = t0
    t1_ref[:] = t1

    @pl.when(j == J - 1)
    def _fin():
        per_pair = jnp.maximum(t1 + tails1_ref[:], t0 + tails0_ref[:])
        blk = jnp.max(per_pair, axis=0, keepdims=True).astype(jnp.int32)

        @pl.when(pb == 0)
        def _first():
            bounds_ref[:] = blk

        @pl.when(pb > 0)
        def _acc():
            bounds_ref[:] = jnp.maximum(bounds_ref[:], blk)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def lb2_bounds_bigj_tpu(tables: BoundTables, child_front_cols,
                        unsched_cols, tile: int,
                        interpret: bool = False):
    """Streaming pallas LB2 for J > 64 (see _lb2_bigj_kernel):
    child_front_cols (M, N) i32, unsched_cols (J, N) bf16 0/1 ->
    (1, N) i32 bounds. `interpret=True` runs the pallas interpreter
    (CPU) — used by the CPU parity tests; hardware parity is pinned by
    tests/test_pallas_tpu.py."""
    M, N = child_front_cols.shape
    J = unsched_cols.shape[0]
    P = int(tables.ma0.shape[0])
    PB = LB2_PB
    NB = -(-P // PB)
    PP = NB * PB
    NT = tile
    assert N % NT == 0, (N, NT)

    def pad_rows(x, rows, fill=0.0):
        pad = rows - x.shape[0]
        if pad == 0:
            return x
        return jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)

    with _x64_off():
        sel0 = pad_rows((tables.ma0[:, None]
                         == jnp.arange(M)).astype(jnp.float32), PP)
        sel1 = pad_rows((tables.ma1[:, None]
                         == jnp.arange(M)).astype(jnp.float32), PP)
        # pad pairs with -3e8 tails: their all-zero chains then lose
        # every max against any real pair's non-negative bound
        tails0 = pad_rows(jnp.take(tables.min_tails, tables.ma0)[:, None]
                          .astype(jnp.float32), PP, -3e8)
        tails1 = pad_rows(jnp.take(tables.min_tails, tables.ma1)[:, None]
                          .astype(jnp.float32), PP, -3e8)
        # per-step tables, job-step-major so grid blocks stream one
        # (1, PB, ·) slab per (j, pb): one-hots bf16 (exact), pt/lag as
        # (J, PP, 1) f32 columns (pairs ride the sublanes, matching the
        # (PB, NT) chain blocks)
        js = pad_rows((tables.js.T[:, :, None]
                       == jnp.arange(J)).astype(jnp.bfloat16)
                      .transpose(1, 0, 2), PP).transpose(1, 0, 2)
        pt0 = pad_rows(tables.ptm0_js.astype(jnp.float32), PP) \
            .T[:, :, None]
        pt1 = pad_rows(tables.ptm1_js.astype(jnp.float32), PP) \
            .T[:, :, None]
        lag = pad_rows(tables.lag_js.astype(jnp.float32), PP) \
            .T[:, :, None]
        cf = child_front_cols.astype(jnp.float32)
        unsched = unsched_cols.astype(jnp.bfloat16)

        kernel = functools.partial(_lb2_bigj_kernel, J, P, PB)
        call = pl.pallas_call(
            kernel,
            grid=(N // NT, NB, J),
            in_specs=[
                pl.BlockSpec((PB, M), lambda t, pb, j: (pb, 0)),    # sel0
                pl.BlockSpec((PB, M), lambda t, pb, j: (pb, 0)),    # sel1
                pl.BlockSpec((PB, 1), lambda t, pb, j: (pb, 0)),    # tails0
                pl.BlockSpec((PB, 1), lambda t, pb, j: (pb, 0)),    # tails1
                pl.BlockSpec((1, PB, J), lambda t, pb, j: (j, pb, 0)),
                pl.BlockSpec((1, PB, 1), lambda t, pb, j: (j, pb, 0)),
                pl.BlockSpec((1, PB, 1), lambda t, pb, j: (j, pb, 0)),
                pl.BlockSpec((1, PB, 1), lambda t, pb, j: (j, pb, 0)),
                pl.BlockSpec((M, NT), lambda t, pb, j: (0, t)),     # cf
                pl.BlockSpec((J, NT), lambda t, pb, j: (0, t)),     # unsched
            ],
            out_specs=pl.BlockSpec((1, NT), lambda t, pb, j: (0, t)),
            out_shape=jax.ShapeDtypeStruct((1, N), jnp.int32),
            scratch_shapes=[pltpu.VMEM((PB, NT), jnp.float32),
                            pltpu.VMEM((PB, NT), jnp.float32)],
            interpret=interpret,
        )
        return call(sel0, sel1, tails0, tails1, js, pt0, pt1, lag,
                    cf, unsched)


def _to_cols(x, G: int, TB: int, J: int):
    """Reorder (B, J, X) -> (X, tile-slot-major columns): within each
    tile of TB parents, column c = i*TB + b."""
    x = x.reshape(G, TB, J, x.shape[-1])
    x = x.transpose(3, 0, 2, 1)                     # (X, G, J, TB)
    return x.reshape(x.shape[0], G * J * TB)


def _xla_parts(tables: BoundTables, prmu_T, depth2, front_T):
    """Shared row-major intermediates of the XLA expand paths: parent
    views, per-machine remain (reconstructed from the permutation,
    kernel-parity), and the child front chains."""
    from . import batched

    J, B = prmu_T.shape
    prmu = prmu_T.T                                 # (B, J)
    depth = depth2.reshape(B)
    front = front_T.T
    sched_mask = jnp.arange(J)[None, :] >= depth[:, None]      # (B, J)
    onehot = (prmu[..., None].astype(jnp.int32)
              == jnp.arange(J, dtype=jnp.int32)) & sched_mask[..., None]
    remain = jnp.einsum("bjv,mv->bm", onehot.astype(jnp.int32),
                        tables.p,
                        preferred_element_type=jnp.int32)      # (B, M)
    child_front, child_p = batched._child_fronts(tables, prmu, front)
    return prmu, depth, front, remain, child_front, child_p


def _bounds_rows(tables: BoundTables, lb_kind: int, prmu, depth, front,
                 remain, child_front, child_p):
    """(B, J) bounds from the row-major parts, or None for LB2, which the
    callers evaluate column-major via lb2_cols on the child fronts (the
    multi-word bitmask covers any job count)."""
    from . import batched

    B, J = prmu.shape
    mask = jnp.ones((B, J), bool)
    if lb_kind == 2:
        return None
    if lb_kind == 1:
        return batched.lb1_from_parts(
            tables, child_front, remain[:, None, :] - child_p, mask)
    return batched.lb1d_from_parts(tables, front, remain, child_p, mask)


def expand_xla(tables: BoundTables, prmu_T, depth2, front_T,
               lb_kind: int = 1, tile: int | None = None):
    """Pure-XLA fallback with the identical contract (feature-major,
    slot-major columns with the given tile size — tile defaults to B so
    the column order matches a single-tile kernel).

    Used on CPU (tests / host debugging) and for LB2.
    """
    J, B = prmu_T.shape
    M = front_T.shape[0]
    TB = B if tile is None else tile
    assert B % TB == 0
    G = B // TB

    prmu, depth, front, remain, child_front, child_p = _xla_parts(
        tables, prmu_T, depth2, front_T)
    bounds = _bounds_rows(tables, lb_kind, prmu, depth, front, remain,
                          child_front, child_p)

    from ..engine.device import make_children
    children = make_children(prmu, depth)           # (B, J, J)
    child_aux = jnp.concatenate(
        [child_front.astype(jnp.int32),
         jnp.broadcast_to((depth + 1)[:, None, None], (B, J, 1))],
        axis=-1)                                    # (B, J, M+1)

    children_T = _to_cols(children.astype(jnp.int32), G, TB, J) \
        .astype(jnp.int16)
    aux_T = _to_cols(child_aux, G, TB, J)
    if bounds is not None:
        bounds_row = _to_cols(bounds[:, :, None], G, TB, J) \
            .astype(jnp.int32)
    else:
        # LB2 bitmask fast path on (pairs, children) lanes; aux rows
        # [0:M] are exactly the child fronts in column order
        sched = sched_mask_cols(prmu_T, depth2, TB)
        bounds_row = lb2_cols(tables, sched, aux_T[:M])
    return children_T, aux_T, bounds_row


def expand_bounds_xla(tables: BoundTables, prmu_T, depth2, front_T,
                      lb_kind: int = 1, tile: int | None = None):
    """Bounds-only XLA fallback: same column order and bound math as
    expand_xla, but never materializes the children/aux block — the
    regather step architecture rebuilds survivors from their parents, so
    building the dense child block here would be pure wasted work."""
    J, B = prmu_T.shape
    TB = B if tile is None else tile
    assert B % TB == 0
    G = B // TB

    prmu, depth, front, remain, child_front, child_p = _xla_parts(
        tables, prmu_T, depth2, front_T)
    bounds = _bounds_rows(tables, lb_kind, prmu, depth, front, remain,
                          child_front, child_p)
    if bounds is not None:
        return _to_cols(bounds[:, :, None], G, TB, J).astype(jnp.int32)
    cf_cols = _to_cols(child_front.astype(jnp.int32), G, TB, J)
    sched = sched_mask_cols(prmu_T, depth2, TB)
    return lb2_cols(tables, sched, cf_cols)


MIN_PALLAS_TILE = 256   # below this mosaic rejects the lane reshapes
MAX_TILE_LANES = 1 << 15  # J*tile cap keeping the tile's VMEM ~10 MB

# Expand-kernel scoped-VMEM cap in J*M*TB units: the kernel's unrolled
# J-loops materialize ~37 B of per-step temporaries per unit. The 512k
# unit point hard-OOMs the 16 MB stack at BOTH measured J's (18.73 MB
# at 100x20x256 AND 18.53 MB at 50x20x512 — so the unit model is
# J-independent, and the pre-cap code had a LATENT compile crash on any
# 50x20 LB1 run, never hit only because that class's LB2 route happens
# to use tile 256); 20x20x1024 = 409.6k units is the proven production
# ceiling, and 100x20x128 compiles and matches the XLA oracle
# bit-exactly. Applied only when the caller supplies `machines`.
EXPAND_TILE_UNITS = 20 * 20 * 1024


def min_tile(jobs: int) -> int:
    """Mosaic's lane-reshape floor for the expand kernels: 256 in
    general; 128 is validated for the wide classes (jobs >= 64 keeps
    the J*tile lane count >= 8192 — measured bit-exact at J=100/TB=128,
    which the 100x20 class needs to fit the scoped-VMEM stack); 64 for
    jobs >= 128, where the 200x20 class needs TB=64 to fit the J*M*TB
    scoped-VMEM unit cap — a tile kernel_shape_ok no longer admits, so
    that class expands on the XLA path."""
    if jobs >= 128:
        return 64
    return 128 if jobs >= 64 else 256


def effective_tile(jobs: int, batch: int, tile: int = 1024,
                   lb_kind: int = 1, machines: int | None = None) -> int:
    """The tile expand() will actually use — THE single source of truth
    for the output column order. Shrinks the requested tile while the
    (jobs x tile) working set exceeds the VMEM budget or, when
    `machines` is given, while the expand kernel's scoped-VMEM units
    (J*M*TB, see EXPAND_TILE_UNITS) exceed the measured ceiling — so
    20x20 runs at 1024, 50x20 and 100x10 at 256, 100x20 and 200x10 at
    128; then falls back to one batch-wide tile if the batch is not a
    multiple. LB2 halves the
    lane budget — its pair-sweep kernel shares the program's VMEM
    headroom. step() derives its mask column order from this same
    function; they must never diverge.
    """
    cap = MAX_TILE_LANES // 2 if lb_kind == 2 else MAX_TILE_LANES
    floor = min_tile(jobs)

    def too_big(t):
        if jobs * t > cap:
            return True
        return machines is not None and jobs * machines * t > EXPAND_TILE_UNITS

    while tile >= floor and too_big(tile):
        tile //= 2
    return tile if batch % tile == 0 else batch


def sched_mask_cols(prmu_T, depth2, tile: int):
    """(W, N) int32 per-child scheduled-set bitmask in the expand column
    order (c = (g*J + i)*TB + b), W = ceil(J/32) words: the parent's
    prefix bits plus the appended job's bit; bit (v % 32) of word
    (v // 32) stands for job v."""
    J, B = prmu_T.shape
    W = sched_words(J)
    G = B // tile
    N = B * J
    one = jnp.int32(1)
    ppi = prmu_T.astype(jnp.int32)
    appended = ppi.reshape(J, G, tile).transpose(1, 0, 2).reshape(1, N)
    in_prefix = jax.lax.broadcasted_iota(jnp.int32, (J, B), 0) < depth2
    words = []
    for w in range(W):
        inw = (ppi >= 32 * w) & (ppi < 32 * (w + 1))
        bit = one << jnp.where(inw, ppi - 32 * w, 0)
        pmask = jnp.sum(jnp.where(in_prefix & inw, bit, 0),
                        axis=0, dtype=jnp.int32)[None, :]      # (1, B)
        pmask_c = jnp.broadcast_to(
            pmask.reshape(G, 1, tile), (G, J, tile)).reshape(1, N)
        ainw = (appended >= 32 * w) & (appended < 32 * (w + 1))
        abit = jnp.where(
            ainw, one << jnp.where(ainw, appended - 32 * w, 0), 0)
        words.append(pmask_c | abit)
    return jnp.concatenate(words, axis=0)


def expand(tables: BoundTables, prmu_T, depth2, front_T,
           lb_kind: int = 1, tile: int = 1024):
    """Dispatch: Pallas on TPU (LB1/LB1_d directly; LB2 as the expand
    kernel for children/aux + the pair-sweep kernel for bounds, when the
    job count fits the scheduled-set bitmask), XLA otherwise.

    front_T may arrive in the pool's narrow aux dtype (device.aux_dtype).
    """
    front_T = front_T.astype(jnp.int32)
    J, B = prmu_T.shape
    # A tile that divides the batch is trusted as-is: step() derives it
    # through effective_tile and builds its masks in that column order,
    # so re-deriving here could silently diverge from the caller
    # (kernel_ok below still gates hardware limits — an oversized trusted
    # tile falls back to XLA, never to a different column order).
    eff_tile = (tile if B % tile == 0
                else effective_tile(J, B, tile, lb_kind,
                                    machines=front_T.shape[0]))
    ok = kernel_ok(J, eff_tile, lb_kind, machines=front_T.shape[0])
    if ok and lb_kind in (0, 1):
        return expand_tpu(tables, prmu_T, depth2, front_T,
                          lb_kind=lb_kind, tile=eff_tile)
    if ok and lb_kind == 2:
        N = B * J
        if lb2_tile(J, int(tables.ma0.shape[0]), N) > 0:
            children, aux, _ = expand_tpu(tables, prmu_T, depth2, front_T,
                                          lb_kind=1, tile=eff_tile)
            sched = sched_mask_cols(prmu_T, depth2, eff_tile)  # (W, N)
            M = tables.p.shape[0]
            bounds = lb2_bounds(tables, aux[:M], sched)
            return children, aux, bounds
    return expand_xla(tables, prmu_T, depth2, front_T,
                      lb_kind=lb_kind, tile=eff_tile)
