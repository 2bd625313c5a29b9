"""Fused Pallas bound+prune+compact: pruned children never touch HBM.

The two-phase step (engine/device.step) still round-trips three dense
(child-grid-wide) intermediates through HBM between separate XLA ops
every iteration: the (1, N) bound row the bounds kernel writes, the
(N,) prune mask, and the (N,) packed sort keys + permutation of the
stable partition — all sized for EVERY child, although the majority of
children on a healthy search are pruned and only their bound's
comparison against the incumbent ever mattered. The reference's answer
is its hand-written CUDA bound kernels with the per-child early exit
(bounds_gpu.cu / evaluate_gpu); the TPU answer here is one fused
kernel per chunk that performs

    expand (children + fronts) -> bound (the LB1 chain) ->
    prune-compare against the traced ``bound_cap`` ->
    within-tile compaction -> cursor write of the SURVIVORS ONLY

entirely in VMEM, double-buffered over the chunk with a grid over
column tiles (the same tiling scheme as the streaming big-J pair
sweep, ops/pallas_expand._lb2_bigj_kernel). What reaches HBM is the
compacted survivor block (children, [front | depth+1] aux, bounds and
— for the two-phase LB2 route — the scheduled-set bitmask words), one
survivor count, and (telemetry builds only) a BOUND_BINS x tiles
histogram of the pruned children's bounds so the audit's
``bound_hist_exact`` identity holds bit-identically without the pruned
bounds themselves ever being materialized.

Survivor storage is capped at ``cap_width`` columns (the engine passes
its steady N/4 frame): a step whose survivors outgrow the cap keeps a
correct COUNT (the cursor keeps accumulating; stores stop), and the
engine's fused route falls back to the unfused pipeline for that rare
step via one lax.cond — bit-identical bounds, so the explored set
cannot depend on which branch ran.

Compaction inside the kernel uses the engine's packed-key partition
trick (device._partition): flag in bit 31, column index in the low
bits, one unstable u32 sort — deterministic because every key is
unique, and stable-in-column-order because tiles are visited in grid
order and the cursor advances monotonically. The kernels run under the
Pallas INTERPRETER on the CPU mesh only (the tests/test_fused.py parity
suite): Mosaic refuses to lower the in-kernel sort for the TPU
(:data:`MOSAIC_REFUSAL`), so there is no hardware route until the
kernel is rewritten without it (ROADMAP A2).

Mode resolution (all env reads HOST-side — the traced step receives
the resolved mode as a static argument, never reads the environment):

- ``off``       fused disabled (the default; bit-identical legacy path)
- ``hw``        the TPU kernels; asking for them (fused="hw", or
                TTS_FUSED=1 on a TPU backend) raises, naming the
                Mosaic refusal
- ``interpret`` TTS_FUSED=1 + TTS_FUSED_INTERPRET=1 on a non-TPU
                backend: the kernels run under pl.pallas_call's
                interpreter inside the compiled step — the CI leg that
                fails kernel-logic regressions without TPU hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_expand
from .batched import BoundTables

I32_MAX = jnp.int32(2**31 - 1)


def store_sub(n_cols: int) -> int:
    """Cursor-store sub-block width for a tile of `n_cols` children —
    ALSO the output frame's store slack (fused_expand's WPAD), so the
    kernel and its caller must derive it from this one function. The
    whole-tile store needed a whole tile of slack past the survivor
    cap; storing in ~N/8 sub-blocks gated on the live survivor count
    cuts the slack (and the engine-side narrowing copy) to one
    sub-block while keeping the store count per tile small. 128-lane
    aligned for the hardware route."""
    if n_cols <= 128:
        return n_cols
    eighth = (n_cols + 7) // 8
    return max(128, (eighth + 127) // 128 * 128)

FUSED_FLAG = "TTS_FUSED"
FUSED_INTERPRET_FLAG = "TTS_FUSED_INTERPRET"

MOSAIC_REFUSAL = (
    "the fused kernels cannot be compiled for the TPU: Mosaic rejects "
    "their in-kernel sort ('Unimplemented primitive in Pallas TPU "
    "lowering for KernelType.TC: sort'). Unset TTS_FUSED; the rewrite "
    "is ROADMAP A2.")


def resolve_mode(flag: bool | str | None = None) -> str:
    """HOST-side resolution of the fused dispatch mode: "off" |
    "interpret". `flag` None reads the TTS_FUSED env knob; an explicit
    string mode passes through (the tests' control channel); True
    resolves against the backend like the env flag. The result is a
    STATIC argument of the compiled step — flipping the env mid-process
    retraces rather than silently reusing a stale executable. A request
    for the hardware kernels raises (:data:`MOSAIC_REFUSAL`)."""
    if isinstance(flag, str):
        if flag == "hw":
            raise RuntimeError(MOSAIC_REFUSAL)
        if flag not in ("off", "interpret"):
            raise ValueError(f"unknown fused mode {flag!r}")
        return flag
    from ..utils import config as _cfg
    if flag is None:
        flag = _cfg.env_flag(FUSED_FLAG)
    if not flag:
        return "off"
    if jax.default_backend() == "tpu":
        raise RuntimeError(MOSAIC_REFUSAL)
    if _cfg.env_flag(FUSED_INTERPRET_FLAG):
        return "interpret"
    return "off"


def fused_ok(mode: str, lb_kind: int) -> bool:
    """THE fused-route admission rule (device.step's gate). Only the
    interpreter route exists (resolve_mode refuses the hardware one);
    it has no Mosaic layout constraints — it validates kernel LOGIC on
    the CPU mesh — and admits any shape of the LB1/LB2 steps."""
    return mode == "interpret" and lb_kind in (1, 2)


def _tile_lanes(x: jax.Array, reps: int) -> jax.Array:
    return jnp.concatenate([x] * reps, axis=1)


def _fused_kernel(J: int, M: int, TB: int, W: int, SW: int, BINS: int,
                  BNDS: bool, AUXI16: bool,
                  p_ref, tails_ref, prmu_ref, depth_ref, front_ref,
                  n_ref, cap_ref, *refs):
    """One grid step = one tile of TB parents -> the tile's SURVIVING
    children appended at the running cursor. Bound math is kept
    formula-identical to ops/pallas_expand._expand_math's LB1 branch
    (the parity suite pins the two bit-exact); pruning compares against
    the traced ``bound_cap`` scalar (the incumbent with this chunk's
    leaf improvements already folded in — the caller's parent-level
    leaf scan owns leaves, so leaf columns are never pushed here).

    ``SW`` > 0 additionally emits the scheduled-set bitmask words of
    every survivor (the two-phase LB2 route's pair-sweep input);
    ``BINS`` > 0 emits the per-tile pruned-bound histogram (engine
    telemetry's bound_hist binning, exact in int32)."""
    out = list(refs)
    children_ref, caux_ref = out[:2]
    out = out[2:]
    bounds_ref = out.pop(0) if BNDS else None
    sched_ref = out.pop(0) if SW else None
    cnt_ref = out.pop(0)
    hist_ref = out.pop(0) if BINS else None
    cur_ref = out.pop(0)

    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        cur_ref[0] = jnp.int32(0)

    N = J * TB
    prmu = prmu_ref[:].astype(jnp.int32)          # (J, TB)
    depth = depth_ref[:]                          # (1, TB)

    prmu_flat = prmu.reshape(1, N)
    depth_flat = _tile_lanes(depth, J)

    # --- child processing times + parent remain: the one-hot matmuls
    # of _expand_math, verbatim (COUPLED COPY — see the marker on
    # pallas_expand._expand_math: any math change there must be
    # mirrored through this block and the LB1 chain below; the parity
    # suite fails CI on divergence)
    onehot = (prmu_flat == jax.lax.broadcasted_iota(
        jnp.int32, (J, 1), 0)).astype(jnp.float32)             # (J, N)
    child_p = jax.lax.dot_general(
        p_ref[:], onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)                                        # (M, N)

    iota_v = jax.lax.broadcasted_iota(jnp.int32, (J, 1), 0)
    mh = jnp.zeros((J, TB), jnp.float32)
    zero_f = jnp.zeros((), jnp.float32)
    for i in range(J):
        sched_i = (depth <= i).astype(jnp.float32)
        mh = mh + jnp.where(prmu[i:i + 1, :] == iota_v, sched_i, zero_f)
    remain = jax.lax.dot_general(
        p_ref[:], mh, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)                                        # (M, TB)

    front_rep = _tile_lanes(front_ref[:], J)
    remain_rep = _tile_lanes(remain, J)

    cf = front_rep[0:1] + child_p[0:1]
    cf_rows = [cf]
    for k in range(1, M):
        cf = jnp.maximum(cf, front_rep[k:k + 1]) + child_p[k:k + 1]
        cf_rows.append(cf)

    # --- children permutations (prefix swap), _expand_math's emit block
    at_depth = prmu[0:1, :]
    for pos in range(1, J):
        at_depth = jnp.where(depth == pos, prmu[pos:pos + 1, :], at_depth)
    slot_flat = jnp.concatenate(
        [jnp.full((1, TB), i, jnp.int32) for i in range(J)], axis=1)
    at_depth_flat = _tile_lanes(at_depth, J)
    child_rows = []
    for pos in range(J):
        base = _tile_lanes(prmu[pos:pos + 1, :], J)
        child_rows.append(
            jnp.where(depth_flat == pos, prmu_flat,
                      jnp.where(slot_flat == pos, at_depth_flat, base)))
    children = jnp.concatenate(child_rows, axis=0)             # (J, N)
    caux = jnp.concatenate(cf_rows + [depth_flat + 1], axis=0)  # (M+1, N)

    # --- LB1 chain (machine_bound_from_parts on the child)
    cr = remain_rep[0:1] - child_p[0:1]
    tmp0 = cf_rows[0] + cr
    lb = tmp0 + tails_ref[0, 0]
    for k in range(1, M):
        crk = remain_rep[k:k + 1] - child_p[k:k + 1]
        tmp1 = jnp.maximum(tmp0, cf_rows[k] + crk)
        lb = jnp.maximum(lb, tmp1 + tails_ref[0, k])
        tmp0 = tmp1

    # --- prune against the traced cap; leaves are the caller's
    # parent-level scan, never pushed
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1) % TB
    valid_flat = (g * TB + lane_b) < n_ref[0, 0]
    maskv = (slot_flat >= depth_flat) & valid_flat
    is_leaf = (depth_flat + 1) == J
    push = maskv & ~is_leaf & (lb < cap_ref[0, 0])
    n_tile = push.sum().astype(jnp.int32)

    if BINS:
        # pruned-bound histogram, telemetry.bound_hist's exact binning:
        # the only trace the pruned children leave
        pruned = (maskv & ~is_leaf & ~push).reshape(-1)
        # bin = min(gap * BINS // ref, BINS - 1), counted as the
        # thresholds ceil(k * ref / BINS) that gap reaches, so no
        # product leaves int32 (ref is INT_MAX before an incumbent)
        ref = jnp.maximum(cap_ref[0, 0], 1)
        gap = jnp.abs(lb.reshape(-1) - ref)
        q, r = ref // BINS, ref % BINS
        bins = sum((gap >= k * q + (k * r + BINS - 1) // BINS)
                   .astype(jnp.int32) for k in range(1, BINS))
        hist_ref[:, :] = jnp.stack(
            [jnp.sum(pruned & (bins == k), dtype=jnp.int32)
             for k in range(BINS)]).reshape(BINS, 1)

    # --- within-tile compaction: the engine's packed-key partition
    key = (jnp.where(push, jnp.uint32(0), jnp.uint32(1) << 31)
           | jax.lax.broadcasted_iota(jnp.uint32, (1, N), 1))
    perm = (jax.lax.sort(key.reshape(-1), is_stable=False)
            & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
    children_c = jnp.take(children, perm, axis=1).astype(jnp.int16)
    caux_c = jnp.take(caux, perm, axis=1)
    if AUXI16:
        # the engine's pool aux rides the narrow per-instance dtype
        # (device.aux_dtype); when the class fits int16 the LB1
        # route's caux block is emitted in it directly — the i32
        # version only ever got cast at the pool write, and the wide
        # frame is pure HBM
        caux_c = caux_c.astype(jnp.int16)
    if BNDS:
        bounds_c = jnp.take(lb, perm, axis=1)

    if SW:
        one = jnp.int32(1)
        rows_i = jax.lax.broadcasted_iota(jnp.int32, (J, TB), 0)
        words = []
        for w in range(SW):
            inw = (prmu >= 32 * w) & (prmu < 32 * (w + 1))
            bit = one << jnp.where(inw, prmu - 32 * w, 0)
            pmask = jnp.sum(jnp.where((rows_i < depth) & inw, bit, 0),
                            axis=0, dtype=jnp.int32)[None, :]   # (1, TB)
            pmask_c = _tile_lanes(pmask, J)
            ainw = (prmu_flat >= 32 * w) & (prmu_flat < 32 * (w + 1))
            abit = jnp.where(
                ainw, one << jnp.where(ainw, prmu_flat - 32 * w, 0), 0)
            words.append(pmask_c | abit)
        sched_c = jnp.take(jnp.concatenate(words, axis=0), perm, axis=1)

    # --- cursor write of the survivors, in SUB-column sub-blocks each
    # gated on the live survivor count: a sub-block with no survivor
    # column never stores, so the frame only needs ONE sub-block of
    # slack past the cap (store_sub — vs a whole tile for the
    # monolithic store; the frame bytes ARE the route's HBM
    # footprint). The second gate keeps a spilling step's stores
    # inside the frame (cur <= W: stores stop past the cap, the count
    # keeps accumulating — the engine's spill test). In the fit case
    # no survivor is dropped: k < n_tile <= W - cur there, so the
    # count gate is the tighter one. A read-merge-write exact-frame
    # variant was measured WORSE on the interpret leg (the grid scan
    # carries each output buffer functionally — every in-kernel read
    # of an output adds a whole-buffer copy).
    SUB = store_sub(N)
    cur = cur_ref[0]

    for k in range(0, N, SUB):
        wk = min(SUB, N - k)

        @pl.when((jnp.int32(k) < n_tile) & (cur + k <= jnp.int32(W)))
        def _store(k=k, wk=wk):
            at = cur + k
            children_ref[:, pl.ds(at, wk)] = children_c[:, k:k + wk]
            caux_ref[:, pl.ds(at, wk)] = caux_c[:, k:k + wk]
            if BNDS:
                bounds_ref[:, pl.ds(at, wk)] = bounds_c[:, k:k + wk]
            if SW:
                sched_ref[:, pl.ds(at, wk)] = sched_c[:, k:k + wk]

    cur_ref[0] = cur + n_tile
    cnt_ref[0, 0] = cur + n_tile


@functools.partial(jax.jit, static_argnames=(
    "lb_kind", "tile", "cap_width", "with_sched", "tele_bins",
    "with_bounds", "aux_i16", "interpret"))
def fused_expand(tables: BoundTables, prmu_T, depth2, front_T,
                 n_valid, bound_cap, lb_kind: int = 1, tile: int = 1024,
                 cap_width: int = 0, with_sched: bool = False,
                 tele_bins: int = 0, with_bounds: bool = True,
                 aux_i16: bool = False, interpret: bool = False):
    """Fused expand+bound+prune+compact over one chunk. Shapes: prmu_T
    (J, B) i16, depth2 (1, B) i32, front_T (M, B) i32 (the pool aux
    widened by the caller), `n_valid` the traced popped count,
    `bound_cap` the traced pruning incumbent. Returns

        (children (J, WPAD) i16,
         caux (M+1, WPAD) i32 — or i16 under `aux_i16`,
         bounds (1, WPAD) i32 | None, sched (SW, WPAD) i32 | None,
         n_surv () i32, hist_pruned (BINS,) i64 | None)

    with WPAD = cap_width + store_sub(J*tile) (one count-gated
    sub-block of store slack; the engine narrows to cap_width where it
    must) — only columns
    [0, min(n_surv, cap_width)) are survivors, in the same global
    column order the unfused partition produces; everything past them
    is unread garbage (the engine's scratch-margin contract). Every
    output byte here is the route's whole HBM footprint, so the
    survivors-only frames come as small as their consumers allow:
    `with_bounds=False` drops the survivor-bound row (only the LB1
    telemetry histogram ever reads it — the LB2 route re-bounds
    survivors with the pair sweeps anyway), and `aux_i16` emits caux
    in the pool's narrow aux dtype when the class fits it (the i32
    version only ever got cast at the pool write). When
    n_surv > cap_width the block is INCOMPLETE and the caller must
    take its unfused fallback; hist_pruned stays valid either way
    (pruning never spills). `lb_kind` must be 1: the LB2 route uses
    this kernel as its fused LB1 prefilter (with_sched=True) and
    sweeps the surviving columns with the existing pair-sweep
    kernels."""
    assert lb_kind == 1, lb_kind
    J, B = prmu_T.shape
    M = front_T.shape[0]
    TB = tile
    assert B % TB == 0, (B, TB)
    G = B // TB
    W = cap_width        # static (static_argnames), already concrete
    assert W >= 1
    WPAD = W + store_sub(J * TB)
    SW = pallas_expand.sched_words(J) if with_sched else 0
    BINS = tele_bins
    adt = jnp.int16 if aux_i16 else jnp.int32

    p_f32 = tables.p.astype(jnp.float32)
    tails = tables.min_tails.reshape(1, M)
    n2 = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    cap2 = jnp.asarray(bound_cap, jnp.int32).reshape(1, 1)

    kernel = functools.partial(_fused_kernel, J, M, TB, W, SW, BINS,
                               with_bounds, aux_i16)
    out_specs = [
        pl.BlockSpec((J, WPAD), lambda g: (0, 0)),          # children
        pl.BlockSpec((M + 1, WPAD), lambda g: (0, 0)),      # caux
    ]
    out_shape = [
        jax.ShapeDtypeStruct((J, WPAD), jnp.int16),
        jax.ShapeDtypeStruct((M + 1, WPAD), adt),
    ]
    if with_bounds:
        out_specs.append(pl.BlockSpec((1, WPAD), lambda g: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, WPAD), jnp.int32))
    if SW:
        out_specs.append(pl.BlockSpec((SW, WPAD), lambda g: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((SW, WPAD), jnp.int32))
    out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # count
    out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.int32))
    if BINS:
        out_specs.append(pl.BlockSpec((BINS, 1), lambda g: (0, g)))
        out_shape.append(jax.ShapeDtypeStruct((BINS, G), jnp.int32))

    # x32 trace, as at pallas_expand's LB2 call: under the package's
    # global x64 the grid index maps and `iota % TB` trace as i64,
    # which Mosaic cannot lower
    with pallas_expand._x64_off():
        call = pl.pallas_call(
            kernel,
            grid=(G,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),          # p
                pl.BlockSpec(memory_space=pltpu.VMEM),          # tails
                pl.BlockSpec((J, TB), lambda g: (0, g)),        # prmu
                pl.BlockSpec((1, TB), lambda g: (0, g)),        # depth
                pl.BlockSpec((M, TB), lambda g: (0, g)),        # front
                pl.BlockSpec(memory_space=pltpu.SMEM),          # n_valid
                pl.BlockSpec(memory_space=pltpu.SMEM),          # bound_cap
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],       # cursor
            interpret=interpret,
        )
        outs = list(call(p_f32, tails, prmu_T, depth2, front_T, n2,
                         cap2))
    children, caux = outs[:2]
    outs = outs[2:]
    bounds = outs.pop(0) if with_bounds else None
    sched = outs.pop(0) if SW else None
    n_surv = outs.pop(0)[0, 0]
    hist = (outs.pop(0).astype(jnp.int64).sum(axis=1) if BINS else None)
    return children, caux, bounds, sched, n_surv, hist
