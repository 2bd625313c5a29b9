"""Measured per-phase cost attribution for the CSV timing columns.

The reference brackets every phase of its host loop with wall-clock
timers (memcpy/malloc/kernel/genchild/poolops/idle/termination,
PFSP_statistic.c:69-112) and its `data/` scripts analyze the breakdown
(data/multigpu-stats-analysis.py:43-70). The TPU engine fuses the whole
pop->bound->prune->branch cycle into ONE compiled loop — the fusion is
the design's performance story, but it means phases cannot be timed
in-flight.

Instead the phase costs are MEASURED (not modeled) on the real instance
and the real shapes: the bound evaluation alone vs. the full step, each
compiled and timed on a warmed pool state; on a mesh additionally one
balance exchange. Wall-clock attribution then scales the measured unit
costs by each worker's actual counters:

    kernel_time[w]   = evals[w]  * (bound step time / evals per step)
    gen_child_time[w] = iters[w] * (full step - bound step)   # compaction
    time_load_bal[w] = rounds    * balance round time
    idle_time[w]     = elapsed - (the above)                  # remainder

so the columns are nonzero, per-worker-differentiated (a starved
worker's masked no-op steps land in idle), and sum to the measured loop
time by construction. memcpy/malloc stay structurally zero — those
phases truly do not exist here (HBM-resident pool, static allocation),
which is itself the honest datum.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import pallas_expand
from ..ops.batched import BoundTables


def _time_fn(fn, args, reps: int) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _time_in_loop(make_body, reps: int):
    """Returns a runner timing `reps` chained applications of
    `make_body(i, *args) -> scalar` in ONE compiled fori_loop dispatch.
    Isolated jit calls carry a host dispatch floor, which inflates
    sub-millisecond unit
    costs 4-20x — exactly the error tools/validate_attribution.py
    caught in the round-2 attribution."""
    @jax.jit
    def loop(*a):
        def body(i, acc):
            return acc + make_body(i, *a)
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

    def run(*a):
        loop(*a).block_until_ready()
        t0 = time.perf_counter()
        loop(*a).block_until_ready()
        return (time.perf_counter() - t0) / reps
    return run


@functools.partial(jax.jit, static_argnames=("lb_kind", "chunk", "tile"))
def _pop_and_bound(tables: BoundTables, state, lb_kind: int, chunk: int,
                   tile: int):
    """The step's pop + dense bound evaluation, nothing else — the
    'kernel' phase in reference terms (evaluate_gpu,
    PFSP_gpu_lib.cu:129-152). For LB2 this times the dense path through
    the same sweep implementation the engine uses (pallas pair kernel
    when lb2_kernel_fits, the XLA scan otherwise — timing the WRONG
    implementation overestimated the unit cost ~7x, caught by
    tools/validate_attribution.py). The dense sweep still overestimates
    the production two-phase route's sweep width (full N vs the
    survivor tiers); profile_phases scales it by the tier fraction."""
    from ..engine import device

    J = state.prmu.shape[0]
    M = tables.p.shape[0]
    P = int(tables.ma0.shape[0])
    if lb_kind == 2:
        # device.lb2_route owns BOTH the tile and the
        # which-implementation decision — the dense proxy must be timed
        # through the same sweep implementation the engine's route uses
        _, TB, pair_kernel = device.lb2_route(J, M, P, chunk, tile)
    else:
        TB = pallas_expand.effective_tile(J, chunk, tile, lb_kind,
                                          machines=M)
        pair_kernel = False
    p_prmu, p_depth, p_aux, *_ = device.pop_chunk(state, chunk, M)
    if lb_kind == 2 and pair_kernel:
        _, _, bounds = pallas_expand.expand(tables, p_prmu, p_depth,
                                            p_aux, lb_kind=2, tile=TB)
        return bounds
    if lb_kind == 2:
        # J > 64: production sweeps ride the streaming big-J pallas
        # kernel when its tile exists (lb2_bounds' own dispatch via
        # lb2_sweep_tile) — price THROUGH lb2_bounds so the proxy uses
        # the same implementation, not the dense-XLA scan (pricing the
        # wrong implementation is the round-2 bug class
        # tools/validate_attribution.py exists to catch)
        lb1b = pallas_expand.expand_bounds(tables, p_prmu, p_depth,
                                           p_aux, lb_kind=1, tile=TB)
        cf = pallas_expand._xla_parts(tables, p_prmu, p_depth,
                                      p_aux.astype(jnp.int32))[4]
        G = p_prmu.shape[1] // TB
        cf_cols = pallas_expand._to_cols(cf.astype(jnp.int32), G, TB, J)
        sched = pallas_expand.sched_mask_cols(p_prmu, p_depth, TB)
        return lb1b + pallas_expand.lb2_bounds(tables, cf_cols, sched)
    return pallas_expand.expand_bounds(tables, p_prmu, p_depth, p_aux,
                                       lb_kind=lb_kind, tile=TB)


def profile_phases(tables: BoundTables, state, lb_kind: int, chunk: int,
                   tile: int = 1024, reps: int = 3,
                   warm_iters: int = 8) -> dict:
    """Measured per-step phase costs on this instance/shapes.

    Returns {"bound": s/step, "step": s/step, "compact": s/step,
    "per_eval": s/eval}. `state` is any seeded pool state; it is run
    forward a few steps first (functionally — the caller's state is
    untouched) so the timed pops see realistic depths."""
    from ..engine import device

    warm = device.run(tables, state, lb_kind, chunk, max_iters=warm_iters,
                      tile=tile)
    if int(np.asarray(warm.size)) < 1:
        warm = state                      # tiny instance: profile the seed
    K = max(reps, 64)

    def timed_bound(kind):
        # K pops at K different window offsets (the -i*128 keeps the
        # loop body loop-variant so XLA cannot hoist it, while
        # preserving the pop window's lane-alignment residue — a -i
        # shift was measured ~4x slower through relayout copies)
        return _time_in_loop(
            lambda i, s: _pop_and_bound(
                tables, s._replace(size=jnp.maximum(s.size - i * 128, 1)),
                kind, chunk, tile).sum(dtype=jnp.float32), K)(warm)

    J = state.prmu.shape[0]
    M = tables.p.shape[0]
    P = int(tables.ma0.shape[0])
    from ..ops import batched as _b

    # device.lb2_route IS the engine's routing decision — sharing it is
    # what keeps the attribution from pricing a path the engine does
    # not take (the round-2 bug class tools/validate_attribution.py
    # exists to catch)
    route, _, _ = device.lb2_route(J, M, P, chunk, tile)
    if lb_kind == 2 and route == "prefilter":
        # prefilter engine: the timeable dense proxy sweeps ALL pairs
        # over the FULL grid; production sweeps run min(KH, P) head
        # pairs over the ~N/4 candidate tier and any remaining tail
        # pairs over the survivor tier — since the round-4 fine sweep
        # ladder (device.step sweep_tiers, rungs of N/64) the tail rung
        # sits snugly at ~5N/64 on the measured ta021 steady state
        # (nkeep ~43k of N=655k) rather than the old coarse 3N/32 rung.
        # Scale the sweep part by that tier fraction so the attribution
        # prices the path the engine actually takes (applies to the
        # J>64 classes too, whose sweeps run as the XLA scan over the
        # same tiers; for P <= KH the tail term is zero — one full
        # sweep at the candidate tier)
        t1 = timed_bound(1)
        t2 = max(timed_bound(2), t1)
        KH = _b.PAIR_PREFILTER
        frac = (0.25 * min(KH, P) / P
                + (5 / 64) * max(P - KH, 0) / P)
        t_bound = t1 + (t2 - t1) * frac
    else:
        t_bound = timed_bound(lb_kind)
    # full step: K live steps of the real compiled loop, one dispatch
    start = int(np.asarray(warm.iters))
    out0 = device.run(tables, warm, lb_kind, chunk, max_iters=start + 1,
                      tile=tile)
    out0.size.block_until_ready()       # compile outside the window
    t0 = time.perf_counter()
    out = device.run(tables, out0, lb_kind, chunk,
                     max_iters=start + 1 + K, tile=tile)
    out.size.block_until_ready()
    did = max(int(np.asarray(out.iters)) - start - 1, 1)
    t_step = max((time.perf_counter() - t0) / did, t_bound)
    return {
        "bound": t_bound,
        "step": t_step,
        "compact": t_step - t_bound,
        "per_eval": t_bound / float(chunk * J),
    }


def profile_balance(mesh, state_stacked, transfer_cap: int,
                    min_transfer: int, limit: int, reps: int = 3) -> float:
    """Measured wall time of one collective balance exchange on the mesh
    (the reference's `time_load_bal`, PFSP_statistic.c:123-167)."""
    from jax.sharding import PartitionSpec as P

    from ..engine import distributed
    from ..engine.device import SearchState
    from ..parallel.mesh import shard_map

    def one_round(*leaves):
        s = distributed._local_state(*leaves)
        s = distributed._balance_round(s, transfer_cap, min_transfer, limit)
        return distributed._expand(s)

    spec = tuple(P(distributed.AX) for _ in SearchState._fields)
    fn = jax.jit(shard_map(one_round, mesh, in_specs=spec, out_specs=spec))
    t_raw = _time_fn(lambda *s: fn(*s), tuple(state_stacked), reps)
    # balance rounds cannot chain inside one dispatch without measuring
    # the cheap cond-gated no-flow path instead of a real exchange, so
    # subtract the measured per-dispatch floor (a trivial jit call)
    trivial = jax.jit(lambda x: x + 1)
    t_disp = _time_fn(trivial, (jnp.float32(0.0),), reps)
    return max(t_raw - t_disp, 0.0)


def attribute(prof: dict, elapsed: float, evals, iters,
              balance_rounds: int = 0, t_balance: float = 0.0) -> dict:
    """Per-worker wall-clock attribution (see module docstring).

    `evals`/`iters` are (D,) arrays (or scalars for one device); returns
    {"kernel_time", "gen_child_time", "balance_time", "idle_time"} as
    (D,) float arrays summing (with the others) to ~elapsed."""
    evals = np.atleast_1d(np.asarray(evals, dtype=float))
    iters = np.broadcast_to(
        np.atleast_1d(np.asarray(iters, dtype=float)), evals.shape)
    kernel = evals * prof["per_eval"]
    compact = iters * prof["compact"]
    balance = np.full_like(kernel, balance_rounds * t_balance)
    idle = np.clip(elapsed - kernel - compact - balance, 0.0, None)
    return {"kernel_time": kernel, "gen_child_time": compact,
            "balance_time": balance, "idle_time": idle}


def publish_attribution(att: dict, registry=None, **labels) -> None:
    """Publish an :func:`attribute` result into a metrics registry
    (obs/metrics) as ``tts_phase_seconds{phase=, worker=, ...labels}``
    gauges — the live exposition of the per-worker breakdown that used
    to exist only in end-of-run CSV rows (the reference's
    PFSP_statistic.c columns). The search service calls this per
    heartbeat with ``request=<id>`` labels (server.py `phase_profile`);
    the CLI's CSV writer publishes its end-of-run attribution the same
    way, so `/metrics` and the CSV can never disagree."""
    from ..obs import metrics as obs_metrics

    reg = registry if registry is not None else obs_metrics.default()
    g = reg.gauge("tts_phase_seconds",
                  "measured per-worker wall-clock phase attribution")
    for phase, arr in att.items():
        name = phase[:-5] if phase.endswith("_time") else phase
        for w, v in enumerate(np.atleast_1d(np.asarray(arr, float))):
            g.set(float(v), phase=name, worker=w, **labels)
