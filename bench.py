"""Benchmark driver: PFSP B&B node-evaluation throughput on one chip.

Runs the single-device engine on Taillard ta021 (20 jobs x 20 machines,
the hardest instance of the reference's headline single-GPU set,
BASELINE.md) with ub=opt for a fixed number of compiled loop iterations,
and reports child-bound evaluations per second for BOTH production
bounds: LB1 (the flagship rate) and LB2 (the bound that solves hard
instances — the axis that must not hide behind the LB1 headline).

Prints one JSON line per bound, LB2 last:
  {"metric": ..., "value": N, "unit": "node_evals_per_sec",
   "vs_baseline": N, "baseline": "..."}

`vs_baseline` is measured against the PER-CHIP share of the north-star
target (BASELINE.json: 1e9 node-evals/s on a v5p-32 pod => 31.25e6 per
chip) — a single-chip rate divided by a pod target would understate the
port 32x.
"""

import contextlib
import json
import os
import sys
import time

import jax
import numpy as np

from tpu_tree_search.engine import device
from tpu_tree_search.ops import batched
from tpu_tree_search.problems import taillard
from tpu_tree_search.tune import defaults as tune_defaults
from tpu_tree_search.utils import compile_cache

compile_cache.enable()


def device_stamp() -> dict:
    """The device every row ran on. A run that finds no TPU fails: a CPU
    rate is measured only when JAX_PLATFORMS=cpu asks for it."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: no TPU found (JAX platform {platform!r}); set "
            "JAX_PLATFORMS=cpu to measure the CPU on purpose")
    return {"platform": platform, "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


STAMP = device_stamp()


# north-star: 1e9 node-evals/s on a v5p-32 pod (BASELINE.json), so the
# single-chip bar is its 1/32 share
PER_CHIP_TARGET = 1e9 / 32
BASELINE_LABEL = "per-chip share of 1e9/s v5p-32 pod target"


def bench_one(tables, p, ub, lb_kind: int, chunk: int, iters: int,
              capacity: int, warm: int = 50):
    jobs = p.shape[1]
    # compile + warm the pool (past the shallow, underfilled iterations)
    state = device.init_state(jobs, capacity, ub, p_times=p)
    state = device.run(tables, state, lb_kind, chunk, max_iters=warm)
    state.size.block_until_ready()
    evals0 = int(state.evals)
    # telemetry baseline at the same cut as evals0, so the reported
    # search-efficiency counts cover exactly the timed window
    tele0 = np.asarray(state.telemetry, dtype=np.int64).copy()

    t0 = time.perf_counter()
    state = device.run(tables, state, lb_kind, chunk,
                       max_iters=warm + iters)
    state.size.block_until_ready()
    dt = time.perf_counter() - t0
    evals = int(state.evals) - evals0
    return evals, dt, state, tele0


def bench_segment_gap(p, ub, inst: int):
    """One segmented distributed mini-run measuring the mean device-idle
    gap between segments (the tts_segment_gap_seconds histogram the
    segmented drivers record; TTS_OVERLAP drives it to ~0). Emitted as
    its own LOWER-IS-BETTER row so tools/perf_sentry.py can gate
    overlap regressions once hardware rows exist. TTS_BENCH_SEGGAP=0
    skips it; the overlap flag itself is whatever TTS_OVERLAP says, and
    the row records which mode it measured."""
    from tpu_tree_search.engine import checkpoint, distributed
    from tpu_tree_search.obs import metrics as obs_metrics
    from tpu_tree_search.utils import config as cfg

    overlap = cfg.env_flag(cfg.OVERLAP_FLAG)
    # register with the driver's own buckets/help: the registry pins
    # whatever the FIRST registration says, and this call can precede
    # the driver's
    hist = obs_metrics.default().histogram(
        "tts_segment_gap_seconds", checkpoint.GAP_HELP,
        buckets=checkpoint.GAP_BUCKETS)
    before = hist.snapshot()
    # small segments + a bounded round count: enough boundaries for a
    # stable mean without stretching the bench (the gap is a per-
    # boundary cost, independent of the instance's size)
    distributed.search(p, lb_kind=1, init_ub=ub, chunk=64,
                       capacity=1 << 16, min_seed=32, segment_iters=8,
                       max_rounds=16, heartbeat=None)
    after = hist.snapshot()
    n = after["count"] - before["count"]
    if n <= 0:
        print("# segment-gap bench SKIPPED: no segment boundaries "
              "recorded", file=sys.stderr)
        return
    gap = (after["sum"] - before["sum"]) / n
    row = {
        "metric": f"pfsp_ta{inst:03d}_segment_gap_s",
        "value": round(gap, 6),
        "unit": "seconds_per_boundary",
        "direction": "lower",
        "segments": int(n),
        "overlap": int(overlap),
        **STAMP,
    }
    print(json.dumps(row))
    print(f"# segment_gap mean={gap * 1e3:.3f}ms over {n} boundaries "
          f"(overlap={int(overlap)})", file=sys.stderr)


def bench_cold_start(p, inst: int):
    """Executor-ready latency of the distributed loop, cold (fresh
    trace+compile, persisted) vs warm (disk AOT deserialize from the
    entry the cold pass just wrote) — the serving stack's restart/
    autoscale story as one LOWER-IS-BETTER bench row per cache mode.
    ``cache_mode`` travels with each row so tools/perf_sentry.py never
    judges a cold compile against a warm replay reference.
    TTS_BENCH_COLDSTART=0 skips it."""
    import shutil
    import tempfile

    from tpu_tree_search.engine import distributed
    from tpu_tree_search.parallel.mesh import worker_mesh
    from tpu_tree_search.service.aot_cache import AOTCache, probe
    from tpu_tree_search.service.executors import ExecutorCache

    if not probe():
        print("# cold-start bench SKIPPED: this jax/backend pin "
              "cannot round-trip a serialized executable",
              file=sys.stderr)
        return

    mesh = worker_mesh(None)       # the full-mesh serving shape
    root = tempfile.mkdtemp(prefix="tts_aot_bench_")
    try:
        for mode in ("cold", "warm"):
            # fresh in-process caches each pass: the second lifetime
            # sees ONLY the disk entry the first one persisted — the
            # restart scenario, not a memo hit. The cold pass runs with
            # XLA's persistent cache off, so cold means cold on every
            # later run of the bench too.
            aot = AOTCache(root)
            cache = ExecutorCache(aot=aot)
            with (compile_cache.disabled() if mode == "cold"
                  else contextlib.nullcontext()):
                t0 = time.perf_counter()
                how = distributed.prewarm(p, lb_kind=1, chunk=64,
                                          capacity=1 << 16, mesh=mesh,
                                          loop_cache=cache)
                dt = time.perf_counter() - t0
            aot.drain()
            aot.close()
            row = {
                "metric": f"pfsp_ta{inst:03d}_cold_start_s",
                "value": round(dt, 4),
                "unit": "seconds_to_executor_ready",
                "direction": "lower",
                "cache_mode": mode,
                "how": how,          # compile (cold) / disk (warm)
                **STAMP,
            }
            print(json.dumps(row))
            print(f"# cold_start mode={mode} how={how} "
                  f"executor_ready={dt:.3f}s", file=sys.stderr)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_ramp_drain(inst: int):
    """Ramp/drain phase cost of a segmented distributed solve: the
    wall seconds spent below 50% chunk occupancy at the START (ramp —
    the warm-up frontier has not yet filled the pools) and at the END
    (drain — the exhausting pools pop underfilled chunks) of one full
    solve. These are exactly the phases the fixed tuned chunk
    over-pays and the chunk ladder (TTS_LADDER=1) shrinks — every row
    carries its ``ladder`` mode so tools/perf_sentry.py never judges a
    laddered phase time against a fixed-chunk reference (cross-mode =
    SKIP, the overlap/cache_mode rule).

    The solve is the bench instance TRUNCATED to its first
    TTS_BENCH_RAMP_JOBS jobs (full solves of real Taillard instances
    are hours; ramp/drain need a complete solve to exist) at a
    deliberately ramp/drain-heavy fixed chunk (TTS_BENCH_RAMP_CHUNK) —
    the truncation is stamped in the metric name. Run TWICE per
    process with a shared executor cache; only the second (compile-
    free) pass is measured, so a cold XLA compile cannot read as ramp
    time. TTS_BENCH_RAMPDRAIN=0 skips."""
    from tpu_tree_search.engine import distributed
    from tpu_tree_search.service.executors import ExecutorCache
    from tpu_tree_search.utils import config as cfg

    ladder_on = cfg.env_flag(cfg.LADDER_FLAG)
    jobs = cfg.env_int("TTS_BENCH_RAMP_JOBS")
    chunk = cfg.env_int("TTS_BENCH_RAMP_CHUNK")
    p = taillard.processing_times(inst)[:, :jobs]
    n_dev = len(jax.devices())
    cache = ExecutorCache()
    segs = []

    def hb(rep):
        segs.append((rep.elapsed, rep.pool_size))

    def solve():
        segs.clear()
        t0 = time.perf_counter()
        # short segments: the phase attribution is per-boundary, and
        # an 8-iteration segment can swallow the whole ramp at a big
        # chunk (the first boundary already reports a filled pool)
        res = distributed.search(p, lb_kind=1, chunk=chunk,
                                 capacity=1 << 16, min_seed=32,
                                 segment_iters=4, heartbeat=hb,
                                 loop_cache=cache)
        return time.perf_counter() - t0, res

    solve()                       # compile pass (cache absorbs it)
    wall, res = solve()           # the measured, compile-free pass
    if not res.complete or len(segs) < 2:
        print("# ramp/drain bench SKIPPED: solve incomplete or too "
              f"few segments ({len(segs)})", file=sys.stderr)
        return
    half = 0.5 * n_dev * chunk
    dts = [(e - (segs[i - 1][0] if i else 0.0), pool)
           for i, (e, pool) in enumerate(segs)]
    filled = [i for i, (_, pool) in enumerate(dts) if pool >= half]
    if filled:
        # ramp = before the FIRST filled boundary, drain = after the
        # LAST one — disjoint by construction (the naive two-scan
        # version double-counted every segment into both phases when
        # the pool never filled)
        ramp = sum(dt for dt, _ in dts[:filled[0]])
        drain = sum(dt for dt, _ in dts[filled[-1] + 1:])
        never_filled = False
    else:
        # the pool never covered half the chunk: the WHOLE solve is
        # one underfilled phase — book it as ramp, zero drain, and
        # stamp the row so a reader knows the split is degenerate
        ramp, drain = wall, 0.0
        never_filled = True
    base = {
        "unit": "seconds_below_half_chunk_occupancy",
        "direction": "lower", "ladder": int(ladder_on),
        "chunk": chunk, "segments": len(segs),
        "wall_s": round(wall, 4), **STAMP,
    }
    if never_filled:
        base["never_filled"] = True
    name = f"pfsp_ta{inst:03d}j{jobs}"
    for phase, value in (("ramp", ramp), ("drain", drain)):
        print(json.dumps({"metric": f"{name}_{phase}_s",
                          "value": round(value, 4), **base}))
    print(json.dumps({"metric": f"{name}_rampdrain_wall_s",
                      "value": round(wall, 4),
                      **{**base,
                         "unit": "seconds_end_to_end_solve"}}))
    print(f"# ramp_drain ladder={int(ladder_on)} chunk={chunk} "
          f"ramp={ramp:.3f}s drain={drain:.3f}s wall={wall:.3f}s "
          f"segments={len(segs)}", file=sys.stderr)


def bench_hbm_bytes(p, ub, inst, lbs):
    """Step-HBM bytes of the compiled search loop, one LOWER-IS-BETTER
    row per bound.

    Measurement: the compiled loop's XLA memory_analysis temp-buffer
    bytes on EVERY backend — deterministic, and exactly the per-step
    HBM working set. A live
    ``peak_bytes_in_use`` delta was rejected: the peak is a lifetime
    high-water the warm run of the same executable already
    establishes, so a warm-vs-measured delta reads ~0 on exactly the
    TPU/GPU backends that report it — a lower-is-better row whose
    floor is its steady state can never FAIL. TTS_BENCH_HBM=0 skips.
    The tile stays pinned at 64 so the row matches its history."""
    import jax.numpy as jnp

    from tpu_tree_search.utils import config as cfg

    # an explicit TTS_BENCH_CHUNK is honored (the row must describe
    # the same compiled program the run's throughput rows measured);
    # only the DEFAULT stays 512 — analysis-only lowering at the
    # 65536 bench default would pay a large compile for a row whose
    # reference history is chunk-stamped anyway
    chunk = cfg.env_int("TTS_BENCH_CHUNK") or 512
    tile = 64
    jobs = p.shape[1]
    tables = batched.make_tables(p)
    for lb_kind in lbs:
        state = device.init_state(jobs, 1 << 18, ub, p_times=p)
        lowered = device._run.lower(
            tables, state, lb_kind, chunk,
            jnp.asarray(60, jnp.int64), jnp.asarray(1, jnp.int32),
            tile=tile)
        value = lowered.compile().memory_analysis() \
            .temp_size_in_bytes
        how = "memory_analysis_temp"
        row = {
            "metric": f"pfsp_ta{inst:03d}_lb{lb_kind}_hbm_bytes",
            "value": int(value),
            "unit": "bytes_per_step",
            "direction": "lower",
            "how": how,
            "chunk": chunk,
            "tile": tile,
            **STAMP,
        }
        print(json.dumps(row))
        print(f"# hbm_bytes lb={lb_kind} "
              f"how={how} bytes={int(value):,}", file=sys.stderr)


def bench_serve_rps():
    """Serving throughput on a small-instance mix: N synthetic 8x5
    PFSP instances submitted to ONE serve session, reported as
    requests/s — the megabatch acceptance row (HIGHER is better, the
    rate default). The row carries a ``megabatch`` mode channel (the
    TTS_MEGABATCH flag it ran under) so tools/perf_sentry.py never
    judges a batched rate against solo history or vice versa
    (cross-mode = SKIP, the overlap/cache_mode/ladder rule). A warm-up
    round of the same shape class pays the compile outside the timed
    window (both modes), so the row measures steady serving, not
    trace+compile. TTS_BENCH_SERVE_RPS=0 skips; TTS_BENCH_SERVE_N
    sizes the mix."""
    from tpu_tree_search.problems.pfsp import PFSPInstance
    from tpu_tree_search.service.server import (SearchRequest,
                                                SearchServer)
    from tpu_tree_search.utils import config as cfg

    n = max(cfg.env_int("TTS_BENCH_SERVE_N"), 1)
    mb = cfg.env_flag(cfg.MEGABATCH_FLAG)
    batch_max = min(cfg.env_int("TTS_BATCH_MAX"), n)

    def req(seed):
        return SearchRequest(
            p_times=PFSPInstance.synthetic(8, 5, seed=seed).p_times,
            lb_kind=1, chunk=64, capacity=1 << 14, min_seed=32,
            segment_iters=64)

    # NOT a `with` block: __enter__ would start() the scheduler before
    # the warm-up batch is fully enqueued, and an age-close could then
    # warm a partial batch's executable instead of the full-size one
    # the timed window runs
    srv = SearchServer(n_submeshes=1, autostart=False,
                       megabatch=mb, batch_max=batch_max,
                       batch_age_s=0.05)
    try:
        # warm-up: one full batch's worth of the class so the timed
        # window replays the (solo or batched) executable
        warm = [srv.submit(req(1000 + s)) for s in range(batch_max)]
        srv.start()
        for rid in warm:
            srv.result(rid, timeout=600)
        t0 = time.perf_counter()
        ids = [srv.submit(req(s)) for s in range(n)]
        for rid in ids:
            rec = srv.result(rid, timeout=600)
            if rec.state != "DONE":
                print(f"# serve-rps bench SKIPPED: request {rid} "
                      f"ended {rec.state} ({rec.error})",
                      file=sys.stderr)
                return
        dt = time.perf_counter() - t0
    finally:
        srv.close()
    rate = n / dt
    row = {
        "metric": "pfsp_serve_rps",
        "value": round(rate, 3),
        "unit": "requests_per_sec",
        "requests": n,
        "megabatch": int(mb),
        **STAMP,
    }
    print(json.dumps(row))
    print(f"# serve_rps megabatch={int(mb)} n={n} wall={dt:.3f}s "
          f"rate={rate:.3f} req/s", file=sys.stderr)


def bench_portfolio_speedup():
    """K-way bound-portfolio race (service/portfolio) vs the BEST
    member run solo, on one synthetic PFSP instance: the racing
    acceptance row. Value is best_solo_wall / race_wall (HIGHER is
    better; >= ~0.87 is the "race costs <= 1.15x the best member"
    acceptance bar) — the shared incumbent board is what keeps the
    race from paying K-fold work, and the stderr line reports the
    bound-eval ledger (race total vs the sum of K solos) that shows
    it. Every member config runs solo FIRST (a warm lap pays each
    config's compile, a timed lap measures it), so both sides of the
    ratio replay warm executables. TTS_BENCH_PORTFOLIO=0 skips;
    TTS_BENCH_PORTFOLIO_K / _JOBS size the race."""
    import dataclasses

    from tpu_tree_search import problems
    from tpu_tree_search.problems.pfsp import PFSPInstance
    from tpu_tree_search.service import portfolio as pf
    from tpu_tree_search.service.server import (SearchRequest,
                                                SearchServer)
    from tpu_tree_search.utils import config as cfg

    k = max(cfg.env_int("TTS_BENCH_PORTFOLIO_K"), 2)
    jobs = cfg.env_int("TTS_BENCH_PORTFOLIO_JOBS")
    inst = PFSPInstance.synthetic(jobs, 5, seed=7)
    # fine segments: the race only discriminates when runs span MANY
    # segment boundaries (wins/cancels land there), and a cancelled
    # loser's post-proof exposure is one segment's worth of work
    base = SearchRequest(p_times=inst.p_times, lb_kind=1, chunk=128,
                         capacity=1 << 16, min_seed=64,
                         segment_iters=32)

    # the race needs k members in flight at once: pick the largest
    # submesh count <= k+1 that divides the device pool (k alone may
    # not — 3 does not divide 8); fall back to serialized members on
    # an indivisible pool rather than skipping the row
    ndev = jax.device_count()
    n_sub = next((s for s in range(min(k + 1, ndev), 0, -1)
                  if ndev % s == 0), 1)
    srv = SearchServer(n_submeshes=n_sub, share_incumbent=True)
    try:
        plan = pf.plan_members(
            base, problems.get(base.problem), k, parent_tag="bench",
            tuner=srv.tuner,
            n_workers=srv.slots[0].mesh.devices.size)
        solo_walls, solo_evals = [], []
        for lap in ("warm", "timed"):
            solo_walls, solo_evals = [], []
            for i, (mreq, _) in enumerate(plan):
                # each solo in its OWN share_group: the board keys by
                # instance digest, so ungrouped same-instance runs
                # would seed each other's incumbents and the timed lap
                # would measure a pre-solved tree
                sreq = dataclasses.replace(
                    mreq, share_group=f"solo-{lap}-{i}",
                    tag=f"{lap}-{i}")
                t0 = time.perf_counter()
                rec = srv.result(srv.submit(sreq), timeout=600)
                dt = time.perf_counter() - t0
                if rec.state != "DONE":
                    print(f"# portfolio bench SKIPPED: solo member "
                          f"{i} ended {rec.state} ({rec.error})",
                          file=sys.stderr)
                    return
                solo_walls.append(dt)
                solo_evals.append(int(rec.result.explored_tree))
        solo_best = min(solo_walls)
        t0 = time.perf_counter()
        rec = srv.result(
            srv.submit(dataclasses.replace(base, portfolio=k,
                                           tag="bench-race")),
            timeout=600)
        race_wall = time.perf_counter() - t0
        if rec.state != "DONE":
            print(f"# portfolio bench SKIPPED: race ended "
                  f"{rec.state} ({rec.error})", file=sys.stderr)
            return
        # the losers finalize at their next segment boundary (the
        # cancel stop path) — wait them out so the eval ledger counts
        # every bound evaluation the race actually paid
        for mrid in rec.portfolio_members or []:
            srv.result(mrid, timeout=600)
        race_evals = sum(
            int(m.result.explored_tree)
            for m in (srv.records.get(rid)
                      for rid in rec.portfolio_members or [])
            if m is not None and m.result is not None)
        best = int(rec.result.best)
    finally:
        srv.close()
    # on a box with fewer cores than racing members the submeshes
    # time-slice one CPU and the race cannot beat the best member's
    # wall — the sequential-sweep sum is the honest reference there
    # (racing K configs <= trying them one after another), and the
    # row records both so hardware rows read against the right bar
    value = solo_best / race_wall
    row = {
        "metric": "pfsp_portfolio_speedup",
        "value": round(value, 3),
        "unit": "x_best_solo_wall",
        "direction": "higher",
        "portfolio": k,
        "submeshes": n_sub,
        "race_evals": race_evals,
        "solo_evals_sum": sum(solo_evals),
        "solo_wall_sum": round(sum(solo_walls), 3),
        **STAMP,
    }
    print(json.dumps(row))
    print(f"# portfolio k={k} best={best} race_wall={race_wall:.3f}s "
          f"best_solo={solo_best:.3f}s solo_sum={sum(solo_walls):.3f}s "
          f"ratio_best={race_wall / solo_best:.3f} "
          f"evals race={race_evals:,} vs solo_sum={sum(solo_evals):,}",
          file=sys.stderr)


def main():
    from tpu_tree_search.utils import config as cfg
    inst = cfg.env_int("TTS_BENCH_INSTANCE")
    p = taillard.processing_times(inst)
    jobs, machines = p.shape[1], p.shape[0]
    # measured single-chip default from the per-shape-class table
    # (tune/defaults.py — the r5 65536 retune lives THERE now, beside
    # its provenance, instead of being hardcoded here)
    chunk = (cfg.env_int("TTS_BENCH_CHUNK")
             or tune_defaults.params_for("bench", jobs,
                                         machines).chunk)
    # long window: a single dispatch through the runtime costs O(100 ms)
    # host-side; the compiled loop itself is ~0.6 ms/iteration, so short
    # windows under-report the sustained rate real runs see
    iters = cfg.env_int("TTS_BENCH_ITERS")
    capacity = 1 << 22
    lbs = [int(x) for x in cfg.env_str("TTS_BENCH_LB").split(",")]

    ub = taillard.optimal_makespan(inst)
    tables = batched.make_tables(p)

    # tuned mode (TTS_BENCH_TUNED=1): resolve the chunk through the
    # Autotuner instead of the fixed default — cache replay when
    # TTS_TUNE_CACHE is warm, else a probe sweep. Rows then carry a
    # "tuned" mode column (stamped ONLY in tuned mode, so untuned rows
    # keep matching the modeless history) and perf_sentry never judges
    # a tuned rate against fixed-chunk history (row-mode SKIP).
    tuner = None
    if cfg.env_flag("TTS_BENCH_TUNED"):
        from tpu_tree_search.tune import Autotuner
        tuner = Autotuner(cache_dir=cfg.env_str("TTS_TUNE_CACHE"))

    for lb_kind in lbs:
        tuned_row = {}
        if tuner is not None:
            params = tuner.resolve(jobs, machines, lb_kind,
                                   allow_probe=True, context="bench")
            chunk = params.chunk
            tuned_row = {"tuned": 1, "tuner_source": params.source}
            print(f"# lb={lb_kind} tuned chunk={chunk} "
                  f"(source={params.source})", file=sys.stderr)
        # LB2 steps are ~4x slower: shorten its window so the total
        # bench stays a few minutes — but only to HALF the LB1 window
        # (a quarter made the fixed ~0.5 s dispatch+fetch cost read as a
        # 10%+ rate loss), and warm PAST the ramp: LB2's early
        # iterations pop underfilled chunks for hundreds of steps, and
        # a timed window straddling the ramp under-reports the
        # sustained rate by >2x. Both windows scale with TTS_BENCH_ITERS
        # so smoke runs stay short; TTS_BENCH_WARM overrides the
        # warm-up directly.
        it = iters if lb_kind != 2 else max(200, iters // 2)
        warm = 50 if lb_kind != 2 else min(1000, max(50, iters // 2))
        # `is None`, not `or`: TTS_BENCH_WARM=0 legitimately disables
        # warm-up (cold-rate measurement) and must not fall through
        env_warm = cfg.env_int("TTS_BENCH_WARM")
        warm = warm if env_warm is None else env_warm
        evals, dt, state, tele0 = bench_one(tables, p, ub, lb_kind,
                                            chunk, it, capacity,
                                            warm=warm)
        if evals == 0 or bool(state.overflow):
            # the warm-up drained or overflowed the pool: there is no
            # sustained rate to report — say so instead of printing a
            # zero that looks like a measurement
            print(f"# lb={lb_kind} SKIPPED: timed window did no work "
                  f"(pool={int(state.size)}, "
                  f"overflow={bool(state.overflow)}) — instance "
                  "exhausts or overflows within the warm-up",
                  file=sys.stderr)
            continue
        rate = evals / dt
        row = {
            "metric": (f"pfsp_ta{inst:03d}_lb{lb_kind}"
                       "_node_evals_per_sec_per_chip"),
            "value": round(rate, 1),
            "unit": "node_evals_per_sec",
            "vs_baseline": round(rate / PER_CHIP_TARGET, 4),
            "baseline": BASELINE_LABEL,
            **STAMP,
            **tuned_row,
        }
        # with TTS_SEARCH_TELEMETRY=1 the row also captures SEARCH
        # efficiency (pruning quality, frontier position, pool
        # pressure), not just throughput — future BENCH_*.json rounds
        # can tell a faster-but-worse-pruning regression from a win.
        # Counts are TIMED-WINDOW deltas (the warm-up baseline is
        # subtracted, same cut as evals0); pool_highwater alone is
        # cumulative — a high-water mark has no window-scoped reading.
        tnow = np.asarray(state.telemetry, dtype=np.int64)
        if tnow.size:
            from tpu_tree_search.engine import telemetry as tele
            d = tele.delta_counts(tnow, tele0)
            row["telemetry"] = {
                "pruning_rate": d["pruning_rate"],
                "frontier_depth": d["frontier_depth"],
                "pool_highwater": int(tnow[tele.O_POOL_HW]),
                "branched": d["branched"],
                "pruned": d["pruned"],
            }
        print(json.dumps(row))
        print(f"# lb={lb_kind} evals={evals} dt={dt:.3f}s iters={it} "
              f"chunk={chunk} pool={int(state.size)} "
              f"best={int(state.best)}", file=sys.stderr)

    if cfg.env_flag("TTS_BENCH_HBM"):
        bench_hbm_bytes(p, ub, inst, lbs)
    if cfg.env_flag("TTS_BENCH_SEGGAP"):
        bench_segment_gap(p, ub, inst)
    if cfg.env_flag("TTS_BENCH_COLDSTART"):
        bench_cold_start(p, inst)
    if cfg.env_flag("TTS_BENCH_RAMPDRAIN"):
        bench_ramp_drain(inst)
    if cfg.env_flag("TTS_BENCH_SERVE_RPS"):
        bench_serve_rps()
    if cfg.env_flag("TTS_BENCH_PORTFOLIO"):
        bench_portfolio_speedup()


if __name__ == "__main__":
    main()
