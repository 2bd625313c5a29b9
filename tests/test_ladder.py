"""Adaptive dispatch, ladder half: chunk-ladder execution in the
segmented distributed driver (TTS_LADDER / search(ladder=...)).

The contracts, pinned on the 8-device virtual CPU mesh:

- ladder OFF (the default) is the pre-ladder single-driver path —
  nothing ladder-related runs (no events, no extra compiles);
- ladder ON at a fixed incumbent (ub = opt) explores the BIT-IDENTICAL
  node set (the explored tree is order-independent when the incumbent
  cannot move) with rung switches in both directions and every audit
  invariant green under TTS_AUDIT_HARD;
- the live rung rides checkpoint meta (``ladder_rung``) and resume
  replays on the recorded rung, with totals exactly matching an
  uninterrupted run;
- measured rung admission: a tuned entry's rung admission profile
  (Params.rung_modes) admits a rung iff its probed ms/iter beats the
  tuned top rung's, degrading to the static floors when the profile
  does not cover the top rung;
- rung pre-readies are PLANNED compiles: compile_storm's signal stays
  at zero across a full ladder boot (every rung warms from abstract
  shapes — which also pins the explicit shardings cross-rung state
  handoffs need on the strict AOT path);
- a ramp/drain-heavy workload (small instance vs a big tuned chunk —
  the fixed chunk pops underfilled the whole solve) improves
  END-TO-END wall time >= 15% under the ladder (measured 1.4-2.0x
  here; the margin absorbs CI noise).
"""

import time

import numpy as np

from tpu_tree_search.engine import distributed
from tpu_tree_search.engine.ladder import (LADDER_MIN_CHUNK,
                                           LADDER_MIN_CHUNK_LB2,
                                           RungController, min_rung_for,
                                           rungs_for, rungs_from_profile)
from tpu_tree_search.obs import tracelog
from tpu_tree_search.parallel.mesh import worker_mesh
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.service.executors import ExecutorCache

# seed 1, 10x5: proof tree 22081 at its optimum 697 — big enough that
# the pool crosses rung thresholds in both directions (switch coverage)
P_BIG = PFSPInstance.synthetic(jobs=10, machines=5, seed=1).p_times
OPT_BIG = 697
# seed 7, 10x5: proof tree 2827 — the pool never fills a 2048 chunk,
# i.e. the ENTIRE solve is ramp/drain at the big fixed chunk (the
# workload family the ladder exists for)
P_SMALL = PFSPInstance.synthetic(jobs=10, machines=5, seed=7).p_times
OPT_SMALL = 797

KW = dict(capacity=1 << 16, min_seed=8, segment_iters=8)


def totals(res):
    return (res.explored_tree, res.explored_sol, res.best)


def ladder_events(since=0):
    return [r for r in tracelog.get().records()
            if r.get("name", "").startswith("ladder")][since:]


def n_records():
    return len([r for r in tracelog.get().records()
                if r.get("name", "").startswith("ladder")])


# ------------------------------------------------------------- geometry


def test_rung_geometry():
    assert rungs_for(65536) == (4096, 16384, 65536)
    assert rungs_for(2048) == (128, 512, 2048)
    assert rungs_for(1024) == (64, 256, 1024)
    # the floor collapses sub-lane rungs (and tiny chunks ladder not
    # at all — the plain driver serves them)
    assert rungs_for(64) == (64,)
    assert rungs_for(256) == (64, 256)
    assert rungs_for(2048, min_chunk=256) == (256, 512, 2048)
    # LB2's floor is the measured 256 (the pair sweep below the lane
    # width costs 220 ms/iter on the CPU mesh vs 15 at 256)
    assert min_rung_for(2) == LADDER_MIN_CHUNK_LB2
    assert min_rung_for(1) == min_rung_for(0) == LADDER_MIN_CHUNK


def test_rungs_from_profile_measured_admission():
    prof = ({"chunk": 2048, "ms_per_iter": 10.0, "evals_per_s": 1e5},
            {"chunk": 512, "ms_per_iter": 4.0, "evals_per_s": 6e4},
            {"chunk": 128, "ms_per_iter": 20.0, "evals_per_s": 3e3})
    # 512 beats the top's ms/iter -> admitted; 128 is slower per
    # iteration than the tuned chunk -> a pure loss, dropped (the
    # static LB2>=256 floor, as per-shape data)
    assert rungs_from_profile(2048, prof) == (512, 2048)
    # no profile / top rung not covered: the caller falls back to the
    # static floors
    assert rungs_from_profile(2048, None) is None
    assert rungs_from_profile(1024, prof) is None
    # a top row without a measured rate is no profile either
    assert rungs_from_profile(2048, ({"chunk": 2048},) + prof[1:]) \
        is None
    # malformed rows (a stale or hand-edited cache) degrade, never
    # crash a boot
    junk = ({"chunk": "x"}, {"no": 1}, None)
    assert rungs_from_profile(2048, tuple(junk) + prof) == (512, 2048)


def test_rung_profile_consistent_with_static_ladder():
    # sanity: profile admission returns a subset of the candidate
    # geometry rungs_for generates (plus always the top rung)
    prof = tuple({"chunk": c, "ms_per_iter": 1.0 + (c == 2048) * 9.0,
                  "evals_per_s": 1e5}
                 for c in rungs_for(2048, min_chunk=1))
    rungs = rungs_from_profile(2048, prof)
    assert 2048 in rungs
    assert set(rungs) <= set(rungs_for(2048, min_chunk=1))


def test_controller_covering_policy_and_momentum():
    drivers = {64: "d64", 256: "d256", 1024: "d1024"}
    c = RungController(drivers, n_workers=8)
    c.start(8 * 200)                 # 200/worker -> smallest covering
    assert c.current_chunk == 256
    c.observe(8 * 250)               # no doubling, 256 still covers
    assert c.current_chunk == 256
    c.observe(8 * 600)               # covering 1024 (growth clamps at
    assert c.current_chunk == 1024   # the top anyway)
    c.observe(8 * 100)               # drain: covering exactly
    assert c.current_chunk == 256
    c.observe(8 * 5)                 # drain tail
    assert c.current_chunk == 64
    assert c.switches == {"up": 1, "down": 2}
    # ramp momentum: a pool that DOUBLED inside the segment is already
    # stale at the boundary — go one rung above covering
    c2 = RungController(drivers, n_workers=8)
    c2.start(8 * 20)
    assert c2.current_chunk == 64
    c2.observe(8 * 60)               # covering is still 64, but the
    assert c2.current_chunk == 256   # 3x growth bumps one rung up


# ----------------------------------------------------------- off parity


def test_ladder_off_runs_nothing(monkeypatch):
    monkeypatch.delenv("TTS_LADDER", raising=False)
    before = n_records()
    cache = ExecutorCache()
    res = distributed.search(P_SMALL, lb_kind=1, init_ub=OPT_SMALL,
                             mesh=worker_mesh(8), chunk=2048,
                             loop_cache=cache, **KW)
    assert res.complete
    assert n_records() == before            # no ladder events at all
    assert len(cache.ledger_snapshot()) == 1   # ONE loop, no rungs


def test_single_rung_chunk_degrades_to_plain_driver():
    before = n_records()
    a = distributed.search(P_SMALL, lb_kind=1, init_ub=OPT_SMALL,
                           mesh=worker_mesh(8), chunk=64, ladder=True,
                           **KW)
    b = distributed.search(P_SMALL, lb_kind=1, init_ub=OPT_SMALL,
                           mesh=worker_mesh(8), chunk=64, ladder=False,
                           **KW)
    assert totals(a) == totals(b)
    assert n_records() == before    # rungs_for(64) is one rung: the
    #                                 controller never constructs


def test_ladder_needs_segmented_execution():
    before = n_records()
    res = distributed.search(P_SMALL, lb_kind=1, init_ub=OPT_SMALL,
                             mesh=worker_mesh(8), chunk=2048,
                             capacity=1 << 16, min_seed=8, ladder=True)
    assert res.complete
    assert n_records() == before    # no segments -> no boundaries ->
    #                                 the plain driver ran


# ---------------------------------------------------- on: bit identical


def test_ladder_bit_identical_with_switches_audit_hard(monkeypatch):
    monkeypatch.setenv("TTS_AUDIT", "1")
    monkeypatch.setenv("TTS_AUDIT_HARD", "1")
    off = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                             mesh=worker_mesh(8), chunk=2048,
                             ladder=False, **KW)
    before = n_records()
    on = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                            mesh=worker_mesh(8), chunk=2048,
                            ladder=True, **KW)
    assert totals(off) == totals(on)
    assert off.complete and on.complete
    evs = ladder_events(before)
    assert evs[0]["name"] == "ladder.start"
    assert evs[0]["source"] == "occupancy"
    dirs = {e["direction"] for e in evs if e["name"] == "ladder.switch"}
    assert "up" in dirs and "down" in dirs     # both ways exercised


def test_ladder_lb2_bit_identical(monkeypatch):
    monkeypatch.setenv("TTS_AUDIT_HARD", "1")
    p = PFSPInstance.synthetic(jobs=11, machines=20, seed=1).p_times
    off = distributed.search(p, lb_kind=2, init_ub=1810,
                             mesh=worker_mesh(8), chunk=1024,
                             ladder=False, capacity=1 << 15,
                             min_seed=8, segment_iters=8)
    on = distributed.search(p, lb_kind=2, init_ub=1810,
                            mesh=worker_mesh(8), chunk=1024,
                            ladder=True, capacity=1 << 15,
                            min_seed=8, segment_iters=8)
    assert totals(off) == totals(on)


# ------------------------------------------------------- compile booking


def test_rung_warms_are_planned_compiles():
    cache = ExecutorCache()
    distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                       mesh=worker_mesh(8), chunk=2048, ladder=True,
                       loop_cache=cache, **KW)
    rungs = rungs_for(2048)
    ledger = cache.ledger_snapshot()
    assert len(ledger) == len(rungs)
    # EVERY rung — the current one included — is pre-readied from
    # abstract shapes via="ladder": planned compiles, zero storm
    # signal (a ladder boot must not read as executable-reuse
    # breaking), and every rung executable shares the explicit
    # worker-axis shardings so cross-rung state handoffs never hit
    # the strict-AOT sharding check
    assert cache.storm_signal() == 0
    assert [e.get("via") for e in ledger] == ["ladder"] * len(rungs)
    assert all(e.get("method") == "aot" for e in ledger)


def test_prewarm_readies_every_rung():
    from tpu_tree_search.utils import config as cfg

    p = PFSPInstance.synthetic(jobs=8, machines=3, seed=3).p_times
    cache = ExecutorCache()
    overlap = cfg.env_flag(cfg.OVERLAP_FLAG)
    # min_seed sets the donor threshold each rung's loop is built with,
    # so the warm and the search below share it
    how = distributed.prewarm(p, chunk=256, capacity=4096,
                              mesh=worker_mesh(4), loop_cache=cache,
                              ladder=True, donate=overlap, min_seed=4)
    assert how == "compile"
    n_rungs = len(rungs_for(256))
    assert len(cache.ledger_snapshot()) == n_rungs
    assert cache.storm_signal() == 0      # every warm is planned
    # idempotent, and key-identical to what a ladder search builds: a
    # ladder search of the same shape/knobs compiles NOTHING new
    distributed.search(p, lb_kind=1, mesh=worker_mesh(4), chunk=256,
                       capacity=4096, min_seed=4, segment_iters=8,
                       ladder=True, loop_cache=cache)
    assert cache.storm_signal() == 0
    assert len(cache.ledger_snapshot()) == n_rungs


# ------------------------------------------------------ checkpoint/resume


def test_resume_replays_recorded_rung_exactly(tmp_path, monkeypatch):
    monkeypatch.setenv("TTS_AUDIT_HARD", "1")
    ckpt = str(tmp_path / "ladder.ckpt.npz")
    mesh = worker_mesh(8)
    # uninterrupted ladder reference
    ref = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                             mesh=mesh, chunk=2048, ladder=True, **KW)
    # truncated run: stops after ~2 segments mid-ladder, final state
    # checkpointed with the live rung in its meta
    part = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                              mesh=mesh, chunk=2048, ladder=True,
                              checkpoint_path=ckpt, max_rounds=1, **KW)
    assert not part.complete
    with np.load(ckpt) as z:
        rung = int(z["meta_ladder_rung"])
    assert rung in rungs_for(2048)
    # resume: starts on the RECORDED rung (ladder.start source=meta)
    # and finishes with totals exactly equal to the uninterrupted run
    before = n_records()
    done = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                              mesh=mesh, chunk=2048, ladder=True,
                              checkpoint_path=ckpt, **KW)
    assert done.complete
    assert totals(done) == totals(ref)
    start = [e for e in ladder_events(before)
             if e["name"] == "ladder.start"][0]
    assert start["source"] == "meta" and start["rung"] == rung


def test_cross_mode_resume_ladder_to_plain(tmp_path):
    """A ladder checkpoint resumes on a ladder-OFF run (the meta key
    is just ignored) and vice versa — the flag is a driver choice, not
    a state format."""
    ckpt = str(tmp_path / "cross.ckpt.npz")
    mesh = worker_mesh(8)
    ref = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                             mesh=mesh, chunk=2048, ladder=False, **KW)
    part = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                              mesh=mesh, chunk=2048, ladder=True,
                              checkpoint_path=ckpt, max_rounds=1, **KW)
    assert not part.complete
    done = distributed.search(P_BIG, lb_kind=1, init_ub=OPT_BIG,
                              mesh=mesh, chunk=2048, ladder=False,
                              checkpoint_path=ckpt, **KW)
    assert done.complete and totals(done) == totals(ref)


# ------------------------------------------------------------- the win


def test_ramp_drain_heavy_wall_time_improves_15pct():
    """The acceptance bar: on the 8-device CPU mesh, a ramp/drain-heavy
    workload (a small instance against the big tuned chunk — the pool
    never covers the chunk, so EVERY fixed-chunk step pays 2048-wide
    kernels for a few hundred parents) solves >= 15% faster end to end
    under the ladder. Measured 1.4-2.0x here; best-of-3 with warmed
    executables on both sides keeps compile noise out."""
    mesh = worker_mesh(8)

    def best_of(ladder, n=3):
        cache = ExecutorCache()

        def solve():
            t0 = time.perf_counter()
            r = distributed.search(P_SMALL, lb_kind=1,
                                   init_ub=OPT_SMALL, mesh=mesh,
                                   chunk=2048, ladder=ladder,
                                   loop_cache=cache, **KW)
            return time.perf_counter() - t0, r

        solve()                       # compile pass
        best, res = float("inf"), None
        for _ in range(n):
            dt, res = solve()
            best = min(best, dt)
        return best, res

    t_off, r_off = best_of(False)
    t_on, r_on = best_of(True)
    assert totals(r_off) == totals(r_on)      # same nodes, same answer
    speedup = t_off / t_on
    assert speedup >= 1.15, (
        f"ladder speedup only {speedup:.2f}x on the ramp/drain-heavy "
        f"workload (off={t_off:.3f}s on={t_on:.3f}s) — the >=15% "
        "acceptance bar")
