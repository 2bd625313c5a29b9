"""Fused Pallas bound+prune+compact route (TTS_FUSED, ops/pallas_fused).

The contracts, pinned on the CPU backend under the Pallas INTERPRETER
(the hardware lowering is gated to TPU backends and validated on the
next on-chip round — the kernel LOGIC is what CI can and must pin):

- the fused route is BIT-IDENTICAL to the unfused pipeline — counts,
  optimum, eval totals, per-worker counter arrays and full telemetry
  blocks — across lb 1/2, tile-remainder chunk sizes, the distributed
  8-worker driver, and a ladder run that switches rungs mid-solve, all
  with the node-conservation audit hard-failing (TTS_AUDIT_HARD);
- admission: the hardware route is refused (Mosaic cannot lower the
  kernels' sort), and the interpreter route exists to validate logic
  and admits any shape of the LB1/LB2 steps;
- spill semantics: a chunk whose survivors outgrow the kernel's
  cap_width keeps an exact COUNT (stores stop, the counter keeps
  accumulating) and a valid pruned-bound histogram, and the stored
  prefix below the cap is unchanged — the engine's lax.cond fallback
  re-runs the step unfused on bit-identical bound math;
- the tuner's per-rung profitability mask (Params.rung_modes) feeds
  measured rung admission (ladder.rungs_from_profile — subsuming the
  static LB2 floor) and per-rung kernel-vs-matmul selection
  (ladder.fused_for), with the TTS_FUSED master switch always able to
  force "off".
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_tree_search.engine import device, distributed
from tpu_tree_search.engine.ladder import (fused_for, rungs_for,
                                           rungs_from_profile)
from tpu_tree_search.obs import tracelog
from tpu_tree_search.ops import batched, pallas_fused
from tpu_tree_search.parallel.mesh import worker_mesh
from tpu_tree_search.problems.pfsp import PFSPInstance

SCALARS = ("tree", "sol", "best", "evals", "iters", "overflow")


def _table(jobs=8, machines=5, seed=0):
    return PFSPInstance.synthetic(jobs=jobs, machines=machines,
                                  seed=seed).p_times


def _run_pair(p, lb, chunk, tile=64, capacity=1 << 14, telemetry=True):
    """The same solve through the unfused and the fused-interpret
    pipelines, from identical seeded states."""
    tables = batched.make_tables(p)
    jobs = p.shape[1]
    s0 = device.init_state(jobs, capacity, None, p_times=p,
                           telemetry=telemetry)
    a = device.run(tables, s0, lb, chunk, tile=tile, fused="off")
    b = device.run(tables, s0, lb, chunk, tile=tile, fused="interpret")
    return a, b


def _assert_states_equal(a, b):
    for f in SCALARS:
        assert int(getattr(a, f)) == int(getattr(b, f)), f
    assert np.array_equal(np.asarray(a.telemetry),
                          np.asarray(b.telemetry))


# -------------------------------------------------------- single device


# Interpreter emulation makes the parity solves the most expensive
# tests in the tier-1 suite; only the [64-64-1] canary stays unmarked
# (tier-1 runs -m 'not slow' under a hard wall-clock cap), the rest
# run in the CI fused-interpret leg, which drops the filter.
@pytest.mark.parametrize("lb", [1, pytest.param(2, marks=pytest.mark.slow)])
@pytest.mark.parametrize("chunk,tile", [
    (64, 64),     # tile == chunk: one tile per step
    pytest.param(128, 64, marks=pytest.mark.slow),   # multi-tile grid
    pytest.param(96, 64, marks=pytest.mark.slow),
    #               tile-remainder chunk: effective_tile falls back to
    #               one batch-wide tile (96), G == 1
    pytest.param(64, 1024, marks=pytest.mark.slow),
    #               requested tile above the chunk: the shrink path
])
def test_fused_parity_single_device(lb, chunk, tile):
    # telemetry ON: the masked-add buckets and both bound histograms
    # (including the kernel's pruned-bound tiles) must match the dense
    # route bit for bit — bound_hist_exact's precondition. The LB2
    # ramp steps (no incumbent yet -> nothing prunes) overflow the
    # kernel's N/4 survivor cap, so this also walks the spill cond's
    # unfused fallback branch.
    a, b = _run_pair(_table(), lb, chunk, tile=tile)
    _assert_states_equal(a, b)


@pytest.mark.slow
def test_fused_parity_larger_instance():
    # 12 jobs: deeper tree, multiple pool refills, nonzero pruning on
    # both routes once the first leaves land
    for lb in (1, 2):
        a, b = _run_pair(_table(jobs=12, seed=3), lb, 128,
                         capacity=1 << 16)
        _assert_states_equal(a, b)


def test_fused_mode_is_static_not_ambient(monkeypatch):
    # an explicit mode string wins over the env: the step's dispatch
    # is a static jit argument resolved host-side, never an env read
    # at trace time
    monkeypatch.setenv(pallas_fused.FUSED_FLAG, "1")
    monkeypatch.setenv(pallas_fused.FUSED_INTERPRET_FLAG, "1")
    p = _table()
    tables = batched.make_tables(p)
    s0 = device.init_state(8, 1 << 14, None, p_times=p)
    a = device.run(tables, s0, 1, 64, fused="off")
    monkeypatch.delenv(pallas_fused.FUSED_FLAG)
    monkeypatch.delenv(pallas_fused.FUSED_INTERPRET_FLAG)
    b = device.run(tables, s0, 1, 64, fused="interpret")
    for f in SCALARS:
        assert int(getattr(a, f)) == int(getattr(b, f)), f


# --------------------------------------------------- distributed driver


def _dist(p, lb, fused, monkeypatch, **kw):
    if fused:
        monkeypatch.setenv(pallas_fused.FUSED_FLAG, "1")
        monkeypatch.setenv(pallas_fused.FUSED_INTERPRET_FLAG, "1")
    else:
        monkeypatch.delenv(pallas_fused.FUSED_FLAG, raising=False)
        monkeypatch.delenv(pallas_fused.FUSED_INTERPRET_FLAG,
                           raising=False)
    return distributed.search(p, lb_kind=lb, mesh=worker_mesh(8),
                              capacity=1 << 14, min_seed=8, **kw)


@pytest.mark.slow
@pytest.mark.parametrize("lb", [1, 2])
def test_fused_parity_distributed_audit_hard(lb, monkeypatch):
    # full 8-worker SPMD parity under the hard node-conservation
    # audit: totals, the per-WORKER counter arrays and the merged
    # telemetry summary all match — the fused route must be invisible
    # to every accounting identity the audit checks
    monkeypatch.setenv("TTS_AUDIT", "1")
    monkeypatch.setenv("TTS_AUDIT_HARD", "1")
    monkeypatch.setenv("TTS_SEARCH_TELEMETRY", "1")
    p = _table(jobs=9, seed=2)
    off = _dist(p, lb, False, monkeypatch, chunk=64)
    on = _dist(p, lb, True, monkeypatch, chunk=64)
    assert (off.explored_tree, off.explored_sol, off.best) \
        == (on.explored_tree, on.explored_sol, on.best)
    assert off.complete and on.complete
    assert set(off.per_device) == set(on.per_device)
    for k in off.per_device:
        assert np.array_equal(np.asarray(off.per_device[k]),
                              np.asarray(on.per_device[k])), k
    assert off.telemetry == on.telemetry


@pytest.mark.slow
def test_fused_parity_ladder_switches_mid_solve(monkeypatch):
    # the per-rung dispatch surface: a chunk-2048 ladder over a
    # 10x5 proof tree switches rungs in BOTH directions mid-solve
    # (tests/test_ladder.py pins the switch behavior itself); with the
    # fused route on, every rung driver carries the fused step and the
    # totals must not move, audit hard-failing throughout
    monkeypatch.setenv("TTS_AUDIT", "1")
    monkeypatch.setenv("TTS_AUDIT_HARD", "1")
    p = PFSPInstance.synthetic(jobs=10, machines=5, seed=1).p_times
    kw = dict(chunk=2048, init_ub=697, ladder=True, segment_iters=8)
    off = _dist(p, 1, False, monkeypatch, **kw)
    before = len([r for r in tracelog.get().records()
                  if r.get("name") == "ladder.switch"])
    on = _dist(p, 1, True, monkeypatch, **kw)
    assert (off.explored_tree, off.explored_sol, off.best) \
        == (on.explored_tree, on.explored_sol, on.best)
    switches = [r for r in tracelog.get().records()
                if r.get("name") == "ladder.switch"][before:]
    dirs = {e["direction"] for e in switches}
    assert "up" in dirs and "down" in dirs


# ------------------------------------------------------------ admission


def test_fused_ok_gates():
    # off mode admits nothing; unknown bounds admit nothing; no hw
    # route exists; the interpreter route admits LB1 and LB2
    assert not pallas_fused.fused_ok("off", 1)
    assert not pallas_fused.fused_ok("interpret", 0)
    assert not pallas_fused.fused_ok("interpret", 3)
    assert not pallas_fused.fused_ok("hw", 1)
    assert pallas_fused.fused_ok("interpret", 1)
    assert pallas_fused.fused_ok("interpret", 2)


def test_resolve_mode(monkeypatch):
    # env resolution is host-side and backend-aware: TTS_FUSED alone
    # on a non-TPU backend resolves OFF (never a silent interpreter
    # run in production), TTS_FUSED_INTERPRET opts the CPU mesh in
    monkeypatch.delenv(pallas_fused.FUSED_FLAG, raising=False)
    monkeypatch.delenv(pallas_fused.FUSED_INTERPRET_FLAG, raising=False)
    assert pallas_fused.resolve_mode(None) == "off"
    monkeypatch.setenv(pallas_fused.FUSED_FLAG, "1")
    assert pallas_fused.resolve_mode(None) == "off"
    monkeypatch.setenv(pallas_fused.FUSED_INTERPRET_FLAG, "1")
    assert pallas_fused.resolve_mode(None) == "interpret"
    # explicit strings pass through (the tests' control channel)
    assert pallas_fused.resolve_mode("off") == "off"
    assert pallas_fused.resolve_mode("interpret") == "interpret"
    # Mosaic cannot lower the kernels' sort: asking for the hardware
    # route, explicitly or through the env on a TPU, names the refusal
    with pytest.raises(RuntimeError, match="Mosaic.*sort"):
        pallas_fused.resolve_mode("hw")
    monkeypatch.setattr(pallas_fused.jax, "default_backend",
                        lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic.*sort"):
        pallas_fused.resolve_mode(None)


# ---------------------------------------------------------------- spill


def test_fused_expand_spill_count_and_prefix():
    # parents all at depth 0 with no incumbent: every non-leaf child
    # survives, far past a small cap. The count must stay EXACT (the
    # engine's spill cond keys off it), the under-cap prefix must
    # equal the roomy call's (stores stop at the cap, they never
    # corrupt what landed below it), and the pruned histogram stays
    # valid (pruning never spills)
    p = _table(jobs=8, machines=5, seed=1)
    tables = batched.make_tables(p)
    J, B = 8, 64
    prmu = jnp.broadcast_to(jnp.arange(J, dtype=jnp.int16)[:, None],
                            (J, B))
    depth = jnp.zeros((1, B), jnp.int32)
    front = jnp.zeros((5, B), jnp.int32)
    kw = dict(lb_kind=1, tile=64, tele_bins=8, interpret=True)
    big = pallas_fused.fused_expand(tables, prmu, depth, front,
                                    jnp.int32(B), jnp.int32(10 ** 6),
                                    cap_width=J * B, **kw)
    small = pallas_fused.fused_expand(tables, prmu, depth, front,
                                      jnp.int32(B), jnp.int32(10 ** 6),
                                      cap_width=128, **kw)
    n_big, n_small = int(big[4]), int(small[4])
    assert n_big == J * B           # every child is non-leaf at d=0
    assert n_small == n_big         # count keeps accumulating on spill
    assert np.array_equal(np.asarray(big[0])[:, :128],
                          np.asarray(small[0])[:, :128])
    assert np.array_equal(np.asarray(big[5]), np.asarray(small[5]))
    assert int(np.asarray(big[5]).sum()) == 0   # nothing pruned


def test_fused_expand_invalid_columns_masked():
    # n_valid below the chunk: the padding columns past the popped
    # count must not contribute survivors
    p = _table(jobs=8, machines=5, seed=1)
    tables = batched.make_tables(p)
    J, B = 8, 64
    prmu = jnp.broadcast_to(jnp.arange(J, dtype=jnp.int16)[:, None],
                            (J, B))
    depth = jnp.zeros((1, B), jnp.int32)
    front = jnp.zeros((5, B), jnp.int32)
    out = pallas_fused.fused_expand(tables, prmu, depth, front,
                                    jnp.int32(5), jnp.int32(10 ** 6),
                                    lb_kind=1, tile=64,
                                    cap_width=J * B, interpret=True)
    assert int(out[4]) == 5 * J


def test_store_sub_slack_geometry():
    # the sub-block width IS the frame slack — one function, shared by
    # the kernel and its caller, lane-aligned for the hardware route
    assert pallas_fused.store_sub(64) == 64      # tiny tiles: one store
    assert pallas_fused.store_sub(1280) == 256
    assert pallas_fused.store_sub(576) == 128
    big = pallas_fused.store_sub(20480)
    assert big % 128 == 0 and big < 20480


# --------------------------------------------- per-rung profitability


def test_rungs_from_profile_measured_admission():
    prof = ({"chunk": 2048, "winner": "unfused", "ms_per_iter": 10.0},
            {"chunk": 512, "winner": "fused", "ms_per_iter": 4.0},
            {"chunk": 128, "winner": "fused", "ms_per_iter": 20.0})
    # 512 beats the top's ms/iter -> admitted; 128 is slower per
    # iteration than the tuned chunk -> a pure loss, dropped (the
    # static LB2>=256 floor, as per-shape data)
    assert rungs_from_profile(2048, prof) == (512, 2048)
    # no profile / top rung not covered: the caller falls back to the
    # static floors
    assert rungs_from_profile(2048, None) is None
    assert rungs_from_profile(1024, prof) is None
    # malformed rows (a stale or hand-edited cache) degrade, never
    # crash a boot
    junk = ({"chunk": "x"}, {"no": 1}, None)
    assert rungs_from_profile(2048, tuple(junk) + prof) == (512, 2048)


def test_rungs_from_profile_judges_the_boots_own_pipeline():
    # a rung whose FUSED rate won the probe is still a pure loss on a
    # TTS_FUSED=0 boot that can only run its matmul rate — admission
    # must judge the pipeline fused_for selects for THIS boot, per
    # pipeline-rate row fields (ms_per_iter_{unfused,fused})
    prof = ({"chunk": 2048, "winner": "unfused", "ms_per_iter": 10.0,
             "ms_per_iter_unfused": 10.0, "ms_per_iter_fused": 12.0,
             "evals_per_s_fused": 1e5},
            {"chunk": 512, "winner": "fused", "ms_per_iter": 4.0,
             "ms_per_iter_unfused": 15.0, "ms_per_iter_fused": 4.0,
             "evals_per_s_fused": 3e5})
    # fused boot: 512 runs fused at 4.0 < top's unfused 10.0 -> in
    assert rungs_from_profile(2048, prof,
                              fused_mode="interpret") == (512, 2048)
    # matmul-only boot: 512 runs unfused at 15.0 > 10.0 -> pure loss
    assert rungs_from_profile(2048, prof, fused_mode="off") == (2048,)
    # masks persisted before the per-pipeline fields fall back to the
    # winner's ms_per_iter (the pre-fix behavior, never a crash)
    old = ({"chunk": 2048, "winner": "unfused", "ms_per_iter": 10.0},
           {"chunk": 512, "winner": "fused", "ms_per_iter": 4.0})
    assert rungs_from_profile(2048, old, fused_mode="off") \
        == (512, 2048)
    # a rung whose FUSED probe failed (field present but None) is
    # refused on a fused boot: fused_for's never-measured guard runs
    # the rung fused, so its unfused 2.0 must not admit it — an
    # unmeasured pipeline is never admitted on the other's rate
    failed = ({"chunk": 2048, "winner": "unfused", "ms_per_iter": 10.0,
               "ms_per_iter_unfused": 10.0, "ms_per_iter_fused": 12.0,
               "evals_per_s_fused": 1e5},
              {"chunk": 512, "winner": "unfused", "ms_per_iter": 2.0,
               "ms_per_iter_unfused": 2.0, "ms_per_iter_fused": None,
               "evals_per_s_fused": None})
    assert rungs_from_profile(2048, failed,
                              fused_mode="interpret") == (2048,)
    assert rungs_from_profile(2048, failed, fused_mode="off") \
        == (512, 2048)


def test_fused_for_master_switch_and_refinement():
    prof = ({"chunk": 512, "winner": "unfused",
             "evals_per_s_fused": 1e5},
            {"chunk": 128, "winner": "fused",
             "evals_per_s_fused": 3e5})
    # the env master switch gates everything
    assert fused_for(512, prof, "off") == "off"
    assert fused_for(128, prof, "off") == "off"
    # a profile row can only REFINE a fused-enabled run back to the
    # matmul pipeline, never enable fused while the switch is off
    assert fused_for(512, prof, "interpret") == "off"
    assert fused_for(128, prof, "interpret") == "interpret"
    # unprofiled rungs take the resolved env mode
    assert fused_for(64, prof, "hw") == "hw"
    assert fused_for(64, None, "hw") == "hw"
    # an "unfused" verdict from a mask that never MEASURED the fused
    # pipeline (TTS_TUNE_RUNGS=1 on a matmul-only boot records
    # winner="unfused", evals_per_s_fused=None for every rung by
    # construction) must NOT disable a later fused-enabled boot
    matmul_only = ({"chunk": 512, "winner": "unfused",
                    "evals_per_s_fused": None},
                   {"chunk": 128, "winner": "unfused"})
    assert fused_for(512, matmul_only, "interpret") == "interpret"
    assert fused_for(128, matmul_only, "hw") == "hw"


def test_rung_profile_consistent_with_static_ladder():
    # sanity: profile admission returns a subset of the candidate
    # geometry rungs_for generates (plus always the top rung)
    prof = tuple({"chunk": c, "winner": "unfused",
                  "ms_per_iter": 1.0 + (c == 2048) * 9.0}
                 for c in rungs_for(2048, min_chunk=1))
    rungs = rungs_from_profile(2048, prof)
    assert 2048 in rungs
    assert set(rungs) <= set(rungs_for(2048, min_chunk=1))
