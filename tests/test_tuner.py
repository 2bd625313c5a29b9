"""Adaptive dispatch, tuner half: the measured-defaults table, the
warmed probe harness, the persistent tuning cache and the Autotuner's
cache -> probe -> defaults resolution.

The contracts, pinned deterministically on the CPU backend:

- ONE defaults table (tune/defaults.py) feeds utils/config, bench and
  the serving request model — the three hardcoded constants that used
  to drift are now reads of it;
- a cold tune() probes (warmed same-state measurements) and persists;
  a RESTARTED tuner over the same cache dir replays the winner with
  ZERO probe executions (the probe ledger stays empty);
- the request hot path (allow_probe=False) never probes: cold cache
  resolves straight to the defaults tier;
- a wrong-fingerprint entry is IGNORED (and overwritten by the next
  probe), never consumed; a corrupt/truncated entry is QUARANTINED
  (*.corrupt) and re-probed — the aot_cache discipline at tuning scale;
- distributed.search(chunk=None, tuner=...) consumes the tuned entry
  (the executor key proves which chunk actually compiled);
- spool payloads opt in with {"tuned": true}.
"""

import json
import os
import sys

import numpy as np
import pytest

from tpu_tree_search.engine import distributed
from tpu_tree_search.parallel.mesh import worker_mesh
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.service import SearchRequest
from tpu_tree_search.service.executors import ExecutorCache
from tpu_tree_search.service.spool import request_from_payload
from tpu_tree_search.tune import (Autotuner, ProbeError, ProbeHarness,
                                  TuningCache, defaults,
                                  measure_balance_periods)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))

# tiny probe knobs: the contracts are about plumbing and persistence,
# not about measuring real optima on the virtual mesh
TUNE_KW = dict(chunks=(8, 16), periods=(2, 4), window_iters=6,
               warm_iters=20, capacity=1 << 12, repeats=1)


def small(seed=1, jobs=8, machines=3):
    return PFSPInstance.synthetic(jobs=jobs, machines=machines,
                                  seed=seed).p_times


# ------------------------------------------------------------- defaults


def test_defaults_table_is_the_single_source():
    from tpu_tree_search.utils.config import NQueensConfig, PFSPConfig
    assert PFSPConfig().chunk == defaults.CLI_CHUNK_DEFAULT
    assert PFSPConfig().balance_period == defaults.BALANCE_PERIOD_DEFAULT
    assert NQueensConfig().chunk == defaults.CLI_CHUNK_DEFAULT
    req = SearchRequest(p_times=small())
    assert req.chunk == defaults.SERVING_CHUNK_DEFAULT
    assert req.balance_period == defaults.BALANCE_PERIOD_DEFAULT
    # the measured bench row (the r5 single-chip retune) lives in the
    # table, per shape class
    assert defaults.params_for("bench", 20, 20).chunk \
        == defaults.BENCH_CHUNK_DEFAULT
    assert defaults.params_for("serving", 20, 20).chunk \
        == defaults.SERVING_CHUNK_DEFAULT
    with pytest.raises(ValueError):
        defaults.params_for("nonsense")


def test_request_chunk_none_is_valid_auto():
    req = SearchRequest(p_times=small(), chunk=None, balance_period=None)
    assert req.validate() is None
    assert SearchRequest(p_times=small(), chunk=0).validate() is not None


def test_spool_tuned_payload_opens_the_knobs():
    p = small()
    req = request_from_payload({"p_times": p.tolist(), "tuned": True})
    assert req.chunk is None and req.balance_period is None
    # explicit knobs in the same payload win over the tuned flag
    req2 = request_from_payload({"p_times": p.tolist(), "tuned": True,
                                 "chunk": 32})
    assert req2.chunk == 32 and req2.balance_period is None


# ---------------------------------------------------------------- probe


def test_probe_harness_same_state_measurement():
    h = ProbeHarness(small(), lb_kind=1, capacity=1 << 12, warm_chunk=8,
                     warm_iters=20, window_iters=6, repeats=1)
    r = h.measure(8, 4)
    assert r.evals > 0 and r.evals_per_s > 0 and r.ms_per_iter > 0
    assert not r.underfilled
    # a chunk above the warmed pool is flagged: its rate is a ramp
    # rate, and the tuner must deprioritize it
    big = h.measure(256, 4)
    assert big.underfilled
    # a chunk whose scratch margin eats the whole pool is refused
    # loudly (the tuner drops the candidate)
    with pytest.raises(ProbeError):
        h.measure(1 << 11, 4)
    # identical state across candidates: the pool the window started
    # from is the same for every measurement
    assert r.pool_start == big.pool_start


def test_probe_harness_refuses_exhausted_instance():
    # 4 jobs: the warm-up drains the whole tree — no steady state
    with pytest.raises(ProbeError):
        ProbeHarness(small(jobs=4), warm_chunk=8, warm_iters=50,
                     capacity=1 << 12)


def test_measure_balance_periods_legacy_rows():
    rows = measure_balance_periods(small(), 1, 8, (2, 4),
                                   capacity=1 << 12, warm_iters=20,
                                   window_iters=6, repeats=1)
    assert [r["balance_period"] for r in rows] == [2, 4]
    assert all(r["ms_per_iter"] > 0 and r["evals_per_s"] > 0
               for r in rows)


# ---------------------------------------------------------------- tuner


def test_tune_persists_and_warm_boot_replays_zero_probes(tmp_path):
    p = small()
    t1 = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    params = t1.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert params.source == "probe"
    assert params.chunk in TUNE_KW["chunks"]
    assert params.balance_period in TUNE_KW["periods"] + (4,)
    assert t1.probes_run > 0 and len(t1.ledger) == t1.probes_run
    assert t1.cache.snapshot()["writes"] == 1

    # the restarted process: same dir, fresh tuner — the winner replays
    # with ZERO probe executions (the warm-boot contract, ledger-pinned)
    t2 = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    p2 = t2.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert (p2.chunk, p2.balance_period) == (params.chunk,
                                             params.balance_period)
    assert p2.source == "cache"
    assert t2.probes_run == 0 and t2.ledger == []
    assert t2.cache.snapshot()["hits"] == 1


def test_hot_path_never_probes(tmp_path):
    t = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    params = t.resolve(8, 3, 1, allow_probe=False)
    assert params.source == "default"
    assert params.chunk == defaults.SERVING_CHUNK_DEFAULT
    assert t.probes_run == 0
    # and without any cache at all, the same defaults tier answers
    t_nocache = Autotuner(**TUNE_KW)
    assert t_nocache.resolve(8, 3, 1).source == "default"


def test_fingerprint_mismatch_ignored_and_overwritten(tmp_path):
    p = small()
    root = tmp_path / "tune"
    ta = Autotuner(cache_dir=root, **TUNE_KW)
    ta.cache.fingerprint = dict(ta.cache.fingerprint, sim_runtime="A")
    pa = ta.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert pa.source == "probe"

    # runtime B (topology/platform drift simulation) must IGNORE A's
    # entry — a TPU optimum must never drive a CPU mesh — and re-probe
    tb = Autotuner(cache_dir=root, **TUNE_KW)
    tb.cache.fingerprint = dict(tb.cache.fingerprint, sim_runtime="B")
    pb = tb.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert pb.source == "probe" and tb.probes_run > 0
    snap = tb.cache.snapshot()
    assert snap["mismatches"] == 1 and snap["hits"] == 0
    assert snap["quarantined"] == 0     # a mismatch is not corruption

    # B's re-probe OVERWROTE the entry: B restarted now replays it
    tb2 = Autotuner(cache_dir=root, **TUNE_KW)
    tb2.cache.fingerprint = dict(tb2.cache.fingerprint, sim_runtime="B")
    pb2 = tb2.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert pb2.source == "cache" and tb2.probes_run == 0


@pytest.mark.parametrize("damage", ["flip", "truncate"])
def test_corrupt_entry_quarantined_and_reprobed(tmp_path, damage):
    p = small()
    root = tmp_path / "tune"
    t1 = Autotuner(cache_dir=root, **TUNE_KW)
    ref = t1.resolve(8, 3, 1, allow_probe=True, p_times=p)

    (entry,) = [f for f in root.iterdir() if f.suffix == ".tune"]
    blob = bytearray(entry.read_bytes())
    if damage == "flip":
        blob[len(blob) // 2] ^= 0xFF
        entry.write_bytes(bytes(blob))
    else:
        entry.write_bytes(bytes(blob[: len(blob) // 2]))

    t2 = Autotuner(cache_dir=root, **TUNE_KW)
    p2 = t2.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert p2.source == "probe"          # re-probed, never loaded
    snap = t2.cache.snapshot()
    assert snap["errors"] == 1 and snap["quarantined"] == 1
    quarantined = [f for f in root.iterdir()
                   if f.name.endswith(".corrupt")]
    assert len(quarantined) == 1
    # the re-probe re-persisted a clean entry beside the quarantine
    t3 = Autotuner(cache_dir=root, **TUNE_KW)
    p3 = t3.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert p3.source == "cache" and t3.probes_run == 0
    assert (p3.chunk, p3.balance_period) == (ref.chunk,
                                             ref.balance_period) \
        or p3.chunk in TUNE_KW["chunks"]   # a re-probe may pick the
    #   other near-tied candidate; what matters is it came from disk


def test_repeat_quarantines_keep_distinct_forensic_copies(tmp_path):
    """Quarantine names are per-writer unique AND counter-suffixed:
    the same entry corrupted twice (or by N processes racing on shared
    fleet storage) keeps BOTH forensic copies — the second rename must
    not os.replace over the first."""
    from tpu_tree_search.tune.cache import TuningCache

    cache = TuningCache(tmp_path / "tune")
    key = ("pfsp", 8, 3, 1, 4)
    for round_ in range(2):
        cache.store(key, {"chunk": 64, "round": round_})
        path = cache.path_for(key)
        path.write_bytes(b"\xff torn" * 4)
        assert cache.load(key) is None
    quarantined = sorted(f.name for f in (tmp_path / "tune").iterdir()
                         if f.name.endswith(".corrupt"))
    assert len(quarantined) == 2, quarantined     # both copies survive
    assert len(set(quarantined)) == 2
    assert cache.snapshot()["quarantined"] == 2
    # and the cache still works: a clean store replays
    cache.store(key, {"chunk": 128})
    assert cache.load(key)["chunk"] == 128


def test_search_consumes_tuned_entry(tmp_path):
    """distributed.search(chunk=None, tuner=...) compiles the TUNED
    chunk — proven from the executor key, not from a log line."""
    p = small()
    tuner = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    tuned = tuner.resolve(8, 3, 1, n_workers=4, allow_probe=True,
                          p_times=p)
    cache = ExecutorCache()
    got = distributed.search(p, lb_kind=1, mesh=worker_mesh(4),
                             chunk=None, balance_period=None,
                             capacity=1 << 12, min_seed=4,
                             loop_cache=cache, tuner=tuner)
    keys = [e["key"] for e in cache.ledger_snapshot()]
    assert len(keys) == 1
    assert keys[0].startswith(f"pfsp/8/3/1/{tuned.chunk}/")
    # and the tuned run solves to the same optimum as a fixed-knob one
    ref = distributed.search(p, lb_kind=1, mesh=worker_mesh(4),
                             chunk=8, capacity=1 << 12, min_seed=4)
    assert got.best == ref.best


def test_tuning_cache_key_is_stable(monkeypatch):
    monkeypatch.delenv("TTS_TUNE_RUNGS", raising=False)
    k1 = Autotuner.key(20, 10, 1, 8)
    assert k1 == ("pfsp", 20, 10, 1, 8)
    c = TuningCache.__new__(TuningCache)   # path_for only needs root
    import pathlib
    c.root = pathlib.Path("/x")
    assert c.path_for(k1) == c.path_for(("pfsp", 20, 10, 1, 8))
    assert c.path_for(k1) != c.path_for(("pfsp", 20, 10, 2, 8))
    # the key never splits on an env: a boot that also gathers the
    # rung admission profile replays (and refreshes) the same entry
    monkeypatch.setenv("TTS_TUNE_RUNGS", "1")
    assert Autotuner.key(20, 10, 1, 8) == k1
    assert Autotuner.key(6, 6, 1, 8, problem="tsp") \
        == ("tsp", 6, 6, 1, 8)
    # only a megabatched dispatch appends a suffix
    assert Autotuner.key(20, 10, 1, 8, batch=1) == k1
    assert Autotuner.key(20, 10, 1, 8, batch=4) \
        == ("pfsp", 20, 10, 1, 8, "batch", 4)


# --------------------------------------------------------------- report


def test_tune_report_renders_entries_and_quarantine(tmp_path):
    import tune_report

    root = tmp_path / "tune"
    t = Autotuner(cache_dir=root, **TUNE_KW)
    t.resolve(8, 3, 1, allow_probe=True, p_times=small())
    (root / "deadbeef.tune.corrupt").write_bytes(b"garbage")
    entries = [tune_report.read_entry(str(f))
               for f in sorted(root.iterdir())
               if f.suffix == ".tune"]
    table = tune_report.render(entries,
                               ["deadbeef.tune.corrupt"])
    assert "pfsp/8/3/1/1" in table
    assert "Quarantined" in table and "deadbeef" in table
    assert tune_report.main([str(root)]) == 0
    assert tune_report.main([str(root), "--json"]) == 0


def test_prewarm_boot_resolves_tuned_spool_shapes(tmp_path):
    """A {"tuned": true} backlog request leaves its knobs open; the
    boot pre-warm must warm the values DISPATCH will resolve to (the
    serving defaults here — no tuned entry, probing off), not crash on
    chunk=None."""
    from tpu_tree_search.service import SearchServer
    from tpu_tree_search.service import spool as spool_mod

    p = small(jobs=7)
    spool_dir = tmp_path / "spool"
    spool_mod.submit_file(spool_dir, {"p_times": p.tolist(), "lb": 1,
                                      "capacity": 4096, "min_seed": 4,
                                      "tuned": True})
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      segment_iters=256,
                      tune_cache_dir=tmp_path / "tune",
                      tune_at_boot=False,
                      share_incumbent=False) as srv:
        s = srv.prewarm_boot(spec="spool", spool_dir=spool_dir)
        assert s["shapes"] == 1 and s["errors"] == 0
        assert s["by"]["compile"] == 2          # one per submesh
        # the warmed key is the defaults-tier chunk — exactly what a
        # dispatch-time resolve of the open knobs returns
        keys = [e["key"] for e in srv.cache.ledger_snapshot()]
        assert all(
            k.startswith(f"pfsp/7/3/1/{defaults.SERVING_CHUNK_DEFAULT}/")
            for k in keys)


def test_tuner_snapshot_shape(tmp_path):
    t = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    snap = t.snapshot()
    assert snap["probes_run"] == 0
    assert snap["cache"]["entries"] == 0
    assert snap["chunk_candidates"] == [8, 16]
    t_nocache = Autotuner(**TUNE_KW)
    assert t_nocache.snapshot()["cache"] is None


def test_tuner_metrics_registry(tmp_path):
    from tpu_tree_search.obs import metrics as obs_metrics
    reg = obs_metrics.Registry("tts_test_tuner")
    t = Autotuner(cache_dir=tmp_path / "tune", registry=reg, **TUNE_KW)
    t.resolve(8, 3, 1, allow_probe=True, p_times=small())
    flat = json.dumps(reg.to_json())
    assert "tts_tuner_probes_total" in flat
    assert "tts_tuner_cache_misses_total" in flat
    t2 = Autotuner(cache_dir=tmp_path / "tune", registry=reg, **TUNE_KW)
    t2.resolve(8, 3, 1, allow_probe=True)
    assert "tts_tuner_cache_hits_total" in json.dumps(reg.to_json())


# ------------------------------------- problem-generic probe harness
# (ROADMAP item 2c: TSP/knapsack shapes get MEASURED chunk optima
# instead of silently riding the serving fallback row)


def test_probe_harness_generalizes_to_tsp():
    from tpu_tree_search.problems.tsp import TSPInstance
    inst = TSPInstance.synthetic(9, seed=0)
    h = ProbeHarness(inst.d, lb_kind=1, capacity=1 << 12, warm_chunk=8,
                     warm_iters=10, window_iters=4, repeats=1,
                     problem="tsp")
    r = h.measure(8, 4)
    assert r.evals > 0 and r.evals_per_s > 0 and r.ms_per_iter > 0


def test_probe_harness_generalizes_to_knapsack():
    from tpu_tree_search.problems.knapsack import KnapsackInstance
    inst = KnapsackInstance.synthetic(18, seed=0)
    h = ProbeHarness(inst.table, lb_kind=1, capacity=1 << 12,
                     warm_chunk=8, warm_iters=10, window_iters=4,
                     repeats=1, problem="knapsack")
    r = h.measure(8, 4)
    assert r.evals > 0 and r.evals_per_s > 0


def test_tune_non_pfsp_without_table_falls_to_defaults(tmp_path):
    # the synthetic-table fallback is a PFSP generator: a non-PFSP
    # probe WITHOUT an instance table must degrade to the defaults
    # tier (ProbeError caught), never probe a wrong-problem table
    t = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    params = t.resolve(9, 9, 1, allow_probe=True, problem="tsp")
    assert params.source == "default"
    assert t.probes_run == 0


def test_resolve_probes_tsp_with_table_and_persists(tmp_path):
    from tpu_tree_search import problems
    from tpu_tree_search.problems.tsp import TSPInstance
    inst = TSPInstance.synthetic(9, seed=0)
    prob = problems.get("tsp")
    jobs, mach = prob.slots(inst.d), prob.aux_rows(inst.d)
    t = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    params = t.resolve(jobs, mach, 1, allow_probe=True,
                       p_times=inst.d, problem="tsp")
    assert params.source == "probe"
    assert params.chunk in TUNE_KW["chunks"]
    assert t.probes_run > 0
    # a restarted tuner over the same cache dir replays with ZERO
    # probes — the PFSP contract, now problem-generic
    t2 = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    p2 = t2.resolve(jobs, mach, 1, allow_probe=True,
                    p_times=inst.d, problem="tsp")
    assert p2.source == "cache" and p2.chunk == params.chunk
    assert t2.probes_run == 0


# ------------------------------------------- rung admission profile


def test_tune_emits_rung_profile_and_cache_roundtrip(tmp_path,
                                                     monkeypatch):
    # under TTS_TUNE_RUNGS the winner's ladder rungs are probed too
    # (below the static rung floor — measured admission subsumes it)
    # and the profile persists with the entry: one row per rung with
    # its measured rates
    monkeypatch.setenv("TTS_TUNE_RUNGS", "1")
    p = small()
    t1 = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    params = t1.resolve(8, 3, 1, allow_probe=True, p_times=p)
    assert params.source == "probe"
    assert params.rung_modes
    chunks = [r["chunk"] for r in params.rung_modes]
    assert params.chunk in chunks
    for row in params.rung_modes:
        assert set(row) == {"chunk", "ms_per_iter", "evals_per_s"}
        assert row["ms_per_iter"] > 0
        assert row["evals_per_s"] > 0
    t2 = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    p2 = t2.resolve(8, 3, 1, allow_probe=False)
    assert p2.source == "cache"
    assert tuple(p2.rung_modes) == tuple(params.rung_modes)


def test_rung_probes_skipped_without_pipeline_choice(tmp_path,
                                                     monkeypatch):
    # default boot (no TTS_TUNE_RUNGS): no rung probes run — each is
    # an extra compile — and the entry persists without a profile
    # (ladder admission falls back to the static floors)
    monkeypatch.delenv("TTS_TUNE_RUNGS", raising=False)
    t = Autotuner(cache_dir=tmp_path / "tune", **TUNE_KW)
    params = t.resolve(8, 3, 1, allow_probe=True, p_times=small())
    assert params.source == "probe"
    assert params.rung_modes is None
    probed = {r["chunk"] for r in t.ledger}
    assert all(c in TUNE_KW["chunks"] for c in probed)
