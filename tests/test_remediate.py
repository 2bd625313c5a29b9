"""Self-healing service: alert-driven remediation, submesh quarantine,
chaos drills (service/remediate.py + the utils/faults drill kinds).

The load-bearing assertions (ISSUE acceptance):

- full alert lifecycle under remediation: an injected stall ->
  pending -> firing -> AUTO-preempt (no human action) -> elastic
  resume on a different, non-excluded submesh -> resolved, with
  bit-identical node/sol/evals totals against an undisturbed run;
- a request whose failures follow it across >= K distinct submeshes
  dead-letters as FAILED with a complete failure_log after a bounded
  attempt count — never an infinite redispatch loop;
- failures localized to ONE submesh quarantine it (drain, hold out of
  the partition, canary-probe, readmit on success) while requests
  route around it;
- TTS_REMEDIATE off (the default) takes ZERO actions — observe-only
  journaling, bit-identical to the pre-remediation server;
- actions are rate-limited per rule per window; reversals
  (admission resume) are exempt;
- the degraded (quarantined-submesh) configuration is visible on
  /status, in the fleet aggregation, and turns the doctor verdict
  nonzero.
"""

import json
import os
import time

import pytest

from tpu_tree_search.engine import distributed, ladder
from tpu_tree_search.obs import aggregate, dashboard, health, metrics, tracelog
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.service import SearchRequest, SearchServer
from tpu_tree_search.utils import faults

KW = dict(chunk=8, capacity=1 << 12, min_seed=4)


@pytest.fixture
def fresh_obs(tmp_path):
    log = tracelog.TraceLog(capacity=1 << 16,
                            sink_path=tmp_path / "trace.jsonl")
    prev_log = tracelog.install(log)
    reg = metrics.Registry()
    prev_reg = metrics.install(reg)
    try:
        yield log, reg
    finally:
        tracelog.install(prev_log)
        metrics.install(prev_reg)
        ladder.set_memory_pressure(False)


def wait_until(cond, timeout=120.0, what="condition"):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"timed out on {what}"
        time.sleep(0.02)


# -------------------------------------------------- chaos-drill faults


def test_fault_drill_parse_and_filters(fresh_obs):
    p = faults.FaultPlan.parse(
        "kill_submesh=2:3@0,oom_segment=1,wedge_executor=3:0.1@1")
    assert p.kill_submesh == (2, 3, 0)
    assert p.oom_segment == (1, 1, None)       # default budget 1
    assert p.wedge_executor == (3, 0.1, 1)
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("kill_submash=2")
    # @0 filter: no ambient submesh context -> never fires
    with faults.scoped("kill_submesh=1:1@0"):
        faults.fire("segment_start", segment=1)     # no context: no-op
        with tracelog.context(submesh=1):
            faults.fire("segment_start", segment=1)  # wrong submesh
        with tracelog.context(submesh=0):
            with pytest.raises(faults.InjectedKill):
                faults.fire("segment_start", segment=1)
            # budget 1 spent: the same point is now clean (the canary
            # probe's readmit contract)
            faults.fire("segment_start", segment=1)
    # oom raises its RESOURCE_EXHAUSTED-shaped transient
    with faults.scoped("oom_segment=2"):
        with pytest.raises(faults.InjectedOOM, match="RESOURCE_EXHAUSTED"):
            faults.fire("segment_start", segment=2)
    # both are TRANSIENT-class: the service retry tier must catch them
    from tpu_tree_search.engine.checkpoint import TRANSIENT_ERRORS
    assert issubclass(faults.InjectedKill, TRANSIENT_ERRORS[1])
    assert issubclass(faults.InjectedOOM, TRANSIENT_ERRORS[1])


# ------------------------------------ the acceptance drill: stall heals


def test_stall_remediation_full_lifecycle(fresh_obs, tmp_path,
                                          monkeypatch):
    """Injected wedge -> stall fires -> controller preempts at the
    segment boundary, checkpoints, requeues with the offending submesh
    excluded -> elastic resume on the OTHER submesh -> DONE with
    bit-identical totals -> alert resolves. No human in the loop."""
    monkeypatch.setenv("TTS_HEALTH_STALL_S", "1.0")
    monkeypatch.setenv("TTS_HEALTH_STALL_WARMUP_S", "5.0")
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    base = distributed.search(inst.p_times, lb_kind=1, init_ub=None,
                              n_devices=4, **KW)
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      health_interval_s=0.05, remediate=True,
                      share_incumbent=False) as srv:
        # warm the executor cache so the wedged request's dispatch goes
        # straight into segments (a cold compile would eat the drill's
        # timing budget, not change its semantics)
        warm = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=8, **KW))
        assert srv.result(warm, timeout=300).state == "DONE"
        # wedge EARLY (segment 2 of a 4-segment solve: 32 iterations)
        # so real work remains after the preempt — a wedge in the last
        # segment would let completion win the race against the stop
        # flag
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=8,
            checkpoint_every=1, faults="wedge_executor=2:4.0", **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "DONE", (rec.state, rec.error)
        # the controller acted: >= 1 auto-preemption, a second dispatch
        # on a submesh OUTSIDE the excluded set, zero failures
        assert rec.preemptions >= 1 and rec.dispatches >= 2
        assert rec.excluded_submeshes, "offender was not excluded"
        assert rec.submesh not in rec.excluded_submeshes
        assert rec.failures == 0 and rec.failure_log == []
        # bit-identical to the undisturbed run (same-size submesh
        # resume is exact)
        res = rec.result
        assert (res.explored_tree, res.explored_sol, res.best) == \
            (base.explored_tree, base.explored_sol, base.best)

        def stall():
            return srv.health.alerts.get("stall")

        wait_until(lambda: stall() is not None
                   and stall().state == health.RESOLVED,
                   what="stall alert resolving")
        assert stall().fired_count >= 1
        snap = srv.status_snapshot()["remediation"]
        assert snap["enabled"] and snap["mode"] == "act"
        applied = [a for a in snap["actions"]
                   if a["action"] == "preempt_requeue"
                   and a["outcome"] == "applied"]
        assert applied and applied[0]["detail"]["request_id"]
    log, _ = fresh_obs
    names = {r["name"] for r in log.records()}
    assert "remediation.applied" in names
    assert "alert.resolved" in names


def test_observe_mode_takes_no_action(fresh_obs, tmp_path, monkeypatch):
    """TTS_REMEDIATE off (default): the same stall is detected and the
    would-be action journaled, but nothing is touched — the request
    rides out the wedge on its original submesh, bit-identically."""
    monkeypatch.setenv("TTS_HEALTH_STALL_S", "0.6")
    monkeypatch.setenv("TTS_HEALTH_STALL_WARMUP_S", "5.0")
    monkeypatch.delenv("TTS_REMEDIATE", raising=False)
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    base = distributed.search(inst.p_times, lb_kind=1, init_ub=None,
                              n_devices=4, **KW)
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      health_interval_s=0.05,
                      share_incumbent=False) as srv:
        assert not srv.remediation.enabled
        warm = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16, **KW))
        assert srv.result(warm, timeout=300).state == "DONE"
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16,
            faults="wedge_executor=2:2.0", **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "DONE", (rec.state, rec.error)
        # zero actions: no preemption, no exclusions, single dispatch
        assert rec.preemptions == 0 and rec.dispatches == 1
        assert rec.excluded_submeshes == set()
        res = rec.result
        assert (res.explored_tree, res.explored_sol, res.best) == \
            (base.explored_tree, base.explored_sol, base.best)
        snap = srv.status_snapshot()["remediation"]
        assert snap["mode"] == "observe"
        observed = [a for a in snap["actions"]
                    if a["outcome"] == "observed"
                    and a["action"] == "preempt_requeue"]
        assert observed, snap["actions"]
        assert all(a["outcome"] == "observed" for a in snap["actions"])


# --------------------------------------------- dead-letter vs quarantine


def test_deadletter_after_distinct_submeshes(fresh_obs, tmp_path):
    """A fault that FOLLOWS the request (kill on every submesh) must
    dead-letter after K distinct submeshes — bounded attempts, full
    failure_log — even with retry budget to spare."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=4, workdir=tmp_path / "wd",
                      health_interval_s=0, remediate=True,
                      service_retry_attempts=8,
                      service_retry_base_s=0.01,
                      share_incumbent=False) as srv:
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16,
            faults="kill_submesh=1:99", **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "FAILED"
        assert "dead-lettered" in rec.error
        assert rec.dispatches == 3          # bounded: K, not 1+retries
        snap = srv.status(rid)
        flog = snap["failure_log"]
        assert len(flog) == 3
        assert len({f["submesh"] for f in flog}) == 3
        assert all(f["error"] and f["attempt"] == i + 1
                   for i, f in enumerate(flog))
        journal = srv.status_snapshot()["remediation"]["actions"]
        assert any(a["action"] == "deadletter"
                   and a["outcome"] == "applied" for a in journal)


def test_deadletter_threshold_clamps_to_partition(fresh_obs, tmp_path):
    """On a 2-submesh server the default threshold (3) clamps to 2:
    a request that failed on BOTH submeshes has followed its fault
    everywhere it can go and must dead-letter, not ping-pong through
    the whole retry budget."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      health_interval_s=0, remediate=True,
                      service_retry_attempts=8,
                      service_retry_base_s=0.01,
                      share_incumbent=False) as srv:
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16,
            faults="kill_submesh=1:99", **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "FAILED" and "dead-lettered" in rec.error
        assert rec.dispatches == 2
        flog = srv.status(rid)["failure_log"]
        assert len({f["submesh"] for f in flog}) == 2


def test_excluded_head_preempts_instead_of_priority_inversion(
        fresh_obs, tmp_path):
    """A free slot only suppresses priority preemption if the head of
    the line can USE it: high-priority H, excluded from the free
    submesh by its own failure there, must preempt low-priority L off
    the submesh H can still run on — not wait behind it."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      health_interval_s=0, remediate=True,
                      service_retry_attempts=4,
                      service_retry_base_s=0.01,
                      share_incumbent=False) as srv:
        lo = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, priority=0,
            segment_iters=8, checkpoint_every=1,
            faults="delay_every=0.3", **KW))
        wait_until(lambda: srv.status(lo)["state"] == "RUNNING",
                   what="low-priority running")
        assert srv.status(lo)["submesh"] == 0
        # H lands on the free submesh 1, dies there once, gets it
        # excluded — and must then preempt L off submesh 0
        hi = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, priority=5,
            segment_iters=16, faults="kill_submesh=1:1@1", **KW))
        rec_hi = srv.result(hi, timeout=300)
        assert rec_hi.state == "DONE", (rec_hi.state, rec_hi.error)
        assert rec_hi.submesh == 0
        assert rec_hi.excluded_submeshes == {1}
        rec_lo = srv.result(lo, timeout=300)
        assert rec_lo.state == "DONE", (rec_lo.state, rec_lo.error)
        assert rec_lo.preemptions >= 1     # it made way for H


def test_quarantine_drains_probes_and_readmits(fresh_obs, tmp_path):
    """Failures LOCALIZED to submesh 0 (a global @0 drill plan)
    quarantine it: requests route around it and complete; the canary
    probe readmits it once the submesh behaves (drill budget spent).
    The degraded window is visible in the snapshot."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    faults.configure("kill_submesh=1:2@0")
    try:
        with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                          health_interval_s=0, remediate=True,
                          service_retry_attempts=4,
                          service_retry_base_s=0.01,
                          share_incumbent=False) as srv:
            srv.remediation.quarantine_fails = 2
            srv.remediation.probe_s = 0.2
            r1 = srv.submit(SearchRequest(
                p_times=inst.p_times, lb_kind=1, segment_iters=16,
                **KW))
            rec1 = srv.result(r1, timeout=300)
            assert rec1.state == "DONE", (rec1.state, rec1.error)
            assert len(srv.status(r1)["failure_log"]) == 1
            r2 = srv.submit(SearchRequest(
                p_times=inst.p_times, lb_kind=1, segment_iters=16,
                **KW))
            rec2 = srv.result(r2, timeout=300)
            assert rec2.state == "DONE", (rec2.state, rec2.error)
            snap = srv.status_snapshot()
            quar = snap["remediation"]["quarantined"]
            assert [q["submesh"] for q in quar] == [0]
            assert snap["submeshes"][0]["quarantined"] is True
            # both requests were healed AROUND the bad submesh
            assert rec1.submesh == 1 and rec2.submesh == 1
            # ...and the canary readmits it (the drill budget is spent,
            # so the synthetic micro-request completes cleanly)
            wait_until(lambda: not srv.slots[0].quarantined,
                       what="canary readmit")
            journal = srv.status_snapshot()["remediation"]["actions"]
            acts = [(a["action"], a["outcome"]) for a in journal]
            assert ("quarantine_submesh", "applied") in acts
            assert ("readmit_submesh", "applied") in acts
            g = srv.metrics.gauge("tts_quarantined_submeshes")
            assert g.value() == 0.0
    finally:
        faults.reset()


def test_spool_holds_backlog_when_pause_lands_mid_iteration(tmp_path):
    """The pause engaging between the serve loop's paused check and
    submit() must HOLD the file for the next poll, never write a
    terminal REJECTED result."""
    from tpu_tree_search.service import spool
    from tpu_tree_search.service.queueing import (AdmissionError,
                                                  AdmissionPaused)

    class StubServer:
        slots = ()

        def __init__(self, exc):
            self.exc = exc
            self.queue = []

        def admission_paused(self):
            return None     # the loop's upfront check sees "admitting"

        def submit(self, request, **kw):   # kw: spool_id (the ledger's
            raise self.exc                 # result-delivery reconnect key)

        def status(self, rid):
            raise AssertionError("nothing should be pending")

    sid = spool.submit_file(tmp_path, {"p_times": [[3, 4], [5, 6]],
                                       "lb": 1})
    srv = StubServer(AdmissionPaused("admission paused: compile storm"))
    served = spool.serve_spool(srv, tmp_path, should_exit=lambda: True)
    assert served == 0
    res = tmp_path / f"{sid}{spool.RES_SUFFIX}"
    assert not res.exists()          # held, not rejected
    # ...while a REAL rejection (queue full) still writes the result
    srv = StubServer(AdmissionError(
        "queue full: depth 64 at the admission bound 64"))
    spool.serve_spool(srv, tmp_path, should_exit=lambda: True)
    assert json.loads(res.read_text())["state"] == "REJECTED"


def test_deadletter_failure_still_quarantines_the_submesh(
        fresh_obs, tmp_path):
    """A failure that dead-letters the request AND trips its submesh's
    localized-failure threshold must do both — the hardware evidence
    stands on its own. Two poisoned requests: the second one's
    failures push BOTH submeshes to the quarantine threshold on the
    same failures that dead-letter it; submesh 0 quarantines (normal
    path), submesh 1 is reached via the DEAD-LETTER branch and then
    refused as the last healthy one."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      health_interval_s=0, remediate=True,
                      service_retry_attempts=8,
                      service_retry_base_s=0.01,
                      share_incumbent=False) as srv:
        srv.remediation.quarantine_fails = 2
        srv.remediation.probe_s = 3600.0     # no readmit mid-test
        r1 = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16,
            faults="kill_submesh=1:2", **KW))
        rec1 = srv.result(r1, timeout=300)
        assert rec1.state == "FAILED" and "dead-lettered" in rec1.error
        assert rec1.dispatches == 2          # clamped threshold: 2
        r2 = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16,
            faults="kill_submesh=1:99", **KW))
        rec2 = srv.result(r2, timeout=300)
        assert rec2.state == "FAILED" and "dead-lettered" in rec2.error
        # submesh 0 hit 2 localized failures -> quarantined; submesh 1
        # hit its 2nd ON the dead-lettering failure -> the quarantine
        # was still attempted (the fix under test) and refused as the
        # last healthy submesh
        assert [s.index for s in srv.slots if s.quarantined] == [0]
        journal = srv.status_snapshot()["remediation"]["actions"]
        acts = [(a["action"], a["outcome"]) for a in journal]
        assert acts.count(("deadletter", "applied")) == 2
        assert ("quarantine_submesh", "applied") in acts
        assert ("quarantine_submesh", "skipped") in acts


def test_quarantine_refuses_last_healthy_submesh(fresh_obs, tmp_path):
    """A single-submesh server must never quarantine itself to zero
    capacity — the decision journals as skipped."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    faults.configure("kill_submesh=1:2@0")
    try:
        with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                          health_interval_s=0, remediate=True,
                          service_retry_attempts=4,
                          service_retry_base_s=0.01,
                          share_incumbent=False) as srv:
            srv.remediation.quarantine_fails = 2
            rid = srv.submit(SearchRequest(
                p_times=inst.p_times, lb_kind=1, segment_iters=16,
                **KW))
            rec = srv.result(rid, timeout=300)
            # two kills, then the budget is spent and the third
            # dispatch (exclusions cleared: nowhere else to run)
            # completes on the sole submesh
            assert rec.state == "DONE", (rec.state, rec.error)
            assert len(srv.status(rid)["failure_log"]) == 2
            assert not srv.slots[0].quarantined
            journal = srv.status_snapshot()["remediation"]["actions"]
            assert any(a["action"] == "quarantine_submesh"
                       and a["outcome"] == "skipped" for a in journal)
    finally:
        faults.reset()


# ----------------------------------------- policy actions, unit-driven


def test_exclusions_covering_all_healthy_slots_do_not_strand(
        fresh_obs, tmp_path):
    """A request excluded from every healthy slot (its exclusions were
    capped against the FULL partition, then a quarantine shrank it)
    must become eligible again instead of sitting QUEUED forever."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      autostart=False, health_interval_s=0,
                      remediate=True, share_incumbent=False) as srv:
        rid = srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                       **KW))
        rec = srv.records[rid]
        srv.add_exclusion(rec, 1)          # excluded from submesh 1...
        srv.slots[0].quarantined = True    # ...and submesh 0 held out
        srv.start()
        done = srv.result(rid, timeout=300)
        assert done.state == "DONE", (done.state, done.error)
        assert done.submesh == 1           # least-bad: the healthy slot


def test_pause_admission_on_compile_storm_and_resume(fresh_obs,
                                                     tmp_path):
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                      autostart=False, health_interval_s=0,
                      remediate=True, share_incumbent=False) as srv:
        from tpu_tree_search.service.queueing import AdmissionError
        ctl = srv.remediation
        assert ctl.handle("compile_storm", "pause_admission",
                          {"detail": {"compiles_in_interval": 9}}) \
            == "applied"
        assert "compile storm" in srv.admission_paused()
        assert srv.metrics.gauge("tts_admission_paused").value() == 1.0
        with pytest.raises(AdmissionError, match="admission paused"):
            srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                     **KW))
        rejected_before = srv.queue.rejected
        assert rejected_before >= 1
        # the resolution reverses the valve — reversals are NEVER
        # rate-limited
        assert ctl.handle("compile_storm", "resume_admission", {}) \
            == "applied"
        assert srv.admission_paused() is None
        assert srv.metrics.gauge("tts_admission_paused").value() == 0.0
        rid = srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                       **KW))
        assert srv.status(rid)["state"] == "QUEUED"


def test_rate_valve_caps_per_rule_per_window(fresh_obs, tmp_path):
    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                      autostart=False, health_interval_s=0,
                      remediate=True, share_incumbent=False) as srv:
        ctl = srv.remediation
        ctl.max_per_rule = 1
        ctl.window_s = 3600.0
        assert ctl.handle("compile_storm", "pause_admission",
                          {}) == "applied"
        assert ctl.handle("compile_storm", "resume_admission",
                          {}) == "applied"          # reversal exempt
        assert ctl.handle("compile_storm", "pause_admission",
                          {}) == "rate_limited"
        # the capped action touched nothing
        assert srv.admission_paused() is None
        c = srv.metrics.counter("tts_remediations_total")
        assert c.value(rule="compile_storm", action="pause_admission",
                       outcome="rate_limited") == 1
        # only EXECUTED actions consume the budget: stale noops (the
        # alerted request is gone) must not rate-limit the next real one
        for _ in range(3):
            assert ctl.handle("stall", "preempt_requeue",
                              {"detail": {"request_id": "gone"}}) \
                == "noop"


def test_mem_headroom_sheds_and_raises_ladder_pressure(fresh_obs,
                                                       tmp_path):
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                      health_interval_s=0, remediate=True,
                      share_incumbent=False) as srv:
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=8,
            checkpoint_every=1, faults="delay_every=0.2", **KW))
        wait_until(lambda: (srv.status(rid)["progress"] or {})
                   .get("segment", 0) >= 1, what="first heartbeat")
        assert srv.remediation.handle("mem_headroom", "shed_memory",
                                      {}) == "applied"
        assert ladder.memory_pressure()
        rec = srv.result(rid, timeout=300)
        assert rec.state == "DONE", (rec.state, rec.error)
        assert rec.preemptions >= 1          # it was shed and resumed
        # shed does NOT exclude the submesh — nothing is wrong with it
        assert rec.excluded_submeshes == set()
        assert srv.remediation.handle(
            "mem_headroom", "clear_memory_pressure", {}) == "applied"
        assert not ladder.memory_pressure()


def test_audit_action_quarantines_bad_checkpoint(fresh_obs, tmp_path):
    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                      autostart=False, health_interval_s=0,
                      remediate=True, share_incumbent=False) as srv:
        bad = tmp_path / "wd" / "t.ckpt.npz"
        bad.write_bytes(b"torn" * 64)
        alert = {"detail": {"invariant": "checkpoint_roundtrip",
                            "detail": {"path": str(bad)}}}
        assert srv.remediation.handle("audit", "quarantine_checkpoint",
                                      alert) == "applied"
        assert not bad.exists()
        assert os.path.exists(str(bad) + ".corrupt")
        # a non-checkpoint audit finding is a noop, not an error
        assert srv.remediation.handle(
            "audit", "quarantine_checkpoint",
            {"detail": {"invariant": "node_conservation"}}) == "noop"


# ------------------------------------------- surfaces: doctor + trace


def test_aggregate_degraded_verdict_and_dashboards(fresh_obs):
    status = {
        "uptime_s": 12.0,
        "queue": {"depth": 0},
        "submeshes": [{"index": 0, "running": None,
                       "quarantined": True},
                      {"index": 1, "running": "req-0001",
                       "quarantined": False}],
        "remediation": {
            "enabled": True, "mode": "act",
            "quarantined": [{"submesh": 0, "since": 1.0,
                             "reason": "localized failures"}],
            "admission_paused": "compile storm",
            "counts": {}, "probes_pending": 1,
            "actions": [{"t": 1.0, "rule": "stall",
                         "action": "preempt_requeue",
                         "outcome": "applied",
                         "detail": {"request_id": "req-0001"}}]},
        "requests": {}}
    fleet = {"t": 0.0, "servers": [{
        "origin": "h:1", "url": "http://h:1", "ok": True,
        "error": None, "healthz": {"code": 200, "status": "ok"},
        "status": status, "alerts": {"firing": 0, "alerts": []},
        "metrics": []}]}
    merged = aggregate.merge(fleet)
    row = merged["servers"][0]
    assert row["quarantined"] == 1
    assert row["admission_paused"] == "compile storm"
    healthy, reasons = aggregate.verdict(merged)
    assert not healthy
    assert any("DEGRADED" in r for r in reasons)
    html = dashboard.render_fleet(merged)
    assert "degraded" in html and "quarantined" in html.lower()
    assert "<script" not in html
    html = dashboard.render_server(status, None, None)
    assert "Self-healing" in html and "preempt_requeue" in html
    assert "paused" in html


def test_trace_summary_failure_log_and_fail_column(fresh_obs):
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent
                           / "tools"))
    import trace_summary

    records = [
        {"name": "request.admit", "ts": 1.0, "request_id": "req-0000"},
        {"name": "request.dispatch", "ts": 2.0,
         "request_id": "req-0000", "submesh": 0},
        {"name": "request.dispatch_failure", "ts": 3.0,
         "request_id": "req-0000", "submesh": 0, "attempt": 1,
         "error": "transient: InjectedKill('boom')"},
        {"name": "request.redispatch", "ts": 3.1,
         "request_id": "req-0000", "failures": 1,
         "error": "transient: InjectedKill('boom')"},
        {"name": "request.dispatch", "ts": 4.0,
         "request_id": "req-0000", "submesh": 1},
        {"name": "remediation.applied", "ts": 4.5,
         "request_id": "req-0000", "rule": "retry",
         "action": "exclude_submesh"},
        # the TERMINAL failure has no redispatch event — only the
        # dispatch_failure record carries it into the trace
        {"name": "request.dispatch_failure", "ts": 5.0,
         "request_id": "req-0000", "submesh": 1, "attempt": 2,
         "error": "transient: InjectedKill('fatal')"},
        {"name": "request.failed", "ts": 5.1,
         "request_id": "req-0000"},
        # server-level remediation (quarantine) carries no request id
        # but must still reach the footer count
        {"name": "remediation.applied", "ts": 5.2, "rule": "quarantine",
         "action": "quarantine_submesh", "submesh": 1},
    ]
    reqs = trace_summary.summarize(records)
    s = reqs["req-0000"]
    assert s["failures"] == 2 and s["remediations"] == 1
    assert [(e["submesh"], e["attempt"]) for e in s["failure_log"]] \
        == [(0, 1), (1, 2)]
    out = trace_summary.render(reqs)
    assert "fail" in out.splitlines()[0]
    assert "failure log req-0000" in out
    assert "InjectedKill" in out and "fatal" in out
    assert "1 request(s)" in out          # the pseudo-row is not a row
    assert "2 dispatch failure(s)" in out
    assert "2 remediation record(s)" in out


def test_failure_log_snapshot_json_safe(fresh_obs, tmp_path):
    """The failure_log rides /status as plain JSON (the dead-letter
    diagnosis surface) and is bounded."""
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=2)
    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                      health_interval_s=0, service_retry_attempts=1,
                      service_retry_base_s=0.01,
                      share_incumbent=False) as srv:
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, segment_iters=16,
            faults="kill_submesh=1:1", **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "DONE", (rec.state, rec.error)
        snap = srv.status(rid)
        assert len(snap["failure_log"]) == 1
        entry = snap["failure_log"][0]
        assert entry["submesh"] == 0 and entry["attempt"] == 1
        assert "InjectedKill" in entry["error"]
        json.dumps(srv.status_snapshot())     # everything serializes
