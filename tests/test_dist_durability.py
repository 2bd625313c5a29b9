"""Distributed durability: lossless overflow growth, stacked
checkpoint/resume, segmented driving with per-worker heartbeat, and the
water-filling balance plan.

This is the layer the reference lacks entirely (SURVEY.md §5:
"Checkpoint/resume: none"; its only stall tooling is a 10-second
"Still Idle" print, pfsp_dist_multigpu_cuda.c:663-668). Round 1 had it
single-device only; a distributed overflow restarted from the warm-up
frontier, discarding all explored work — these tests pin the lossless
behavior that replaced it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_tree_search.engine import checkpoint, distributed, sequential as seq
from tpu_tree_search.parallel import balance as bal
from tpu_tree_search.problems.pfsp import PFSPInstance


def test_exchange_plan_multi_receiver():
    """One hot worker must feed several starving workers in one round
    (the round-1 pairing fed exactly one receiver per donor)."""
    import jax.numpy as jnp

    sizes = jnp.asarray([100, 0, 0, 0], jnp.int32)
    plan = np.asarray(bal.exchange_plan(sizes, cap=64, min_transfer=4))
    assert plan[0].sum() > 0
    assert (plan[0] > 0).sum() >= 2        # multiple receivers
    assert plan[0, 0] == 0                 # no self-flow
    # every receiver below the mean is filled, the donor ends at it
    np.testing.assert_array_equal(plan[0, 1:], [25, 25, 25])
    assert 100 - plan[0].sum() == 25


def test_exchange_plan_balanced_is_empty():
    import jax.numpy as jnp

    sizes = jnp.asarray([50, 52, 49, 51], jnp.int32)
    plan = np.asarray(bal.exchange_plan(sizes, cap=64, min_transfer=8))
    assert plan.sum() == 0


def _counting_grow(monkeypatch):
    calls = []
    orig_grow = checkpoint.grow

    def counting(state, new_capacity):
        calls.append(new_capacity)
        return orig_grow(state, new_capacity)

    monkeypatch.setattr(checkpoint, "grow", counting)
    return calls


def test_dist_overflow_grows_and_resumes_losslessly(monkeypatch):
    """A pool that must overflow mid-run grows and RESUMES with no node
    lost or duplicated. N-Queens is the exact oracle for this: no
    incumbent, so tree/sol counts are invariant to exploration order —
    any lost (or doubled) subtree would shift them. Balancing is
    disabled (huge min_transfer) and the warm-up stripe sized near the
    limit so the pools MUST overflow mid-run."""
    from tpu_tree_search.problems import nqueens as nq

    calls = _counting_grow(monkeypatch)
    kw = dict(chunk=4, n_devices=2, min_seed=200, min_transfer=10**6)
    small = nq.search_distributed(10, capacity=1 << 8, **kw)
    assert calls, "tiny pool never overflowed — capacity too generous " \
                  "for the test to exercise the grow path"
    big = nq.search_distributed(10, capacity=1 << 15, **kw)
    assert (small.explored_tree, small.explored_sol) == \
           (big.explored_tree, big.explored_sol) == (35538, 724)


def test_dist_pfsp_overflow_grow_still_optimal(monkeypatch):
    """PFSP with ub=inf through the overflow-grow path still proves the
    optimum (with a live incumbent the exact tree shape is schedule-
    dependent — as in the reference's threaded runs — so the invariant
    checked is optimality + completion, not node counts)."""
    inst = PFSPInstance.synthetic(jobs=11, machines=4, seed=11)
    kw = dict(lb_kind=0, init_ub=None, chunk=8, transfer_cap=8, min_seed=8)
    big = distributed.search(inst.p_times, capacity=1 << 14, **kw)
    calls = _counting_grow(monkeypatch)
    small = distributed.search(inst.p_times, capacity=1 << 8, **kw)
    assert calls, "tiny pool never overflowed"
    assert small.complete
    assert small.best == big.best


def test_dist_segmented_checkpoint_resume(tmp_path):
    """Kill/resume a multi-device run: a checkpointed truncated run,
    resumed to completion, reproduces the uninterrupted totals exactly."""
    inst = PFSPInstance.synthetic(jobs=9, machines=4, seed=7)
    kw = dict(lb_kind=1, init_ub=None, chunk=4, capacity=1 << 12,
              min_seed=8)
    full = distributed.search(inst.p_times, **kw)

    ckpt = tmp_path / "dist.npz"
    part = distributed.search(inst.p_times, **kw, segment_iters=3,
                              checkpoint_path=str(ckpt), max_rounds=6,
                              heartbeat=None)
    assert ckpt.exists()
    assert not part.complete

    reports = []
    res = distributed.search(inst.p_times, **kw, segment_iters=64,
                             checkpoint_path=str(ckpt),
                             heartbeat=reports.append)
    assert res.complete
    assert (res.explored_tree, res.explored_sol, res.best) == \
           (full.explored_tree, full.explored_sol, full.best)
    # per-worker heartbeat surfaced (8 virtual workers)
    assert reports and reports[0].per_worker is not None
    assert len(reports[0].per_worker["size"]) == 8
    assert len(reports[0].per_worker["steals"]) == 8


def test_dist_checkpoint_elastic_resume_fewer_workers(tmp_path):
    """An 8-worker checkpoint resumes on a 2-worker mesh (elastic
    resume: the pools are concatenated and water-filled across the new
    mesh) and still reaches the exact uninterrupted totals — at ub=opt
    the explored set is exploration-order independent, so any lost or
    duplicated node would shift the counts. (This replaced the hard
    'resume needs the same worker count' error: on real fleets a
    preempted job rarely gets the same topology back.)"""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=7)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    ckpt = tmp_path / "dist8.npz"
    part = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                              chunk=4, capacity=1 << 12, min_seed=8,
                              segment_iters=2, checkpoint_path=str(ckpt),
                              max_rounds=2, heartbeat=None)
    assert ckpt.exists()
    assert not part.complete, "partial run finished — nothing to resume"
    with pytest.warns(RuntimeWarning, match="resharding"):
        res = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                                 n_devices=2, chunk=4, capacity=1 << 12,
                                 checkpoint_path=str(ckpt), heartbeat=None)
    assert res.complete
    assert (res.explored_tree, res.explored_sol, res.best) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_grow_stacked_state():
    """checkpoint.grow re-homes stacked (D, jobs, cap) pools."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=3)
    res = distributed.search(inst.p_times, lb_kind=1, init_ub=None,
                             chunk=4, capacity=1 << 12, min_seed=8)
    del res  # only needed the import path warm; build a tiny fake state
    from tpu_tree_search.engine.device import SearchState

    import jax.numpy as jnp
    D, J, cap, M = 4, 8, 64, 4
    s = SearchState(
        prmu=jnp.zeros((D, J, cap), jnp.int16),
        depth=jnp.zeros((D, cap), jnp.int16),
        aux=jnp.zeros((D, M, cap), jnp.int32),
        size=jnp.full((D,), 5, jnp.int32),
        best=jnp.full((D,), 99, jnp.int32),
        tree=jnp.full((D,), 7, jnp.int64),
        sol=jnp.zeros((D,), jnp.int64),
        iters=jnp.zeros((D,), jnp.int64),
        evals=jnp.zeros((D,), jnp.int64),
        sent=jnp.zeros((D,), jnp.int64),
        recv=jnp.zeros((D,), jnp.int64),
        steals=jnp.zeros((D,), jnp.int64),
        overflow=jnp.ones((D,), bool),
    )
    g = checkpoint.grow(s, 256)
    assert g.prmu.shape == (D, J, 256)
    assert g.depth.shape == (D, 256)
    assert g.aux.shape == (D, M, 256)
    assert not np.asarray(g.overflow).any()
    assert (np.asarray(g.tree) == 7).all()


# ta003 LB2 at ub=opt, chunk 32: the deterministic campaign totals every
# supervisor test asserts bit-identical (tree, best, iters)
CAMPAIGN_GOLDEN = (80062, 1081, 2511)


def _campaign_env(tmp_path, out, **over):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "TTS_CAMPAIGN_OUT": str(out),
           "TTS_WORKDIR": str(tmp_path),
           "TTS_LB": "2", "TTS_CHUNK": "32", "TTS_SEG": "600",
           "TTS_CKPT_EVERY": "1", "TTS_BUDGET_S": "600",
           "TTS_POOL_ROWS": "65536"}
    env.pop("XLA_FLAGS", None)   # no need for the 8-device split here
    env.update(over)
    return env


def _campaign_cmd():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the supervisor tests pin the LEGACY process-per-instance path
    # (kept one release behind the deprecated --no-serve flag);
    # serve-mode coverage is test_campaign_serve_mode_same_rows below
    # and tests/test_service.py
    return [sys.executable, "-u",
            os.path.join(repo, "tools", "run_campaign.py"),
            "--no-serve", "3"]


def test_supervisor_parent_starts_no_jax_backend(tmp_path):
    """A chip belongs to one process: the --no-serve supervisor must not
    start a JAX backend before spawning its workers, or each worker
    would find the chip held by its parent."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys\n"
        f"sys.path.insert(0, {os.path.join(repo, 'tools')!r})\n"
        "import run_campaign as rc\n"
        "from jax._src import xla_bridge\n"
        "def spawn(*a, **k):\n"
        "    print('BACKEND', xla_bridge.backends_are_initialized())\n"
        "    raise SystemExit(0)\n"
        "rc.subprocess.Popen = spawn\n"
        "rc.main(['--no-serve', '3'])\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=repo,
                       env=_campaign_env(tmp_path, tmp_path / "o.jsonl"),
                       capture_output=True, text=True, timeout=120)
    assert "BACKEND False" in r.stdout, r.stdout + r.stderr[-2000:]


def test_supervisor_stall_resume(tmp_path):
    """The campaign supervisor must survive a dead worker dispatch: the
    worker hangs mid-run (the test hook simulates a hung device
    dispatch), the supervisor detects the stale
    heartbeat, kills the process group, respawns resuming from the last
    checkpoint — and the final counters are bit-identical to an unkilled
    run (the same exact-count invariant the multichip dryrun pins)."""
    out = tmp_path / "campaign.jsonl"
    env = _campaign_env(tmp_path, out,
                        TTS_TEST_STALL_AT_SEG="3",
                        TTS_STALL_GRACE="180", TTS_STALL_MIN="4",
                        TTS_STALL_FACTOR="4")
    proc = subprocess.run(_campaign_cmd(), env=env, timeout=900,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1, proc.stdout
    row = rows[0]
    assert row["restarts"] >= 1, (row, proc.stdout)
    assert row["done"], row
    assert (row["tree"], row["best"], row["iters"]) == CAMPAIGN_GOLDEN


def test_supervisor_relaunch_resumes_checkpoint(tmp_path):
    """The CAMPAIGN PROCESS itself dying must not discard durable
    progress: a relaunched supervisor finds a matching-config
    checkpoint, resumes it, and the final counters stay bit-identical
    (r5 review finding: the first version unconditionally deleted any
    existing checkpoint at instance start). The first run uses the
    stall hook to PARK deterministically after segment 3 (checkpoint of
    segment 2 on disk, supervisor held off by a long stall floor), so
    the mid-run kill cannot race a fast solve."""
    out = tmp_path / "campaign.jsonl"
    env = _campaign_env(tmp_path, out,
                        TTS_TEST_STALL_AT_SEG="3",
                        TTS_STALL_GRACE="600", TTS_STALL_MIN="600")
    ckpt = tmp_path / "tts_ta003_lb2.ckpt.npz"

    import time
    proc = subprocess.Popen(_campaign_cmd(), env=env,
                            start_new_session=True,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    deadline = time.time() + 300
    while time.time() < deadline and not ckpt.exists():
        time.sleep(1.0)
    assert ckpt.exists(), "no checkpoint appeared within 300s"
    # the worker is parked in the stall hook; kill the WHOLE campaign
    import signal as _sig
    try:
        os.killpg(proc.pid, _sig.SIGKILL)
    except ProcessLookupError:
        pytest.fail("campaign exited before the kill — the stall hook "
                    "did not park it")
    proc.wait()
    assert not out.exists() or not out.read_text().strip(), \
        "instance finished before the kill — the stall hook is broken"

    # relaunch WITHOUT the stall hook: must resume, not restart
    env2 = _campaign_env(tmp_path, out)
    r = subprocess.run(_campaign_cmd(), env=env2, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "resuming from existing checkpoint" in r.stdout, r.stdout
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1
    assert rows[0]["done"]
    assert (rows[0]["tree"], rows[0]["best"], rows[0]["iters"]) == \
        CAMPAIGN_GOLDEN
    assert not ckpt.exists(), "completed run must remove its checkpoint"


def test_supervisor_recovers_from_repeated_kill_injection(tmp_path):
    """Preemption torture: TTS_FAULTS=kill_after_segment=2 rides the
    supervisor's env into EVERY respawned worker, so each incarnation
    is killed (exit 137) two segments after it resumes. Progress still
    converges — every death leaves a fresh checkpoint behind — and the
    final counters are bit-identical to an unkilled run."""
    out = tmp_path / "campaign.jsonl"
    env = _campaign_env(tmp_path, out,
                        TTS_FAULTS="kill_after_segment=2",
                        TTS_STALL_GRACE="180", TTS_STALL_MIN="4")
    proc = subprocess.run(_campaign_cmd(), env=env, timeout=900,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1, proc.stdout
    row = rows[0]
    assert row["restarts"] >= 1, (row, proc.stdout)
    assert row["done"], row
    assert (row["tree"], row["best"], row["iters"]) == CAMPAIGN_GOLDEN


def test_campaign_partial_budget_keeps_checkpoint_and_extends(tmp_path):
    """ADVICE r5: the supervisor used to unlink the checkpoint on
    budget-exhausted PARTIAL rows and the rerun skip-key ignored
    budget/done — so a larger-budget rerun silently skipped the
    instance and the in-flight progress was unrecoverable. Now a
    partial row keeps its checkpoint, a same-budget rerun still skips,
    and a larger-budget rerun RESUMES it to the bit-identical solved
    counters."""
    out = tmp_path / "campaign.jsonl"
    ckpt = tmp_path / "tts_ta003_lb2.ckpt.npz"
    env = _campaign_env(tmp_path, out, TTS_BUDGET_S="0.01")
    r = subprocess.run(_campaign_cmd(), env=env, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1 and rows[0]["done"] is False, rows
    assert ckpt.exists(), "partial row must keep its checkpoint"

    # same budget: nothing new to measure — skip, no new row
    r2 = subprocess.run(_campaign_cmd(), env=env, timeout=600,
                        capture_output=True, text=True)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "skipping" in r2.stdout, r2.stdout
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1

    # larger budget: resume the kept checkpoint and finish — counters
    # bit-identical to an uninterrupted run (the stall-test invariant)
    env3 = _campaign_env(tmp_path, out)          # default budget 600 s
    r3 = subprocess.run(_campaign_cmd(), env=env3, timeout=600,
                        capture_output=True, text=True)
    assert r3.returncode == 0, r3.stdout + r3.stderr
    assert "extending partial row" in r3.stdout, r3.stdout
    assert "resuming from existing checkpoint" in r3.stdout, r3.stdout
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 2 and rows[1]["done"], rows
    assert (rows[1]["tree"], rows[1]["best"], rows[1]["iters"]) == \
        CAMPAIGN_GOLDEN
    assert not ckpt.exists(), "solved run must retire its checkpoint"


def test_supervisor_screens_out_corrupt_checkpoint(tmp_path):
    """A mid-file-corrupted checkpoint (torn write: zlib.error /
    BadZipFile on read, neither a KeyError/OSError/ValueError) must be
    screened out and deleted at campaign startup, not crash the
    supervisor."""
    from tpu_tree_search.utils import faults

    out = tmp_path / "campaign.jsonl"
    ckpt = tmp_path / "tts_ta003_lb2.ckpt.npz"
    env = _campaign_env(tmp_path, out, TTS_BUDGET_S="0.01")
    r = subprocess.run(_campaign_cmd(), env=env, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert ckpt.exists()
    faults.corrupt_file(ckpt)

    env2 = _campaign_env(tmp_path, out)
    r2 = subprocess.run(_campaign_cmd(), env=env2, timeout=600,
                        capture_output=True, text=True)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert rows[-1]["done"], rows
    assert (rows[-1]["tree"], rows[-1]["best"], rows[-1]["iters"]) == \
        CAMPAIGN_GOLDEN


def test_campaign_serve_mode_same_rows(tmp_path):
    """The campaign's default path is now the search service
    (tools/run_campaign.py serve_main): one process, every instance
    submitted to an in-process SearchServer, the SAME JSONL row schema.
    The ta003 totals must match the legacy golden (tree/best are
    engine-invariant under ub=opt; iters is not asserted — the service
    runs the distributed engine with a BFS warm-up, the legacy worker
    the root-seeded single-device loop), a solved row must retire its
    checkpoint, and the executable-cache summary line must report the
    compile count."""
    out = tmp_path / "campaign.jsonl"
    ckpt = tmp_path / "tts_ta003_lb2.ckpt.npz"
    env = _campaign_env(tmp_path, out)
    cmd = [c for c in _campaign_cmd() if c != "--no-serve"]
    r = subprocess.run(cmd, env=env, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "executor cache" in r.stdout, r.stdout
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1, r.stdout
    row = rows[0]
    assert row["done"], row
    assert (row["tree"], row["best"]) == CAMPAIGN_GOLDEN[:2]
    # same schema as the legacy supervisor's rows
    for key in ("inst", "jobs", "machines", "lb", "chunk", "budget_s",
                "ub_mode", "done", "elapsed_s", "tree", "sol", "best",
                "evals", "iters", "pool_at_stop", "pushed_per_s",
                "evals_per_s", "restarts"):
        assert key in row, key
    assert not ckpt.exists(), "solved run must retire its checkpoint"

    # rerun: the done row retires the instance in serve mode too
    r2 = subprocess.run(cmd, env=env, timeout=600,
                        capture_output=True, text=True)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "skipping" in r2.stdout, r2.stdout
    assert len(out.read_text().splitlines()) == 1


def test_campaign_serve_partial_budget_extends(tmp_path):
    """Serve-mode budget semantics match the legacy supervisor's: a
    budget-exhausted instance lands a partial row (DEADLINE) keeping a
    checkpoint that carries the legacy config meta (inst/lb/chunk/
    ub_mode — the --no-serve supervisor's screen accepts it) AND the
    cumulative spent_s clock; a larger-budget rerun EXTENDS from the
    checkpoint to the bit-identical solved counters."""
    out = tmp_path / "campaign.jsonl"
    ckpt = tmp_path / "tts_ta003_lb2.ckpt.npz"
    cmd = [c for c in _campaign_cmd() if c != "--no-serve"]
    env = _campaign_env(tmp_path, out, TTS_BUDGET_S="0.01")
    r = subprocess.run(cmd, env=env, timeout=600,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 1 and rows[0]["done"] is False, rows
    assert ckpt.exists(), "partial row must keep its checkpoint"
    with np.load(ckpt) as z:
        assert int(z["meta_inst"]) == 3 and int(z["meta_lb"]) == 2
        assert int(z["meta_chunk"]) == 32
        assert str(z["meta_ub_mode"]) == "opt"
        assert float(z["meta_spent_s"]) > 0.0

    env2 = _campaign_env(tmp_path, out)          # default budget 600 s
    r2 = subprocess.run(cmd, env=env2, timeout=600,
                        capture_output=True, text=True)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "extending partial row" in r2.stdout, r2.stdout
    rows = [json.loads(ln) for ln in out.read_text().splitlines() if ln]
    assert len(rows) == 2 and rows[1]["done"], rows
    assert (rows[1]["tree"], rows[1]["best"]) == CAMPAIGN_GOLDEN[:2]
    # cumulative clock: the second row's elapsed includes the first
    # run's spend (budget continuity across server lifetimes)
    assert rows[1]["elapsed_s"] >= float(np.float64(rows[0]["elapsed_s"]))
    assert not ckpt.exists(), "solved run must retire its checkpoint"


def test_worker_resumes_stacked_distributed_checkpoint(tmp_path):
    """ADVICE r5: worker resume called int(np.asarray(state.iters)) and
    died with TypeError on a stacked distributed checkpoint, turning a
    config mistake into repeated worker deaths. Now it collapses the
    stack onto the single device via the elastic reshard and completes
    with exact accounting (warm-up counters ride the meta)."""
    from tpu_tree_search.problems import taillard

    out = tmp_path / "campaign.jsonl"
    status = tmp_path / "tts_ta003_lb2.status.jsonl"
    ckpt = tmp_path / "tts_ta003_lb2.ckpt.npz"
    p = taillard.processing_times(3)
    opt = taillard.optimal_makespan(3)
    part = distributed.search(p, lb_kind=2, init_ub=opt, n_devices=2,
                              chunk=8, capacity=1 << 16, min_seed=8,
                              segment_iters=20, max_rounds=10,
                              checkpoint_path=str(ckpt), heartbeat=None)
    assert ckpt.exists()
    assert not part.complete, "partial run finished — nothing to resume"

    cmd = _campaign_cmd()[:-1] + ["--worker", "3"]
    env = _campaign_env(tmp_path, out)
    proc = subprocess.run(cmd, env=env, timeout=600,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    recs = [json.loads(ln) for ln in status.read_text().splitlines()
            if ln.strip()]
    kinds = [r["kind"] for r in recs]
    assert "reshard" in kinds, kinds
    done = [r for r in recs if r["kind"] == "done"]
    assert done and done[0]["done"], recs
    assert done[0]["best"] == opt == 1081
    # explored-node accounting exact across the 2-worker -> 1-device
    # reshard: warm-up + device counters add up to the campaign golden
    assert done[0]["tree"] == CAMPAIGN_GOLDEN[0]


def test_dist_ub_opt_unchanged_counts():
    """The new balance plan + transactional rounds keep the ub=opt
    deterministic-tree invariant vs the sequential oracle."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=0)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=2, init_ub=opt)
    got = distributed.search(inst.p_times, lb_kind=2, init_ub=opt,
                             chunk=8, capacity=1 << 12, min_seed=4,
                             balance_period=2, min_transfer=2)
    assert (got.explored_tree, got.explored_sol, got.best) == \
           (want.explored_tree, want.explored_sol, want.best)
