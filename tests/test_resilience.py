"""Fault-tolerant search runtime: atomic/checksummed checkpoints with
last-good rollback, elastic resharding across worker counts, retry/
backoff + watchdog in the segmented driver, and the deterministic
fault-injection harness that makes all of it testable.

Every corruption path here must end in one of exactly two places: the
previous last-good snapshot, or a clear error — never a silent resume
of wrong state (the failure mode that poisons a multi-day campaign).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_tree_search.engine import checkpoint, device, distributed, \
    sequential as seq
from tpu_tree_search.engine.device import SearchState
from tpu_tree_search.ops import batched
from tpu_tree_search.parallel import balance as bal
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fault_plan():
    """Install a fault plan for the test, always disarmed afterwards."""
    yield faults.configure
    faults.reset()


def _setup():
    # seed=7: the largest ub=opt tree of the tiny synthetic family
    # (495 pushed nodes) — interruption points actually interrupt
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=7)
    opt = inst.brute_force_optimum()
    tables = batched.make_tables(inst.p_times)
    return inst, opt, tables


def _mid_state(inst, opt, tables, iters=3):
    state = device.init_state(inst.jobs, 1 << 10, opt,
                              p_times=inst.p_times)
    state = device.run(tables, state, 1, 8, max_iters=iters)
    assert int(state.size) > 0
    return state


def test_oracle_truncation_is_detectable():
    """The Python oracle reports truncation (max_nodes / deadline_s)
    via complete=False instead of silently returning partial counts a
    test could mistake for totals."""
    inst, opt, _ = _setup()
    full = seq.pfsp_search(inst, lb=1, init_ub=opt)
    assert full.complete
    part = seq.pfsp_search(inst, lb=1, init_ub=opt, max_nodes=3)
    assert not part.complete
    dead = seq.pfsp_search(inst, lb=1, init_ub=opt, deadline_s=0.0)
    assert not dead.complete


# ------------------------------------------------------------- waterfill


def test_waterfill_counts():
    c = bal.waterfill_counts(10, 4)
    assert c.tolist() == [3, 3, 2, 2]
    assert bal.waterfill_counts(0, 3).tolist() == [0, 0, 0]
    assert bal.waterfill_counts(2, 5).tolist() == [1, 1, 0, 0, 0]
    # water-filled: max-min difference <= 1, total preserved
    for total, m in ((17, 8), (8, 17), (1, 1)):
        c = bal.waterfill_counts(total, m)
        assert c.sum() == total
        assert c.max() - c.min() <= 1


# ------------------------------------------- atomic save / integrity


def test_save_rotates_last_good(tmp_path):
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    assert not checkpoint.last_good_path(path).exists()
    state2 = device.run(tables, state, 1, 8, max_iters=5)
    checkpoint.save(path, state2, meta={"segment": 2})
    prev = checkpoint.last_good_path(path)
    assert prev.exists()
    _, meta_cur = checkpoint.load(path)
    _, meta_prev = checkpoint.load(prev)
    assert int(meta_cur["segment"]) == 2
    assert int(meta_prev["segment"]) == 1
    # no stale temp file survives a clean save
    assert not path.with_suffix(".tmp.npz").exists()


def test_truncated_checkpoint_rolls_back(tmp_path):
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    checkpoint.save(path, device.run(tables, state, 1, 8, max_iters=5),
                    meta={"segment": 2})
    # torn write: the current file lost its tail
    data = path.read_bytes()
    path.write_bytes(data[:len(data) // 3])
    with pytest.raises(checkpoint.CheckpointCorrupt):
        checkpoint.load(path)
    with pytest.warns(RuntimeWarning, match="last-good"):
        st, meta, used = checkpoint.load_resilient(path)
    assert used == checkpoint.last_good_path(path)
    assert int(meta["segment"]) == 1
    # the rolled-back state finishes to the exact oracle totals
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    final = device.run(tables, st, 1, 8)
    assert (int(final.tree), int(final.sol), int(final.best)) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_flipped_bytes_roll_back(tmp_path):
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    checkpoint.save(path, device.run(tables, state, 1, 8, max_iters=5),
                    meta={"segment": 2})
    faults.corrupt_file(path)
    with pytest.raises(checkpoint.CheckpointCorrupt):
        checkpoint.load(path)
    with pytest.warns(RuntimeWarning, match="last-good"):
        _, meta, used = checkpoint.load_resilient(path)
    assert int(meta["segment"]) == 1


def test_embedded_crc_catches_valid_zip_with_wrong_payload(tmp_path):
    """Damage the zip container cannot see (a member rewritten whole)
    still fails the embedded payload CRC."""
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["best"] = np.asarray(arrays["best"] - 1)   # silent bit rot
    np.savez_compressed(path, **arrays)               # valid zip again
    with pytest.raises(checkpoint.CheckpointCorrupt, match="CRC32"):
        checkpoint.load(path)


def test_future_schema_version_fails_clearly(tmp_path):
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    checkpoint.save(path, state, meta={"segment": 2})
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["meta_schema_version"] = np.asarray(checkpoint.SCHEMA_VERSION + 1)
    np.savez_compressed(path, **arrays)
    with pytest.raises(checkpoint.CheckpointSchemaError,
                       match="schema version"):
        checkpoint.load(path)
    # NOT swallowed by the fallback: a valid newer-schema file must not
    # be silently shadowed by an older last-good snapshot
    with pytest.raises(checkpoint.CheckpointSchemaError):
        checkpoint.load_resilient(path)


def test_interrupted_write_uses_last_good(tmp_path):
    """Crash between the two renames: temp file present, current file
    missing, last-good holds the previous snapshot."""
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    # simulate save() dying after rotation, before the final rename
    os.replace(path, checkpoint.last_good_path(path))
    path.with_suffix(".tmp.npz").write_bytes(b"half-written garbage")
    assert checkpoint.resume_path(path) == checkpoint.last_good_path(path)
    st, meta, used = checkpoint.load_resilient(path)
    assert used == checkpoint.last_good_path(path)
    assert int(meta["segment"]) == 1
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    final = device.run(tables, st, 1, 8)
    assert (int(final.tree), int(final.sol), int(final.best)) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_corrupt_current_is_quarantined_not_rotated(tmp_path):
    """A skipped corrupt current file must be quarantined by
    load_resilient: otherwise the NEXT save rotates it over the good
    last-good, and a crash between save's two renames would leave zero
    loadable checkpoints (total progress loss)."""
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    state2 = device.run(tables, state, 1, 8, max_iters=5)
    checkpoint.save(path, state2, meta={"segment": 2})
    faults.corrupt_file(path)
    with pytest.warns(RuntimeWarning, match="last-good"):
        st, meta, used = checkpoint.load_resilient(path)
    assert int(meta["segment"]) == 1
    # the torn current was moved aside, not left for rotation
    assert not path.exists()
    assert path.with_name(path.name + ".corrupt").exists()
    # the next save must keep the GOOD seg-1 snapshot as last-good
    checkpoint.save(path, device.run(tables, st, 1, 8, max_iters=5),
                    meta={"segment": 3})
    _, meta_prev = checkpoint.load(checkpoint.last_good_path(path))
    assert int(meta_prev["segment"]) == 1
    _, meta_cur = checkpoint.load(path)
    assert int(meta_cur["segment"]) == 3


def test_everything_corrupt_raises_clear_error(tmp_path):
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)
    path = tmp_path / "c.npz"
    checkpoint.save(path, state, meta={"segment": 1})
    checkpoint.save(path, state, meta={"segment": 2})
    faults.corrupt_file(path)
    faults.corrupt_file(checkpoint.last_good_path(path))
    with pytest.warns(RuntimeWarning):
        with pytest.raises(checkpoint.CheckpointCorrupt,
                           match="no loadable checkpoint"):
            checkpoint.load_resilient(path)


# ------------------------------------------------------ elastic reshard


def test_reshard_preserves_totals_and_rows():
    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables, iters=3)

    def live_rows(s):
        s = SearchState(*(np.asarray(x) for x in s))
        if s.prmu.ndim == 2:
            s = SearchState(*(a[None, ...] for a in s))
        rows = []
        for d in range(s.prmu.shape[0]):
            n = int(np.atleast_1d(s.size)[d])
            for r in range(n):
                rows.append((tuple(s.prmu[d, :, r].tolist()),
                             int(s.depth[d, r]),
                             tuple(s.aux[d, :, r].tolist())))
        return sorted(rows)

    before = live_rows(state)
    for m in (1, 3, 5, 8):
        out = checkpoint.reshard_state(state, m)
        assert np.asarray(out.prmu).shape[0] == m
        sizes = np.asarray(out.size)
        assert sizes.max() - sizes.min() <= 1          # water-filled
        assert live_rows(out) == before                # no node lost/dup
        assert int(np.asarray(out.tree).sum()) == int(state.tree)
        assert int(np.asarray(out.sol).sum()) == int(state.sol)
        assert int(np.asarray(out.evals).sum()) == int(state.evals)
        assert int(np.asarray(out.best).min()) == int(state.best)
        assert (np.asarray(out.iters) == int(state.iters)).all()
        assert not np.asarray(out.overflow).any()
    # squeeze round-trips to the single-device shape device.run expects
    back = checkpoint.reshard_state(
        checkpoint.reshard_state(state, 5), 1, squeeze=True)
    assert np.asarray(back.prmu).ndim == 2
    assert live_rows(back) == before
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    final = device.run(tables, back, 1, 8)
    assert (int(final.tree), int(final.sol), int(final.best)) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_dist_elastic_resume_more_workers(tmp_path):
    """2-worker checkpoint resumes on the full 8-worker mesh (M > N)
    with exact totals."""
    inst, opt, tables = _setup()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    ckpt = tmp_path / "dist2.npz"
    part = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                              n_devices=2, chunk=4, capacity=1 << 12,
                              min_seed=8, segment_iters=2,
                              checkpoint_path=str(ckpt), max_rounds=2,
                              heartbeat=None)
    assert ckpt.exists()
    assert not part.complete, "partial run finished — nothing to resume"
    with pytest.warns(RuntimeWarning, match="resharding"):
        res = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                                 chunk=4, capacity=1 << 12,
                                 checkpoint_path=str(ckpt),
                                 heartbeat=None)
    assert res.complete
    assert (res.explored_tree, res.explored_sol, res.best) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_single_device_checkpoint_resumes_on_mesh(tmp_path):
    """A single-device snapshot lifts onto a 4-worker mesh — the
    smallest-slice-to-bigger-slice elastic path."""
    inst, opt, tables = _setup()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    state = _mid_state(inst, opt, tables)
    ckpt = tmp_path / "single.npz"
    checkpoint.save(ckpt, state)
    with pytest.warns(RuntimeWarning, match="resharding"):
        res = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                                 n_devices=4, chunk=4, capacity=1 << 12,
                                 checkpoint_path=str(ckpt),
                                 heartbeat=None)
    assert res.complete
    assert (res.explored_tree, res.explored_sol, res.best) == \
           (want.explored_tree, want.explored_sol, want.best)


# ------------------------------------- retry / watchdog / fault harness


def test_fault_spec_parsing():
    plan = faults.FaultPlan.parse(
        "kill_after_segment=3, corrupt_checkpoint=2,"
        "delay_segment=4:0.25,fail_host_fetch=2")
    assert plan.kill_after_segment == 3
    assert plan.corrupt_checkpoint == 2
    assert plan.delay_segment == (4, 0.25)
    assert plan.fail_host_fetch == 2
    with pytest.raises(ValueError, match="unknown fault"):
        faults.FaultPlan.parse("tip_over_rack=1")


def test_transient_fetch_failures_are_retried(fault_plan):
    inst, opt, tables = _setup()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    fault_plan("fail_host_fetch=2")

    def run_fn(state, target):
        return device.run(tables, state, 1, 8, max_iters=target)

    state = device.init_state(inst.jobs, 1 << 10, opt,
                              p_times=inst.p_times)
    with pytest.warns(RuntimeWarning, match="transient"):
        final = checkpoint.run_segmented(run_fn, state, segment_iters=4,
                                         heartbeat=None,
                                         retry_base_s=0.01)
    assert (int(final.tree), int(final.sol), int(final.best)) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_retry_gives_up_after_attempts(fault_plan):
    inst, opt, tables = _setup()
    fault_plan("fail_host_fetch=100")

    def run_fn(state, target):
        return device.run(tables, state, 1, 8, max_iters=target)

    state = device.init_state(inst.jobs, 1 << 10, opt,
                              p_times=inst.p_times)
    with pytest.warns(RuntimeWarning, match="transient"):
        with pytest.raises(faults.InjectedFault):
            checkpoint.run_segmented(run_fn, state, segment_iters=4,
                                     heartbeat=None, retry_attempts=2,
                                     retry_base_s=0.01)


def test_segment_watchdog_times_out():
    import time as _time

    inst, opt, tables = _setup()
    state = _mid_state(inst, opt, tables)

    def hung_run_fn(s, target):
        _time.sleep(5)
        return s

    with pytest.raises(checkpoint.SegmentTimeout, match="watchdog"):
        checkpoint.run_segmented(hung_run_fn, state, segment_iters=4,
                                 heartbeat=None, segment_timeout_s=0.2)


def test_delay_segment_injection(fault_plan):
    import time as _time

    inst, opt, tables = _setup()
    fault_plan("delay_segment=1:0.3")

    def run_fn(state, target):
        return device.run(tables, state, 1, 8, max_iters=target)

    state = device.init_state(inst.jobs, 1 << 10, opt,
                              p_times=inst.p_times)
    t0 = _time.perf_counter()
    checkpoint.run_segmented(run_fn, state, segment_iters=4,
                             heartbeat=None, max_segments=1)
    assert _time.perf_counter() - t0 >= 0.3


def test_corrupt_checkpoint_injection_rolls_back(fault_plan, tmp_path):
    """The corrupt-checkpoint injection tears the file written at
    segment 2; the resume path must land on segment 1's last-good
    snapshot and still finish to the exact oracle totals."""
    inst, opt, tables = _setup()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    fault_plan("corrupt_checkpoint=2")
    path = tmp_path / "c.npz"

    def run_fn(state, target):
        return device.run(tables, state, 1, 2, max_iters=target)

    state = device.init_state(inst.jobs, 1 << 10, opt,
                              p_times=inst.p_times)
    part = checkpoint.run_segmented(run_fn, state, segment_iters=1,
                                    checkpoint_path=str(path),
                                    heartbeat=None, max_segments=2)
    assert int(part.size) > 0, "run finished inside 2 segments"
    faults.reset()
    with pytest.raises(checkpoint.CheckpointCorrupt):
        checkpoint.load(path)
    with pytest.warns(RuntimeWarning, match="last-good"):
        st, meta, used = checkpoint.load_resilient(path)
    assert int(meta["segment"]) == 1
    final = checkpoint.run_segmented(run_fn, st, segment_iters=64,
                                     heartbeat=None)
    assert (int(final.tree), int(final.sol), int(final.best)) == \
           (want.explored_tree, want.explored_sol, want.best)


# ------------------------------------------------- kernel_ok tightening


def test_kernel_ok_admits_only_validated_tile_family(monkeypatch):
    from tpu_tree_search.ops import pallas_expand

    monkeypatch.setattr(pallas_expand.jax, "default_backend",
                        lambda: "tpu")
    # the validated family stays admitted
    assert pallas_expand.kernel_ok(20, 1024, 1)     # 128-aligned tile
    assert pallas_expand.kernel_ok(200, 128, 1)
    # TB=64 at big J: Mosaic refuses its reshape on the installed jax
    # (tests/test_tpu_compile.py), so it takes the XLA fallback
    assert not pallas_expand.kernel_ok(200, 64, 1)
    # the relaxed-arithmetic shapes the old branch silently admitted
    # (never run on hardware) take the XLA fallback
    assert not pallas_expand.kernel_ok(130, 192, 1)  # 130*192 % 128 == 0
    assert not pallas_expand.kernel_ok(128, 96, 1)   # 128*96 % 128 == 0
    assert not pallas_expand.kernel_ok(129, 64, 1)   # odd J at TB=64


# ------------------------------------------ end-to-end kill smoke (slow)


@pytest.mark.slow
def test_kill_injection_elastic_restart_smoke(tmp_path):
    """The acceptance drill: a 4-worker distributed search is preempted
    by the kill-after-segment injection (exit 137, checkpoint on disk),
    restarted on a DIFFERENT worker count, and the final makespan and
    explored-node accounting match an uninterrupted run exactly."""
    inst, opt, tables = _setup()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    ckpt = tmp_path / "kill.npz"
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
from tpu_tree_search.engine import distributed
from tpu_tree_search.problems.pfsp import PFSPInstance
inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=7)
distributed.search(inst.p_times, lb_kind=1, init_ub={opt},
                   n_devices=4, chunk=4, capacity=1 << 12, min_seed=8,
                   segment_iters=2, checkpoint_path={str(ckpt)!r},
                   heartbeat=None)
"""
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "TTS_FAULTS": "kill_after_segment=2"}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=600, capture_output=True, text=True)
    assert proc.returncode == faults.KILL_EXIT_CODE, \
        (proc.returncode, proc.stdout, proc.stderr)
    assert ckpt.exists(), "preemption left no checkpoint"

    with pytest.warns(RuntimeWarning, match="resharding"):
        res = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                                 n_devices=8, chunk=4, capacity=1 << 12,
                                 checkpoint_path=str(ckpt),
                                 heartbeat=None)
    assert res.complete
    assert res.best == want.best == opt
    assert (res.explored_tree, res.explored_sol) == \
           (want.explored_tree, want.explored_sol)
