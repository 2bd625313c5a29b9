"""Campaign driver: solve Taillard instances end-to-end with a
per-instance compute budget, partial-progress reporting, and automatic
recovery.

TWO EXECUTION MODES:

- **serve (default)**: the campaign is the first client of the search
  service (tpu_tree_search/service/): ONE long-lived process submits
  every selected instance to an in-process SearchServer, polls, and
  writes the same JSONL rows. No per-instance process spin-up, and the
  executable cache compiles each (jobs x machines, lb, submesh) shape
  ONCE for the whole campaign instead of once per instance —
  `--submeshes K` additionally solves K instances concurrently on a
  partitioned mesh. Budget exhaustion maps to the service's DEADLINE
  state (checkpoint kept; a rerun with a larger TTS_BUDGET_S resumes
  it), and the legacy checkpoint naming is preserved, so in-flight
  legacy checkpoints resume under serve mode (elastically resharded).
- **--no-serve (DEPRECATED, kept for one release)**: the original
  process-per-instance supervisor below — worker subprocess per
  instance, heartbeat-age stall kill + respawn. Still the right tool
  when the device runtime itself is expected to wedge whole processes;
  the serve path keeps everything in one process and cannot kill a
  truly hung dispatch. The parent never starts a JAX backend (a chip
  belongs to one process): only the workers touch the device.

Legacy architecture (--no-serve), per-instance wall budget and
AUTOMATIC STALL RECOVERY:

Generalizes tools/run_single_device_table.py (the 20x20
table) to the reference's wider campaign groups: the
50-job groups its intra-node driver enumerates
(/root/reference/pfsp/launch_scripts/mgpu_launch.sh:51-58 — ta031-ta050
and ta052/53/56/57/58) and any other instance list, at either bound.

Architecture: each instance runs in a WORKER SUBPROCESS
that heartbeats a JSON status line per segment and checkpoints every
--checkpoint-every segments; the supervisor in this process watches the
heartbeat age and, when it exceeds ~4x the recent segment pace (a hung
device dispatch), kills the worker's process group and respawns it resuming from the last
checkpoint. Search determinism (fixed chunk, DFS order) makes the
redo-from-checkpoint lossless: final counters are bit-identical to an
unkilled run (tests/test_dist_durability.py::test_supervisor_stall_resume).
The reference's only stall tooling is a 10 s "Still Idle" print
(pfsp_dist_multigpu_cuda.c:663-668) — it never recovers.

Per instance: solve to the PROVEN optimum (ub=opt by default, pool
drained) within the budget, else stop at the budget and record the
partial row — tree so far, sustained pushed-nodes/s and eval rate — so
infeasible instances get a measured rate + extrapolation instead of
silence. Overflow grows the pool losslessly (checkpoint.grow) and
continues.

    TTS_BUDGET_S=7200 nohup python -u tools/run_campaign.py 31 32 ... \
        > /tmp/campaign.log 2>&1 &

Env: TTS_BUDGET_S (default 7200), TTS_LB (default 2), TTS_CHUNK
(default 32768), TTS_CAMPAIGN_OUT (default /tmp/campaign.jsonl),
TTS_WORKDIR (status/checkpoint files, default /tmp), TTS_SEG (default
2000 iters/segment), TTS_CKPT_EVERY (segments between checkpoints,
default 8), TTS_UB ("opt" | "inf", default opt), TTS_SUBMESHES (serve
mode: concurrent submeshes, default 1), TTS_STALL_GRACE
(seconds before the first heartbeat may be declared dead, default 900 —
covers a cold 50x20 compile), TTS_MAX_RESTARTS (default 50).
Resilience knobs ride through to the worker's run_segmented:
TTS_RETRY_ATTEMPTS / TTS_RETRY_BASE_S (transient-error backoff) and
TTS_SEG_TIMEOUT_S (per-segment wall watchdog — the in-process
complement of this supervisor's heartbeat-age kill).
TTS_SEARCH_TELEMETRY=1 compiles the on-device search-telemetry block
into every solve (engine/telemetry.py): rows gain a `telemetry` column
(pruning rate, frontier depth, pool high-water, steal flow) and the
serve-mode trace carries per-segment search.telemetry events
(tools/search_report.py renders them). Checkpoints are
atomic + checksummed with a rotating `.prev` last-good; a worker that
finds its current snapshot torn rolls back to the last-good one
(engine/checkpoint.load_resilient). A budget-exhausted PARTIAL row
keeps its checkpoint, and a rerun with a larger TTS_BUDGET_S resumes
it instead of skipping (only `done` rows retire their checkpoints).
Test hooks (worker side): TTS_TEST_STALL_AT_SEG=N — after writing
segment N's heartbeat, hang forever (simulates a hung device
dispatch); TTS_FAULTS — deterministic fault injection
(utils/faults.py: kill_after_segment / corrupt_checkpoint /
delay_segment / fail_host_fetch), inherited by every respawned worker.
"""

import json
import os
import signal
import subprocess
import sys
import time
import zipfile
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# knob reads go through the lint-checked registry accessors
# (utils/config.KNOBS — defaults live there, tts_lint enforces the
# single-sourcing). Importing the package starts no JAX backend.
from tpu_tree_search.utils import config as _cfg  # noqa: E402

OUT = _cfg.env_str("TTS_CAMPAIGN_OUT")
WORKDIR = _cfg.env_str("TTS_WORKDIR")
LB = _cfg.env_int("TTS_LB")
CHUNK = _cfg.env_int("TTS_CHUNK")
BUDGET_S = _cfg.env_float("TTS_BUDGET_S")
SEG = _cfg.env_int("TTS_SEG")
CKPT_EVERY = _cfg.env_int("TTS_CKPT_EVERY")
UB_MODE = _cfg.env_str("TTS_UB")
STALL_GRACE = _cfg.env_float("TTS_STALL_GRACE")
STALL_FACTOR = _cfg.env_float("TTS_STALL_FACTOR")
# the floor is long on purpose: the supervisor exists for PERMANENT
# hangs, and killing a dispatch that is merely slow loses its unsaved
# segments; ~12 min detection latency is noise on the multi-hour runs
# it protects.
STALL_MIN = _cfg.env_float("TTS_STALL_MIN")
MAX_RESTARTS = _cfg.env_int("TTS_MAX_RESTARTS")
# consecutive worker deaths with no iteration progress before giving
# up: 5, not fewer — after a crash the first several respawns can
# each burn the full init grace
DEAD_LIMIT = _cfg.env_int("TTS_DEAD_LIMIT")


def paths(inst: int, lb: int):
    base = os.path.join(WORKDIR, f"tts_ta{inst:03d}_lb{lb}")
    return base + ".status.jsonl", base + ".ckpt.npz"


def _telemetry_columns(block_or_summary) -> dict:
    """Search-efficiency columns for a result row, from either a raw
    state.telemetry block (legacy worker) or a DistResult.telemetry
    summary dict (serve mode); {} when telemetry is off — rows from
    telemetry-off campaigns keep their exact historical schema."""
    s = block_or_summary
    if s is None:
        return {}
    if not isinstance(s, dict):
        import numpy as np
        if not np.asarray(s).size:
            return {}
        from tpu_tree_search.engine import telemetry as tele
        s = tele.summarize(np.asarray(s))
    return {"telemetry": {
        "pruning_rate": s["pruning_rate"],
        "frontier_depth": s["frontier_depth"],
        "pool_highwater": s["pool_highwater"],
        "branched": sum(s["branched"]),
        "pruned": sum(s["pruned"]),
        "steal_sent": s["steal_sent"],
        "steal_recv": s["steal_recv"],
        "improvements": s["improvements"],
    }}


# the rotating last-good sibling every atomic save leaves beside the
# checkpoint (engine/checkpoint.LAST_GOOD_SUFFIX — duplicated here so
# the supervisor process never imports the engine, which would start a
# JAX backend and hold the chip its worker needs)
def last_good(path: str) -> str:
    return path + ".prev"


def unlink_checkpoint(ckpt_path: str) -> None:
    for p in (ckpt_path, last_good(ckpt_path)):
        if os.path.exists(p):
            os.unlink(p)


# ----------------------------------------------------------------- worker

def worker_main(inst: int) -> None:
    """Solve one instance via checkpoint.run_segmented (THE segmented
    driver — this function only adds the status-file heartbeat, the wall
    budget, and overflow growth), heartbeating + checkpointing.

    Resumes from the checkpoint file if it exists (the pool arrays AND
    every counter live in the SearchState the checkpoint stores, so the
    resumed run continues the exact count sequence)."""
    from tpu_tree_search.utils import compile_cache

    compile_cache.enable()

    import numpy as np

    import jax

    from tpu_tree_search.engine import checkpoint, device
    from tpu_tree_search.ops import batched
    from tpu_tree_search.problems import taillard

    lb = LB
    status_path, ckpt_path = paths(inst, lb)
    stall_at = _cfg.env_int("TTS_TEST_STALL_AT_SEG")

    def emit(rec: dict) -> None:
        rec["t"] = time.time()
        with open(status_path, "a") as f:
            f.write(json.dumps(rec) + "\n")

    p = taillard.processing_times(inst)
    ub = taillard.optimal_makespan(inst) if UB_MODE == "opt" else None
    m, jobs = p.shape
    tables = batched.make_tables(p)
    capacity = _cfg.env_int("TTS_POOL_ROWS") or \
        max(device.default_capacity(jobs, m), 4 * CHUNK * jobs)
    grows = 0
    spent_before = 0.0
    warm_tree = warm_sol = 0
    state = None
    if checkpoint.resume_path(ckpt_path):
        # load_resilient: a torn current snapshot (the worker was killed
        # mid-save) rolls back to the rotating last-good sibling instead
        # of crash-looping the respawn cycle
        try:
            state, meta, used = checkpoint.load_resilient(ckpt_path,
                                                          p_times=p)
        except checkpoint.CheckpointSchemaError as e:
            # a newer-schema checkpoint is an operator problem (wrong
            # build), not damage: abort the campaign loudly via the
            # fatal channel — the supervisor would otherwise respawn
            # the same crash DEAD_LIMIT times and silently drop the
            # instance
            emit({"kind": "fatal", "reason": str(e)[:300]})
            sys.exit(3)
        except checkpoint.CheckpointCorrupt as e:
            # EVERY candidate unreadable: delete the husks and restart
            # the instance from scratch — losing the (garbage) file is
            # recovery, crash-looping until DEAD_LIMIT is not.
            # CheckpointSchemaError stays fatal on purpose (a valid
            # newer-format file is an operator problem, not damage).
            emit({"kind": "corrupt_restart", "reason": str(e)[:200]})
            unlink_checkpoint(ckpt_path)
    if state is not None:
        if str(used) != str(ckpt_path):
            emit({"kind": "rollback", "path": str(used)})
        if np.asarray(meta.get("host_depth", np.zeros(0))).size:
            # a -C distributed checkpoint carries carved host-tier seed
            # rows; silently dropping them would lose subtrees — refuse
            # loudly, the distributed engine owns that resume path
            emit({"kind": "fatal",
                  "reason": "checkpoint carries a host-tier share; "
                            "resume it with the distributed engine"})
            sys.exit(3)
        if np.asarray(state.prmu).ndim == 3:
            # a stacked distributed checkpoint (e.g. TTS_WORKDIR pointed
            # at a file the distributed engine wrote): collapse it onto
            # this single device instead of dying on the shape — the
            # shared helper owns the sizing invariant (footprint +
            # usable-row headroom)
            state = checkpoint.collapse_to_single_device(state, CHUNK,
                                                         jobs)
            emit({"kind": "reshard", "workers": 1})
        # warm-up counters live in the checkpoint's meta, not the state
        # (distributed.search tracks them the same way); carry them so
        # the final row's accounting stays exact across elastic resumes
        warm_tree = int(meta.get("warmup_tree", 0))
        warm_sol = int(meta.get("warmup_sol", 0))
        capacity = state.prmu.shape[-1]
        grows = int(meta.get("grows", 0))
        spent_before = float(meta.get("spent_s", 0.0))
        if bool(np.asarray(state.overflow).any()):
            # killed right after an overflow checkpoint: grow NOW or the
            # resumed loop would exit immediately forever
            capacity *= 2
            grows += 1
            state = checkpoint.grow(state, capacity)
            emit({"kind": "grow", "capacity": capacity})
        emit({"kind": "resume", "iters": int(np.asarray(state.iters).max()),
              "capacity": capacity, "spent_s": spent_before})
    else:
        state = device.init_state(jobs, capacity, ub, p_times=p)

    t0 = time.perf_counter()

    def spent_now(elapsed: float) -> float:
        return spent_before + elapsed

    def hb(rep):
        # the worker clock (t0), NOT rep.elapsed: run_segmented restarts
        # its elapsed at every overflow-grow re-entry, which would reset
        # the wall budget after each grow
        emit({"kind": "seg", "seg": rep.segment, "iters": rep.iters,
              "tree": rep.tree, "sol": rep.sol, "best": rep.best,
              "size": rep.pool_size, "capacity": capacity,
              "spent_s": round(spent_now(time.perf_counter() - t0), 2)})
        if rep.segment % CKPT_EVERY == 0:
            # run_segmented saves right after this callback; the marker
            # tells the supervisor to allow a long heartbeat gap for the
            # save (a multi-hundred-MB pool fetch from the device)
            emit({"kind": "ckpt_start", "seg": rep.segment})
        if stall_at and rep.segment >= stall_at:
            emit({"kind": "test_stall", "seg": rep.segment})
            time.sleep(10 ** 6)  # simulated dead dispatch (test hook)

    def run_fn(s, target):
        return device.run(tables, s, lb, CHUNK, max_iters=target)

    while True:
        def mk_meta():
            return {"inst": inst, "lb": lb, "chunk": CHUNK,
                    "ub_mode": UB_MODE, "grows": grows,
                    "warmup_tree": warm_tree, "warmup_sol": warm_sol,
                    "spent_s": round(
                        spent_now(time.perf_counter() - t0), 2)}

        try:
            state = checkpoint.run_segmented(
                run_fn, state, segment_iters=SEG,
                checkpoint_path=ckpt_path, checkpoint_every=CKPT_EVERY,
                heartbeat=hb, checkpoint_meta=mk_meta,
                should_stop=lambda rep: spent_now(
                    time.perf_counter() - t0) > BUDGET_S)
            break
        except checkpoint.PoolOverflow as e:
            capacity *= 2
            grows += 1
            emit({"kind": "grow", "capacity": capacity})
            state = checkpoint.grow(e.state, capacity)

    fetched = jax.device_get((state.iters, state.tree, state.sol,
                              state.best, state.size, state.evals))
    iters, tree, sol, best, size, evals = (int(np.asarray(v).max())
                                           for v in fetched)
    tree += warm_tree
    sol += warm_sol
    spent = spent_now(time.perf_counter() - t0)
    done = size == 0
    row = {"inst": inst, "jobs": jobs, "machines": m, "lb": lb,
           "chunk": CHUNK, "budget_s": BUDGET_S, "ub_mode": UB_MODE,
           "done": done, "elapsed_s": round(spent, 2), "tree": tree,
           "sol": sol, "best": best, "evals": evals, "iters": iters,
           "capacity": capacity, "grows": grows, "pool_at_stop": size,
           "pushed_per_s": round(tree / max(spent, 1e-9), 1),
           "evals_per_s": round(evals / max(spent, 1e-9), 1)}
    row.update(_telemetry_columns(state.telemetry))
    if done and UB_MODE == "opt" and best != ub:
        # a WRONG ANSWER is never a transient — the supervisor must
        # abort the campaign loudly, not retry/skip
        emit({"kind": "fatal",
              "reason": f"wrong answer: best={best} != optimum {ub}",
              **row})
        sys.exit(3)
    emit({"kind": "done", **row})


# ------------------------------------------------------------- supervisor

def read_status(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if ln:
                try:
                    out.append(json.loads(ln))
                except json.JSONDecodeError:
                    pass  # torn write from a killed worker
    return out


def stall_timeout(fresh: list[dict]) -> float:
    """Adaptive heartbeat timeout: ~STALL_FACTOR x the slowest recent
    inter-heartbeat gap (checkpoint segments are legitimately slower —
    a multi-hundred-MB pool fetch from the device), floored at
    STALL_MIN. Gaps are measured within the CURRENT worker run only —
    a gap spanning a previous kill+respawn would inflate the estimate
    by the very stall it recovered from. Before any gap is measurable,
    STALL_GRACE (cold compile)."""
    ts = [r["t"] for r in fresh[-12:]]
    gaps = [b - a for a, b in zip(ts, ts[1:]) if b > a]
    if not gaps:
        return STALL_GRACE
    return max(STALL_MIN, STALL_FACTOR * max(gaps))


def supervise(inst: int, lb: int) -> dict | None:
    """Run the worker for one instance, restarting it (resume from the
    last checkpoint) whenever its heartbeat goes dead. Returns the final
    row, or None if the instance failed MAX_RESTARTS times."""
    status_path, ckpt_path = paths(inst, lb)
    if os.path.exists(status_path):
        os.unlink(status_path)
    # A checkpoint from a DIFFERENT configuration would silently resume
    # work measured under other settings — but one matching the current
    # (inst, lb, chunk) is durable in-flight progress from a killed
    # campaign supervisor and must be resumed, not discarded. Both the
    # current file and its rotating last-good sibling are screened: a
    # torn current is deleted (the worker would only fall back anyway)
    # while a good last-good survives to be the worker's rollback.
    import numpy as np
    resumable = False
    for cand in (ckpt_path, last_good(ckpt_path)):
        if not os.path.exists(cand):
            continue
        try:
            with np.load(cand) as z:
                match = (int(z["meta_inst"]) == inst
                         and int(z["meta_lb"]) == lb
                         and int(z["meta_chunk"]) == CHUNK
                         and str(z["meta_ub_mode"]) == UB_MODE)
        except (KeyError, OSError, ValueError, EOFError,
                zipfile.BadZipFile, zlib.error):
            # the same error surface checkpoint.load treats as
            # corruption — a torn file must be screened out here, not
            # crash the whole campaign at startup
            match = False
        if match:
            resumable = True
        else:
            os.unlink(cand)
    if resumable:
        print(f"ta{inst:03d} lb{lb}: resuming from existing "
              f"checkpoint {ckpt_path}", flush=True)

    restarts = 0
    iters_at_spawn = -1
    dead_without_progress = 0
    while True:
        n_before = len(read_status(status_path))
        proc = subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__),
             "--worker", str(inst)],
            start_new_session=True)
        spawn_t = time.time()
        outcome = None      # "done" | "exit" | "stall"
        while True:
            time.sleep(1.0)
            recs = read_status(status_path)
            fresh = recs[n_before:]
            for r in fresh:
                if r.get("kind") == "done":
                    outcome = "done"
                    row = r
                    break
            if outcome == "done":
                break
            rc = proc.poll()
            if rc is not None:
                outcome = "exit"
                break
            last_t = fresh[-1]["t"] if fresh else spawn_t
            timeout = stall_timeout(fresh)
            if fresh and fresh[-1].get("kind") == "ckpt_start":
                # a checkpoint save is in flight — legitimately minutes
                # at production pool sizes; don't kill it on the segment pace
                timeout = max(timeout, STALL_GRACE)
            if time.time() - last_t > timeout:
                outcome = "stall"
                break
        if outcome == "done":
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            proc.wait()
            # ONLY a solved (done=true, drained-pool) run retires its
            # checkpoint — a surviving final checkpoint would make a
            # later re-measurement campaign "resume" it and instantly
            # re-report THESE counters as a fresh result. A
            # budget-exhausted PARTIAL row keeps the checkpoint: it is
            # recoverable in-flight progress, and a rerun with a larger
            # TTS_BUDGET_S extends it instead of starting over
            # (ADVICE.md round 5, the unconditional unlink made partial
            # progress unrecoverable).
            if row.get("done") is True:
                unlink_checkpoint(ckpt_path)
            elif os.path.exists(ckpt_path):
                print(f"ta{inst:03d} lb{lb}: budget exhausted — keeping "
                      f"checkpoint {ckpt_path} for a larger-budget rerun",
                      flush=True)
            row.pop("kind", None)
            row.pop("t", None)
            row["restarts"] = restarts
            return row
        # dead or hung: kill the whole process group and resume
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
        recs = read_status(status_path)
        for r in recs[n_before:]:
            if r.get("kind") == "fatal":
                # a wrong answer is never a transient — abort the whole
                # campaign loudly rather than retry or skip
                raise RuntimeError(
                    f"ta{inst:03d} lb{lb}: {r.get('reason', 'fatal')}")
        iters_now = max((r.get("iters", 0) for r in recs), default=0)
        if iters_now <= iters_at_spawn:
            dead_without_progress += 1
        else:
            dead_without_progress = 0
        iters_at_spawn = iters_now
        restarts += 1
        print(f"ta{inst:03d} lb{lb}: worker {outcome} "
              f"(restart {restarts}, iters={iters_now}); resuming from "
              f"checkpoint", flush=True)
        if restarts >= MAX_RESTARTS or dead_without_progress >= DEAD_LIMIT:
            print(f"ta{inst:03d} lb{lb}: giving up after {restarts} "
                  f"restarts ({dead_without_progress} without progress)",
                  flush=True)
            return None
        time.sleep(min(30, 5 * dead_without_progress + 2))


def select_instances(insts: list[int]) -> list[int]:
    """Drop instances already retired by a row in OUT (shared by both
    modes). The skip key includes done/budget, not just (inst, lb,
    chunk): a PARTIAL row only retires its instance up to the budget it
    was measured at — a rerun with a larger TTS_BUDGET_S resumes the
    kept checkpoint and extends it (ADVICE.md round 5: the old key
    silently skipped exactly the reruns partial rows exist for)."""
    done = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            for ln in f:
                if ln.strip():
                    r = json.loads(ln)
                    # rows from before the chunk field default to the
                    # current CHUNK (they predate configurable rechecks)
                    done[(r["inst"], r["lb"], r.get("chunk", CHUNK))] = r
    out = []
    for inst in insts:
        r = done.get((inst, LB, CHUNK))
        if r is not None and (r.get("done", True)
                              or float(r.get("budget_s", BUDGET_S))
                              >= BUDGET_S):
            tag = "done" if r.get("done", True) else \
                f"partial at budget {r.get('budget_s')}s"
            print(f"ta{inst:03d} lb{LB}: already {tag} "
                  f"(chunk={r.get('chunk', CHUNK)} "
                  f"t={r['elapsed_s']}s tree={r['tree']}), skipping",
                  flush=True)
            continue
        if r is not None:
            print(f"ta{inst:03d} lb{LB}: extending partial row "
                  f"(budget {r.get('budget_s')}s -> {BUDGET_S:.0f}s)",
                  flush=True)
        out.append(inst)
    return out


def append_row(row: dict) -> None:
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")
    tag = "SOLVED" if row["done"] else "partial"
    print(f"ta{row['inst']:03d} lb{row['lb']}: {tag} "
          f"t={row['elapsed_s']}s tree={row['tree']} "
          f"pushed/s={row['pushed_per_s']} "
          f"restarts={row.get('restarts', 0)}", flush=True)


# ----------------------------------------------------------- serve mode

def serve_main(insts: list[int], n_submeshes: int) -> None:
    """The campaign as the search service's first client: ONE process,
    every instance submitted up front, results polled in order — the
    executable cache compiles each instance CLASS once for the whole
    campaign, and `n_submeshes > 1` solves that many instances
    concurrently. Budget exhaustion is the service's DEADLINE state
    (checkpoint kept under the legacy name, so --no-serve and serve
    runs resume each other's partials)."""
    from tpu_tree_search.utils import compile_cache

    compile_cache.enable()

    import numpy as np  # noqa: F401 (platform init order)

    from tpu_tree_search.obs import tracelog
    from tpu_tree_search.problems import taillard
    from tpu_tree_search.service import SearchRequest, SearchServer

    todo = select_instances(insts)
    if not todo:
        return
    # the campaign's flight recorder: every row points at the JSONL
    # event log that shows its requests' dispatches, preemptions,
    # checkpoints and retries (tools/trace_summary.py renders it;
    # obs/chrome_trace converts it for Perfetto)
    trace_file = _cfg.env_str("TTS_TRACE_FILE") or \
        os.path.join(WORKDIR, "campaign_trace.jsonl")
    tracelog.get().set_sink(trace_file)
    print(f"flight recorder: {trace_file}", flush=True)
    with SearchServer(n_submeshes=n_submeshes, workdir=WORKDIR,
                      max_queue_depth=max(64, len(todo) + 1),
                      segment_iters=SEG,
                      checkpoint_every=CKPT_EVERY) as srv:
        from tpu_tree_search.engine import device

        rids = {}
        for inst in todo:
            p = taillard.processing_times(inst)
            ub = (taillard.optimal_makespan(inst) if UB_MODE == "opt"
                  else None)
            # the legacy worker's capacity floor (4*chunk*jobs headroom
            # above the class default); the distributed driver still
            # grows losslessly on overflow, this just avoids paying the
            # grow+recompile on instances the floor was tuned for
            capacity = _cfg.env_int("TTS_POOL_ROWS") or \
                max(device.default_capacity(p.shape[1], p.shape[0]),
                    4 * CHUNK * p.shape[1])
            rids[inst] = srv.submit(SearchRequest(
                p_times=p, lb_kind=LB, init_ub=ub, chunk=CHUNK,
                capacity=capacity, deadline_s=BUDGET_S,
                # the legacy worker's checkpoint base name AND config
                # meta (inst/lb/chunk/ub_mode): serve-mode campaigns
                # resume --no-serve partials and vice versa — the
                # legacy supervisor's config screen accepts these files
                tag=f"tts_ta{inst:03d}_lb{LB}",
                checkpoint_meta={"inst": inst, "lb": LB, "chunk": CHUNK,
                                 "ub_mode": UB_MODE}))
            print(f"ta{inst:03d} lb{LB}: submitted "
                  f"(budget {BUDGET_S:.0f}s)", flush=True)
        for inst in todo:
            rec = srv.result(rids[inst])
            row = _serve_row(inst, rec, trace_file)
            if row is None:
                continue
            if (row["done"] and UB_MODE == "opt"
                    and row["best"] != taillard.optimal_makespan(inst)):
                raise RuntimeError(
                    f"ta{inst:03d} lb{LB}: wrong answer: "
                    f"best={row['best']} != optimum "
                    f"{taillard.optimal_makespan(inst)}")
            append_row(row)
        snap = srv.status_snapshot()
        print(f"campaign served {snap['counters']['done']} done / "
              f"{snap['counters']['deadline']} partial; executor cache "
              f"{snap['executor_cache']['hits']} hits / "
              f"{snap['executor_cache']['misses']} compiles", flush=True)


def _serve_row(inst: int, rec, trace_file: str | None = None
               ) -> dict | None:
    """A service RequestRecord -> the campaign's JSONL row schema."""
    from tpu_tree_search.problems import taillard

    p = taillard.processing_times(inst)
    m, jobs = p.shape
    res = rec.result
    if res is None or rec.state in ("FAILED", "CANCELLED"):
        print(f"ta{inst:03d} lb{LB}: {rec.state} "
              f"({rec.error or 'no result'}); no row", flush=True)
        return None
    spent = rec.spent_s()
    per = res.per_device
    evals = int(sum(per.get("evals", [0])))
    iters = int(max(per.get("iters", [0])))
    pool = int(sum(per.get("final_size", [0])))
    done = rec.state == "DONE" and res.complete
    return {**_telemetry_columns(getattr(res, "telemetry", None)),
            "inst": inst, "jobs": jobs, "machines": m, "lb": LB,
            "chunk": CHUNK, "budget_s": BUDGET_S, "ub_mode": UB_MODE,
            "done": done, "elapsed_s": round(spent, 2),
            "tree": int(res.explored_tree), "sol": int(res.explored_sol),
            "best": int(res.best), "evals": evals, "iters": iters,
            "capacity": int(rec.request.capacity or 0),
            "grows": 0, "pool_at_stop": pool,
            "pushed_per_s": round(res.explored_tree / max(spent, 1e-9), 1),
            "evals_per_s": round(evals / max(spent, 1e-9), 1),
            "restarts": rec.dispatches - 1,
            # where this row's lifecycle (dispatches, preemptions,
            # checkpoints, retries) is flight-recorded
            "trace_file": trace_file,
            "request_id": rec.id}


# ----------------------------------------------------------- entry point

def legacy_main(insts: list[int]) -> None:
    for inst in select_instances(insts):
        print(f"ta{inst:03d} lb{LB}: solving (budget {BUDGET_S:.0f}s)...",
              flush=True)
        row = supervise(inst, LB)
        if row is None:
            continue
        append_row(row)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="Solve Taillard instances to a per-instance compute "
                    "budget, writing JSONL result rows. Default mode "
                    "runs ONE in-process search service "
                    "(tpu_tree_search/service/) and submits every "
                    "instance to it — no per-instance process/compile.",
        epilog="Env knobs: TTS_BUDGET_S TTS_LB TTS_CHUNK "
               "TTS_CAMPAIGN_OUT TTS_WORKDIR TTS_SEG TTS_CKPT_EVERY "
               "TTS_UB TTS_SUBMESHES (see the module docstring).")
    ap.add_argument("instances", nargs="+", type=int,
                    help="Taillard instance ids (e.g. 31 32 ... 50)")
    ap.add_argument("--no-serve", action="store_true",
                    help="DEPRECATED: use the legacy process-per-"
                         "instance supervisor (worker subprocess + "
                         "heartbeat stall kill/respawn) instead of the "
                         "search service. Kept for one release for "
                         "runtimes where a hung device dispatch must be "
                         "killed at the process level; it will be "
                         "removed — migrate to the default serve mode.")
    ap.add_argument("--submeshes", type=int,
                    default=_cfg.env_int("TTS_SUBMESHES"),
                    help="serve mode: partition the device mesh into "
                         "this many equal submeshes and solve that many "
                         "instances concurrently (default 1)")
    args = ap.parse_args(argv)
    if args.no_serve:
        print("warning: --no-serve (process-per-instance supervisor) is "
              "deprecated and will be removed after one release; the "
              "service path is the default", flush=True)
        legacy_main(args.instances)
    else:
        serve_main(args.instances, args.submeshes)


if __name__ == "__main__":
    # worker dispatch is positional-flag tolerant ("--no-serve --worker
    # 3" and "--worker 3" both reach worker_main): the supervisor
    # respawns workers with the flags it was launched with
    if "--worker" in sys.argv[1:]:
        worker_main(int(sys.argv[sys.argv.index("--worker") + 1]))
    else:
        main()
