"""Command-line interface.

Mirrors the reference's flag vocabulary (reference: PFSP_lib.c:173-320 for
PFSP, nqueens_multigpu_cuda.cu:25-89 for N-Queens) and its settings/results
report format (PFSP_lib.c:133-170), so reference users can re-run their
command lines against the TPU engine:

    python -m tpu_tree_search pfsp -i 14 -l 1 -u 1 -D 1
    python -m tpu_tree_search nqueens -N 13 -g 1

Beyond the reference's one-shot runs, `serve` starts the long-lived
search service (tpu_tree_search/service/) over a file spool and
`client` submits requests to it:

    python -m tpu_tree_search serve --spool /tmp/tts-spool --submeshes 2
    python -m tpu_tree_search client --spool /tmp/tts-spool -i 21 -l 1
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from .utils.config import NQueensConfig, PFSPConfig


def _pfsp_parser(sub):
    p = sub.add_parser("pfsp", help="Taillard PFSP B&B")
    d = PFSPConfig()
    p.add_argument("-i", "--inst", type=int, default=d.inst)
    p.add_argument("-l", "--lb", type=int, default=d.lb, choices=(0, 1, 2))
    p.add_argument("-u", "--ub", type=int, default=d.ub, choices=(0, 1))
    p.add_argument("-m", type=int, default=d.m)
    p.add_argument("-M", type=int, default=d.M)
    p.add_argument("-T", type=int, default=d.T,
                   help="reference CPU bulk-pop size; accepted for "
                        "command-line and CSV-schema compatibility but "
                        "inert here, like -p (the host tier's native DFS "
                        "pops per node; PFSP_lib.c:175-185)")
    p.add_argument("-D", type=int, default=d.D)
    p.add_argument("-C", type=int, default=d.C)
    p.add_argument("--host-fraction", type=int, default=None,
                   help="with -C 1: seed the native host tier with every "
                        "k-th warm-up node (default 8; 0 disables the "
                        "concurrent tier)")
    p.add_argument("--host-threads", type=int, default=None,
                   help="with -C 1: native host worker threads "
                        "(default: host cores / device count, the "
                        "reference's num_procs/deviceCount rule, "
                        "pfsp_multigpu_cuda.c:61-69)")
    p.add_argument("-w", "--ws", type=int, default=d.ws)
    p.add_argument("-L", type=int, default=d.L)
    p.add_argument("-p", "--perc", type=float, default=d.perc)
    p.add_argument("--chunk", type=int, default=d.chunk)
    p.add_argument("--capacity", type=int, default=None,
                   help=f"pool rows (default: sized by instance class, "
                        f"at least {d.capacity}; weak-bound classes "
                        "like 50x5 pre-size large — device."
                        "default_capacity)")
    p.add_argument("--balance-period", type=int, default=d.balance_period)
    p.add_argument("--csv", type=str, default=None)
    p.add_argument("--max-iters", type=int, default=None,
                   help="truncate the search (debugging)")
    p.add_argument("--segment-iters", type=int, default=None,
                   help="run in bounded segments with heartbeat reports "
                        "(enables checkpointing; any -D)")
    p.add_argument("--checkpoint", type=str, default=None,
                   help="checkpoint path; if the file exists the search "
                        "resumes from it")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="write the checkpoint every N segments (the "
                        "compressed pool snapshot costs seconds at "
                        "production sizes; amortize it on long runs)")
    p.add_argument("--grow-capacity", type=int, default=None,
                   help="re-home a resumed checkpoint into a larger pool "
                        "(recovery after an overflow abort)")
    from .utils import config as _cfg
    p.add_argument("--retry-attempts", type=int, default=None,
                   help="transient-error retries per segment operation "
                        f"(default {_cfg.RETRY_ATTEMPTS_DEFAULT}; "
                        "exponential backoff base "
                        f"{_cfg.RETRY_BASE_S_DEFAULT}s — also via "
                        "TTS_RETRY_ATTEMPTS / TTS_RETRY_BASE_S)")
    p.add_argument("--segment-timeout", type=float, default=None,
                   help="per-segment wall-clock watchdog in seconds "
                        "(0/default: off; a hung device dispatch raises "
                        "instead of waiting forever — also via "
                        "TTS_SEG_TIMEOUT_S)")
    p.add_argument("--faults", type=str, default=None,
                   help="deterministic fault-injection spec for "
                        "resilience drills, e.g. "
                        "'kill_after_segment=3,fail_host_fetch=1' "
                        "(utils/faults.py; also via TTS_FAULTS)")
    p.add_argument("--search-telemetry", action="store_true",
                   help="compile the on-device search-telemetry block "
                        "into the loop (engine/telemetry.py: depth-"
                        "bucketed pruning counts, bound histograms, "
                        "pool high-water, steal flow, incumbent ring; "
                        "also via TTS_SEARCH_TELEMETRY=1). Node counts "
                        "stay bit-identical; segmented runs emit per-"
                        "segment search.telemetry trace events "
                        "(tools/search_report.py renders them)")


def _serve_parser(sub):
    from .utils import config as _cfg
    p = sub.add_parser(
        "serve",
        help="run the in-process search service over a file spool "
             "(service/: submesh scheduling, priority preemption, "
             "executable reuse)")
    p.add_argument("--spool", type=str, required=True,
                   help="directory watched for <id>.req.json request "
                        "files; results land beside them as "
                        "<id>.res.json (see service/spool.py for the "
                        "payload schema)")
    p.add_argument("--submeshes", type=int,
                   default=_cfg.env_int("TTS_SUBMESHES"),
                   help="partition the device mesh into this many equal "
                        "submeshes, one concurrent request each "
                        "(must divide the device count; TTS_SUBMESHES "
                        "sets the default — the campaign respawn "
                        "channel)")
    p.add_argument("--workdir", type=str, default=None,
                   help="checkpoint directory for preempted/deadline "
                        "requests (default: a fresh temp dir)")
    p.add_argument("--queue-depth", type=int,
                   default=_cfg.env_int("TTS_QUEUE_DEPTH"),
                   help="admission bound: requests beyond this are "
                        "rejected with a reason, not buffered")
    p.add_argument("--segment-iters", type=int,
                   default=_cfg.SERVICE_SEGMENT_ITERS_DEFAULT,
                   help="segment length between stop-flag checks — the "
                        "preemption/deadline reaction granularity")
    p.add_argument("--idle-exit", type=float, default=None,
                   help="exit after this many seconds with no queued or "
                        "running work (default: serve forever)")
    p.add_argument("--status-every", type=float, default=30.0,
                   help="print a JSON status snapshot every N seconds "
                        "(0 disables)")
    p.add_argument("--http-port", type=int, default=None,
                   help="start the observability HTTP front-end "
                        "(obs/httpd: /healthz /metrics /status /trace) "
                        "on this port (0 = ephemeral, printed at "
                        "startup; default: off)")
    p.add_argument("--http-host", type=str, default="127.0.0.1",
                   help="bind address for --http-port (default "
                        "loopback; 0.0.0.0 exposes it)")
    p.add_argument("--trace-file", type=str, default=None,
                   help="append the flight recorder's span/event log "
                        "to this JSONL file (also via TTS_TRACE_FILE; "
                        "convert with tools/trace_summary.py or the "
                        "/trace endpoint)")
    p.add_argument("--phase-metrics", action="store_true",
                   help="measure per-phase unit costs once per request "
                        "shape and publish live per-worker "
                        "kernel/genchild/balance/idle attribution as "
                        "tts_phase_seconds gauges (adds seconds of "
                        "profiling to each shape's first dispatch)")
    p.add_argument("--search-telemetry", action="store_true",
                   help="compile the on-device search-telemetry block "
                        "into every served loop (also via "
                        "TTS_SEARCH_TELEMETRY=1): per-request pruning "
                        "efficiency on /metrics (tts_search_* gauges), "
                        "search.telemetry trace events, Perfetto "
                        "counter tracks on /trace")
    p.add_argument("--otel-endpoint", type=str, default=None,
                   help="export the session's flight-recorder ring as "
                        "OTLP spans to this OTLP/HTTP traces URL at "
                        "shutdown (obs/otel.py; requires the "
                        "opentelemetry SDK — a clean no-op warning "
                        "when it is not installed)")
    p.add_argument("--otel-interval-s", type=float, default=0.0,
                   help="also flush the flight-recorder ring to "
                        "--otel-endpoint every N seconds while serving "
                        "(seq-watermarked: each flush ships only new "
                        "records, so a crashed server has exported "
                        "everything up to its last interval; <= 0 "
                        "keeps the shutdown-only behavior)")
    p.add_argument("--profile-dir", type=str, default=None,
                   help="artifact root for POST /profile captures "
                        "(obs/profiler; one subdirectory per capture; "
                        "default: <workdir>/profiles)")
    p.add_argument("--resource-sample-s", type=float, default=None,
                   help="device-memory/host-RSS sampler cadence in "
                        "seconds (obs/resource: tts_device_bytes_* "
                        "gauges + Perfetto memory lanes; default "
                        "1.0, also via TTS_RESOURCE_SAMPLE_S; <= 0 "
                        "disables)")
    p.add_argument("--health-interval-s", type=float, default=None,
                   help="health rules-engine evaluation cadence in "
                        "seconds (obs/health: /alerts, /dashboard, "
                        "tts_alerts gauges; default "
                        f"{_cfg.OBS_HEALTH_INTERVAL_S_DEFAULT}, also "
                        "via TTS_HEALTH_INTERVAL_S; <= 0 disables "
                        "the daemon — thresholds via TTS_HEALTH_*)")
    p.add_argument("--overlap", action="store_true",
                   help="pipeline segmented execution (also via "
                        "TTS_OVERLAP=1): the next segment dispatches "
                        "before the previous segment's counters are "
                        "fetched (donated carries) and checkpoint "
                        "serialization moves to a writer thread — "
                        "device-idle gap between segments -> ~0 "
                        "(tts_segment_gap_seconds), bit-identical "
                        "node accounting")
    p.add_argument("--share-incumbent", action="store_true",
                   help="share best-makespan incumbents across "
                        "concurrent same-instance requests (also via "
                        "TTS_SHARE_INCUMBENT=1): each segment boundary "
                        "publishes the submesh's best and folds the "
                        "global best in as the next pruning ceiling "
                        "(monotone-only, audited; "
                        "tts_incumbent_folds_total)")
    p.add_argument("--aot-cache", type=str, default=None,
                   help="disk directory for persisted AOT executables "
                        "(also via TTS_AOT_CACHE): a restarted server "
                        "deserializes previously-compiled loops from "
                        "it (~0.2 s, ledger source=disk) instead of "
                        "re-tracing+compiling; entries are CRC-"
                        "stamped, fingerprinted against the runtime, "
                        "corrupt ones quarantined (service/"
                        "aot_cache.py). Default: off (in-memory "
                        "executor cache only)")
    p.add_argument("--tune-cache", type=str, default=None,
                   help="persistent tuning-cache directory (also via "
                        "TTS_TUNE_CACHE): requests submitted with open "
                        "knobs ({'tuned': true} spool payloads / "
                        "chunk=None) resolve chunk/balance_period from "
                        "probed optima instead of the defaults table "
                        "(tune/: fingerprint-checked, CRC-stamped, "
                        "corrupt entries quarantined). Default: off")
    p.add_argument("--tune", action="store_true",
                   help="with --prewarm: PROBE cold shapes at boot "
                        "(short warmed measurement sweeps, winners "
                        "persisted to --tune-cache; also via "
                        "TTS_TUNE=1). A warm cache replays with zero "
                        "probe executions either way")
    p.add_argument("--ladder", action="store_true",
                   help="chunk-ladder execution (also via "
                        "TTS_LADDER=1): pre-build 2-3 chunk rungs per "
                        "served shape and switch at segment "
                        "boundaries from the pool-occupancy signal, "
                        "so ramp/drain run small-chunk steps "
                        "(engine/ladder.py; off-mode is bit-identical "
                        "to the fixed-chunk driver)")
    p.add_argument("--megabatch", action="store_true",
                   help="request megabatching (also via "
                        "TTS_MEGABATCH=1; engine/megabatch.py): the "
                        "admission queue becomes a batch-former — "
                        "same-shape-class requests stack into ONE "
                        "vmapped compiled loop per submesh (close on "
                        "size TTS_BATCH_MAX or age TTS_BATCH_AGE_S; a "
                        "lone request age-closes onto the solo path). "
                        "Every batched request's counts/optimum/"
                        "telemetry are bit-identical to its solo run; "
                        "default off = the solo scheduler exactly")
    p.add_argument("--batch-max", type=int, default=None,
                   help="megabatch: close a forming batch at this "
                        "many members (also via TTS_BATCH_MAX, "
                        f"default {_cfg.BATCH_MAX_DEFAULT})")
    p.add_argument("--batch-age-s", type=float, default=None,
                   help="megabatch: close a forming batch once its "
                        "oldest member has waited this long (also via "
                        "TTS_BATCH_AGE_S, default "
                        f"{_cfg.BATCH_AGE_S_DEFAULT:g})")
    p.add_argument("--remediate", action="store_true",
                   help="EXECUTE the self-healing policy table (also "
                        "via TTS_REMEDIATE=1; service/remediate.py): "
                        "stall alerts auto-preempt + requeue with the "
                        "offending submesh excluded, failures "
                        "localized to one submesh quarantine it "
                        "(drain, canary-probe, readmit), failures "
                        "following a request across submeshes "
                        "dead-letter it with a full failure_log, "
                        "compile storms pause admission (429), audit "
                        "failures quarantine the bad checkpoint. "
                        "Default: observe-only — the controller logs "
                        "the action it WOULD take and touches nothing")
    p.add_argument("--ledger", type=str, default=None,
                   help="durable request-ledger directory (also via "
                        "TTS_LEDGER; service/ledger.py): every request "
                        "state transition is journaled (fsync'd, "
                        "CRC-stamped JSONL) BEFORE it is acknowledged "
                        "— a POST /submit 200 becomes a durability "
                        "promise — and a restarted server REPLAYS the "
                        "ledger at boot: queued/active requests "
                        "re-admit with budgets/exclusions/failure "
                        "logs intact and resume from their "
                        "checkpoints, terminal results re-serve "
                        "idempotently, quarantines and admission "
                        "pauses are restored. Pairs with a persistent "
                        "--workdir (default with --ledger: "
                        "<ledger>/workdir). Default: off")
    p.add_argument("--fleet-dir", type=str, default=None,
                   help="shared fleet root for high availability (also "
                        "via TTS_FLEET_DIR; service/lease.py + "
                        "failover.py): the server takes an fsync'd, "
                        "CRC-stamped LEASE on its --ledger dir (owner "
                        "id, fencing epoch, TTL TTS_LEASE_TTL_S) and "
                        "renews it from a daemon thread; every ledger "
                        "append and checkpoint save is stamped with "
                        "the epoch, and a FailoverWatcher scans the "
                        "fleet root for peer leases that expired "
                        "without release. Requires --ledger. Default: "
                        "off (single-server PR-12 behavior)")
    p.add_argument("--failover", action="store_true",
                   help="ARM peer-ledger takeover (also via "
                        "TTS_FAILOVER=1): when a peer's lease expires, "
                        "CAS-bump its epoch, adopt its ledger — "
                        "re-admit queued/active requests here with "
                        "budgets/exclusions/spool ids intact, re-serve "
                        "done tags idempotently — and keep its lease "
                        "so the stale owner boots fenced. Default: "
                        "observe-only — peer-down detection and "
                        "journaling only, zero takeovers, behavior "
                        "bit-identical to a fleet-less server")
    p.add_argument("--drain-timeout", type=float, default=None,
                   help="graceful SIGTERM/SIGINT drain budget in "
                        "seconds (also via TTS_DRAIN_TIMEOUT_S, "
                        f"default {_cfg.DRAIN_TIMEOUT_S_DEFAULT:g}): "
                        "stop admission, preempt running requests at "
                        "segment boundaries (checkpointed), drain the "
                        "checkpoint/AOT/ledger writers, exit 0; past "
                        "the budget the process checkpoint-and-aborts "
                        "(nonzero exit — with --ledger the abort is "
                        "itself recoverable)")
    p.add_argument("--prewarm", type=str, nargs="?", const="",
                   default=None, metavar="SPEC",
                   help="boot pre-warm: ready compiled loops BEFORE "
                        "the first request (also via TTS_PREWARM). "
                        "SPEC is comma-separated 'taillard' (the "
                        "standard shape families), 'spool' (shapes in "
                        "the backlog) and/or explicit JxM entries; "
                        "bare --prewarm means 'spool,taillard' "
                        "(backlog shapes first). With "
                        "--aot-cache, a warm dir makes this a burst "
                        "of disk loads and a cold dir pays each "
                        "compile exactly once across lifetimes")


def _problem_instance_args(p, require_inst: bool = False):
    """Shared instance-selection flags for `solve` and `client`: a
    problem name plus ONE instance source — a Taillard id (PFSP only),
    a synthetic --size/--seed, or a raw table from a JSON file."""
    p.add_argument("--problem", type=str, default="pfsp",
                   help="workload plugin (problems/base.py): pfsp | "
                        "nqueens | tsp | knapsack")
    p.add_argument("-i", "--inst", type=int,
                   required=require_inst, default=None,
                   help="Taillard instance id (PFSP only)")
    p.add_argument("--size", type=int, default=None,
                   help="synthetic instance size: jobs (pfsp), board "
                        "n (nqueens), cities (tsp), items (knapsack)")
    p.add_argument("--machines", type=int, default=5,
                   help="machines for a synthetic PFSP --size instance")
    p.add_argument("--seed", type=int, default=0,
                   help="synthetic instance seed")
    p.add_argument("--instance-json", type=str, default=None,
                   help="path to a JSON 2-D instance table (the "
                        "problem's p_times format, problems/base.py)")


def _solve_instance_table(args):
    """Resolve the instance table for `solve`/`client` from the flags
    (--inst > --instance-json > --size synthetic)."""
    import numpy as _np

    if args.inst is not None:
        if args.problem != "pfsp":
            raise SystemExit("--inst (a Taillard id) is PFSP-only; "
                             "use --size or --instance-json")
        from .problems import taillard
        return taillard.processing_times(args.inst)
    if args.instance_json:
        import json as _json
        return _np.asarray(
            _json.load(open(args.instance_json)), _np.int32)
    if args.size is None:
        raise SystemExit("pick an instance: -i (pfsp), --size or "
                         "--instance-json")
    n, seed = args.size, args.seed
    if args.problem == "pfsp":
        from .problems.pfsp import PFSPInstance
        return PFSPInstance.synthetic(jobs=n, machines=args.machines,
                                      seed=seed).p_times
    if args.problem == "nqueens":
        from .problems import nqueens as nq
        return nq.table(n)
    if args.problem == "tsp":
        from .problems.tsp import TSPInstance
        return TSPInstance.synthetic(n, seed).d
    if args.problem == "knapsack":
        from .problems.knapsack import KnapsackInstance
        return KnapsackInstance.synthetic(n, seed).table
    raise SystemExit(f"no synthetic builder for problem "
                     f"{args.problem!r}; use --instance-json")


def _solve_parser(sub):
    p = sub.add_parser(
        "solve",
        help="one-shot solve of ANY registered problem through the "
             "generic plugin engine (single-device or distributed)")
    _problem_instance_args(p)
    p.add_argument("-l", "--lb", type=int, default=None,
                   help="bound kind (default: the problem's default)")
    p.add_argument("-u", "--ub", type=int, default=None,
                   help="seed incumbent value (objective units)")
    p.add_argument("-D", type=int, default=1,
                   help="devices (1 = single-device engine)")
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--capacity", type=int, default=None)
    p.add_argument("--max-iters", type=int, default=None,
                   help="truncate the search (debugging)")


def run_solve(args) -> int:
    import json

    from . import problems
    from .engine import device, distributed

    try:
        prob = problems.get(args.problem)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    table = _solve_instance_table(args)
    reason = prob.validate(table)
    if reason is not None:
        print(f"error: invalid instance: {reason}", file=sys.stderr)
        return 2
    lb = prob.default_lb if args.lb is None else args.lb
    # --ub is in OBJECTIVE units; the engine's incumbent lives in the
    # minimized domain (knapsack: -value)
    init_ub = (None if args.ub is None
               else prob.engine_objective(args.ub))
    print("=" * 49)
    print(f"TPU B&B problem={prob.name} shape="
          f"{'x'.join(map(str, table.shape))} lb={lb} D={args.D}")
    print("=" * 49)
    t0 = time.perf_counter()
    if args.D == 1:
        out = device.solve(prob, table, lb_kind=lb, init_ub=init_ub,
                           chunk=args.chunk, capacity=args.capacity,
                           max_iters=args.max_iters)
        tree, sol, best = out.explored_tree, out.explored_sol, out.best
        complete = out.complete
    else:
        res = distributed.search(
            table, problem=prob, lb_kind=lb, init_ub=init_ub,
            n_devices=args.D, chunk=args.chunk,
            capacity=args.capacity or prob.default_capacity(table),
            max_rounds=args.max_iters)
        tree, sol, best = (res.explored_tree, res.explored_sol,
                           res.best)
        complete = res.complete
    elapsed = time.perf_counter() - t0
    print(json.dumps({
        "problem": prob.name, "explored_tree": tree,
        "explored_sol": sol, "best": int(best),
        "objective": prob.display_objective(best),
        "complete": bool(complete), "elapsed_s": round(elapsed, 4)}))
    return 0


def _client_parser(sub):
    p = sub.add_parser(
        "client",
        help="submit one request to a running `serve` spool and wait")
    p.add_argument("--spool", type=str, required=True)
    _problem_instance_args(p)
    p.add_argument("-l", "--lb", type=int, default=None,
                   help="bound kind (default: the problem's default)")
    p.add_argument("-u", "--ub", type=int, default=1, choices=(0, 1),
                   help="1: seed the incumbent with the known optimum "
                        "(applies to Taillard -i instances only)")
    p.add_argument("--priority", type=int, default=0,
                   help="higher preempts lower on a full mesh")
    p.add_argument("--deadline", type=float, default=None,
                   help="compute budget in seconds (accumulated "
                        "execution time, not queue wait)")
    p.add_argument("--chunk", type=int, default=None)
    p.add_argument("--capacity", type=int, default=None)
    p.add_argument("--tag", type=str, default=None,
                   help="checkpoint tag; resubmitting a DEADLINE "
                        "request's tag with a larger budget extends it")
    p.add_argument("--portfolio", type=int, default=None, metavar="K",
                   help="bound-portfolio racing: fan out as K sibling "
                        "configs (bound tiers, tuned chunk plans) "
                        "sharing one incumbent board; first proof "
                        "wins, losers cancel (service/portfolio.py)")
    p.add_argument("--timeout", type=float, default=None,
                   help="give up waiting for the result after N seconds")


# exit code of the drain-timeout escalation (checkpoint-and-abort):
# distinct from clean drains (0), tracebacks (1) and the injected hard
# kill (137) so a supervisor's restart policy can tell them apart
DRAIN_ESCALATE_EXIT_CODE = 70


def _install_drain_handlers(drain_evt, timeout_s: float):
    """SIGTERM/SIGINT -> graceful drain: set `drain_evt` (the serve
    loop exits, the server close() preempts at segment boundaries and
    drains every writer) and arm the escalation watchdog — a drain
    that cannot finish inside `timeout_s` checkpoint-and-aborts
    instead of hanging the pod's termination grace period. A second
    signal escalates immediately. Returns False when handlers cannot
    be installed (not the main thread — in-process tests)."""
    import os as _os
    import signal
    import threading

    def _escalate():
        from .obs import tracelog
        tracelog.event("server.drain_escalated", timeout_s=timeout_s)
        print(f"drain exceeded {timeout_s:g}s: checkpoint-and-abort",
              flush=True)
        _os._exit(DRAIN_ESCALATE_EXIT_CODE)

    def _handler(signum, frame):
        if drain_evt.is_set():
            _os._exit(DRAIN_ESCALATE_EXIT_CODE)
        print(f"signal {signum}: draining (budget {timeout_s:g}s)",
              flush=True)
        drain_evt.set()
        t = threading.Timer(timeout_s, _escalate)
        t.daemon = True
        t.start()
        drain_evt.watchdog = t

    try:
        signal.signal(signal.SIGTERM, _handler)
        signal.signal(signal.SIGINT, _handler)
    except ValueError:      # not the main thread
        return False
    return True


def run_serve(args) -> int:
    import threading

    from .obs import tracelog
    from .service import SearchServer, spool
    from .utils import config as _cfg

    if args.search_telemetry:
        # static compile-in flag, read at each request's state init
        _cfg.set_env("TTS_SEARCH_TELEMETRY", "1")
    if args.overlap:
        # env too, not just the server knob: campaign-style respawns
        # and in-process tools must see the same static flag
        _cfg.set_env("TTS_OVERLAP", "1")
    if args.share_incumbent:
        _cfg.set_env("TTS_SHARE_INCUMBENT", "1")
    if args.ladder:
        # static flag: every engine entry (serve dispatches, prewarm's
        # rung warms, in-process tools) must see the same ladder mode
        _cfg.set_env(_cfg.LADDER_FLAG, "1")
    if args.remediate:
        _cfg.set_env(_cfg.REMEDIATE_FLAG, "1")
    if args.megabatch:
        _cfg.set_env(_cfg.MEGABATCH_FLAG, "1")
    if args.fleet_dir:
        # env too: worker respawns and the lease/watcher layers all
        # resolve TTS_FLEET_DIR at one site (the server constructor)
        _cfg.set_env(_cfg.FLEET_DIR_ENV, args.fleet_dir)
    if args.failover:
        _cfg.set_env(_cfg.FAILOVER_FLAG, "1")
    if args.trace_file:
        tracelog.get().set_sink(args.trace_file)
        print(f"flight recorder: {args.trace_file}", flush=True)
    # --ledger passes straight through: SearchServer resolves the
    # TTS_LEDGER env fallback itself (one resolution site) and, with a
    # ledger and no explicit --workdir, defaults the workdir to
    # <ledger>/workdir — checkpoints must survive the restart the
    # ledger exists for
    drain_evt = threading.Event()
    drain_timeout = (args.drain_timeout if args.drain_timeout is not None
                     else _cfg.env_float("TTS_DRAIN_TIMEOUT_S"))
    _install_drain_handlers(drain_evt, drain_timeout)
    httpd = None
    otel_exp = None
    otel_stop = None
    if args.otel_endpoint:
        from .obs import otel
        # ONE exporter for interval flushes AND the shutdown flush: its
        # seq watermark is what keeps a record from shipping twice
        otel_exp = otel.IncrementalExporter(endpoint=args.otel_endpoint)
        if args.otel_interval_s and args.otel_interval_s > 0:
            otel_stop = threading.Event()

            def _otel_tick():
                while not otel_stop.wait(args.otel_interval_s):
                    try:
                        otel_exp.flush(tracelog.get().records())
                    except Exception:  # noqa: BLE001 — a flaky
                        # collector must not kill the flusher; the next
                        # tick (same watermark) retries the same tail
                        pass
            threading.Thread(target=_otel_tick, name="otel-flush",
                             daemon=True).start()
            print(f"otel: flushing to {args.otel_endpoint} every "
                  f"{args.otel_interval_s:g}s", flush=True)
    try:
        with SearchServer(n_submeshes=args.submeshes,
                          workdir=args.workdir,
                          max_queue_depth=args.queue_depth,
                          segment_iters=args.segment_iters,
                          phase_profile=(True if args.phase_metrics
                                         else None),
                          resource_sample_s=args.resource_sample_s,
                          health_interval_s=args.health_interval_s,
                          overlap=(True if args.overlap else None),
                          share_incumbent=(True if args.share_incumbent
                                           else None),
                          aot_cache_dir=args.aot_cache,
                          tune_cache_dir=args.tune_cache,
                          tune_at_boot=(True if args.tune else None),
                          remediate=(True if args.remediate else None),
                          ledger_dir=args.ledger,
                          megabatch=(True if args.megabatch else None),
                          batch_max=args.batch_max,
                          batch_age_s=args.batch_age_s
                          ) as srv:
            if srv.megabatch:
                print(f"megabatch: ON (max {srv.former.max_size}, "
                      f"age {srv.former.age_s:g}s)", flush=True)
            print(f"remediation: "
                  f"{'ACT' if srv.remediation.enabled else 'observe'}"
                  f"-mode (TTS_REMEDIATE)", flush=True)
            if srv.ledger is not None:
                led = srv.ledger.snapshot()
                rec = srv._recovered
                print(f"ledger: {led['dir']} (restart "
                      f"#{led['restarts']}, replayed "
                      f"{led['replayed']} record(s), recovered "
                      f"{rec['queued']}q/{rec['active']}a/"
                      f"{rec['held']}h/{rec['terminal']}t, "
                      f"truncated {led['truncated']})", flush=True)
            if srv.lease is not None or srv.fenced:
                mode = ("FENCED" if srv.fenced else
                        ("ACT" if srv.watcher is not None
                         and srv.watcher.act else "observe"))
                epoch = srv.lease.epoch if srv.lease is not None else "-"
                print(f"failover: {mode}-mode, lease epoch {epoch}, "
                      f"ttl {_cfg.env_float('TTS_LEASE_TTL_S'):g}s "
                      f"(TTS_FLEET_DIR/TTS_FAILOVER)", flush=True)
            if srv.aot is not None:
                print(f"aot cache: {srv.aot.root} "
                      f"({srv.aot.entries()} entr(y/ies))", flush=True)
            if srv.tuner is not None and srv.tuner.cache is not None:
                print(f"tune cache: {srv.tuner.cache.root} "
                      f"({srv.tuner.cache.entries()} entr(y/ies), "
                      f"probe-at-boot={srv.tune_at_boot})", flush=True)
            if args.http_port is not None:
                # BEFORE pre-warm: a cold-dir warm of the full shape
                # family list is minutes of compiles at production
                # shapes, and a readiness probe (or the doctor) that
                # cannot reach /healthz during it would restart the
                # server into the same warm — the crash-loop the
                # feature exists to prevent
                from .obs.httpd import start_http_server
                httpd = start_http_server(srv, host=args.http_host,
                                          port=args.http_port,
                                          profile_dir=args.profile_dir)
                print(f"observability: {httpd.url}/healthz /metrics "
                      "/status /trace /alerts /dashboard; "
                      "POST /submit /cancel /profile?duration_s=N",
                      flush=True)
            env_spec = _cfg.env_str(_cfg.PREWARM_ENV)
            prewarm_spec = (args.prewarm if args.prewarm is not None
                            else env_spec)
            if env_spec is not None and env_spec.strip().lower() in (
                    "0", "off", "no"):
                # the env kill-switch wins even over the CLI flag: an
                # operator must be able to disable a unit file's
                # --prewarm during an incident without editing it
                prewarm_spec = None
            if prewarm_spec is not None \
                    and prewarm_spec.strip().lower() not in ("0", "off",
                                                             "no"):
                try:
                    summary = srv.prewarm_boot(prewarm_spec,
                                               spool_dir=args.spool)
                except Exception as e:  # noqa: BLE001 — pre-warm is
                    # an optimization: a typo'd TTS_PREWARM spec in a
                    # fleet unit file must degrade to a cold boot, not
                    # crash-loop every server (the first request pays
                    # its compile as before)
                    print(f"prewarm SKIPPED: {e}", flush=True)
                else:
                    print(f"prewarm: {summary['warms']} "
                          f"executable(s) for "
                          f"{summary['shapes']} shape(s) in "
                          f"{summary['seconds']}s "
                          f"(disk={summary['by']['disk']} "
                          f"compile={summary['by']['compile']} "
                          f"warm={summary['by']['warm']} "
                          f"skipped={summary['by']['skipped']} "
                          f"errors={summary['errors']})", flush=True)
            print(f"serving: {args.submeshes} submesh(es) x "
                  f"{srv.slots[0].mesh.devices.size} device(s), "
                  f"spool {args.spool}", flush=True)
            served = spool.serve_spool(
                srv, args.spool, idle_exit_s=args.idle_exit,
                status_every_s=args.status_every or None,
                emit=lambda s: print(s, flush=True),
                # a FENCED server (lease lost to an adopter) must stop
                # serving the spool too: its requests now live on the
                # peer, and a fenced loop polling forever would shadow
                # the adopter's results
                should_exit=lambda: drain_evt.is_set() or srv.fenced)
            # the `with` close() below IS the drain: stop at segment
            # boundaries, checkpoint, flush the async checkpoint/AOT/
            # ledger writers — the watchdog escalates if it wedges
    finally:
        if httpd is not None:
            httpd.close()
        if otel_stop is not None:
            otel_stop.set()
        if otel_exp is not None:
            # same instance as the interval flusher: only the tail past
            # its watermark ships, never a duplicate of a prior flush
            n = otel_exp.flush(tracelog.get().records())
            print(f"otel: exported {n} span(s) at shutdown "
                  f"({otel_exp.spans} total) to "
                  f"{args.otel_endpoint}", flush=True)
    watchdog = getattr(drain_evt, "watchdog", None)
    if watchdog is not None:
        watchdog.cancel()       # drained inside the budget: exit 0
    if drain_evt.is_set():
        print("drained cleanly", flush=True)
    if srv.fenced:
        # clean exit 0 ON PURPOSE: a fenced server did the right thing
        # (zero commits past the fence) — a nonzero exit would make a
        # supervisor restart-loop a host whose ledger now lives on a
        # peer
        print(f"fenced: {srv._fence_reason or 'lease lost'} — a peer "
              "owns this ledger now; exited without commits",
              flush=True)
    print(f"served {served} request(s)", flush=True)
    return 0


def run_client(args) -> int:
    import json

    from .service import spool

    payload = {"problem": args.problem,
               "priority": args.priority, "deadline_s": args.deadline,
               "chunk": args.chunk, "capacity": args.capacity,
               "tag": args.tag}
    if args.lb is not None:
        payload["lb"] = args.lb
    if args.portfolio is not None:
        payload["portfolio"] = args.portfolio
    if args.problem == "pfsp" and args.inst is not None:
        payload["inst"] = args.inst
        payload["ub"] = "opt" if args.ub == 1 else None
    else:
        payload["p_times"] = _solve_instance_table(args).tolist()
    sid = spool.submit_file(args.spool, payload)
    print(f"submitted {sid}", flush=True)
    try:
        res = spool.wait_result(args.spool, sid, timeout=args.timeout)
    except TimeoutError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    print(json.dumps(res, indent=1))
    return 0 if res.get("state") == "DONE" else 1


def _profile_parser(sub):
    p = sub.add_parser(
        "profile",
        help="standalone capture-on-demand: warm the single-device "
             "engine past its ramp, capture an XLA profiler trace of "
             "a steady-state window (obs/profiler — same session as "
             "POST /profile), and print the self-time attribution")
    p.add_argument("-i", "--inst", type=int, default=21,
                   help="Taillard instance id")
    p.add_argument("-l", "--lb", type=int, default=1, choices=(0, 1, 2))
    p.add_argument("--chunk", type=int, default=256)
    p.add_argument("--capacity", type=int, default=1 << 18)
    p.add_argument("--warm", type=int, default=50,
                   help="warm-up iterations before the traced window")
    p.add_argument("--iters", type=int, default=20,
                   help="traced-window iterations")
    p.add_argument("--out", type=str, default=None,
                   help="artifact root (default: a fresh temp dir); "
                        "each capture gets its own subdirectory")
    p.add_argument("--top", type=int, default=15,
                   help="ops to list in the self-time table")


def run_profile(args) -> int:
    import json
    import tempfile

    from .engine import device
    from .obs import chrome_trace, profiler
    from .ops import batched
    from .problems import taillard

    p = taillard.processing_times(args.inst)
    ub = taillard.optimal_makespan(args.inst)
    tables = batched.make_tables(p)
    state = device.init_state(p.shape[1], args.capacity, ub, p_times=p)
    state = device.run(tables, state, args.lb, args.chunk,
                       max_iters=args.warm)
    state.size.block_until_ready()
    print(f"# warmed: iters={int(state.iters)} pool={int(state.size)}",
          file=sys.stderr)

    sess = profiler.session()
    root = args.out or tempfile.mkdtemp(prefix="tts_profile_")
    log_dir = sess.fresh_dir(root)
    with sess.trace(log_dir):
        out = device.run(tables, state, args.lb, args.chunk,
                         max_iters=args.warm + args.iters)
        out.size.block_until_ready()

    self_us, counts = chrome_trace.self_times(
        chrome_trace.load_xla_trace(log_dir))
    total = sum(self_us.values())
    buckets = chrome_trace.bucketed_self_times(self_us)
    print(json.dumps({
        "artifact": log_dir, "inst": args.inst, "lb": args.lb,
        "iters": int(out.iters) - int(state.iters),
        "evals": int(out.evals) - int(state.evals),
        "device_self_ms": round(total / 1e3, 2),
        "buckets_ms": {k: round(v / 1e3, 2)
                       for k, v in buckets.most_common()},
    }))
    print("\n# top ops by device self-time "
          "(tools/search_report.py renders the same table):")
    for name, d in self_us.most_common(args.top):
        print(f"{d / 1e3:10.2f} ms  x{counts[name]:<6} "
              f"[{chrome_trace.bucket_of(name):>15}]  {name[:90]}")
    print(f"\n# artifact: {log_dir}")
    return 0


def _doctor_parser(sub):
    p = sub.add_parser(
        "doctor",
        help="one-shot fleet health verdict: scrape N servers' "
             "/healthz /status /metrics /alerts (obs/aggregate), "
             "print the judgment, exit nonzero on any unreachable "
             "server or firing alert")
    p.add_argument("urls", nargs="+", metavar="URL",
                   help="server base URLs (http://host:port)")
    p.add_argument("--json", action="store_true",
                   help="print the merged fleet view as JSON instead "
                        "of the human table")
    p.add_argument("--dashboard", type=str, default=None,
                   help="also render the fleet dashboard HTML here "
                        "(obs/dashboard; self-contained, no external "
                        "assets — CI uploads it as an artifact)")
    p.add_argument("--metrics-out", type=str, default=None,
                   help="also write the merged, origin-labeled "
                        "Prometheus exposition here (one aggregated "
                        "scrape target for the fleet)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="per-endpoint scrape timeout in seconds")
    p.add_argument("--fleet-dir", type=str, default=None,
                   help="shared fleet root (TTS_FLEET_DIR): also read "
                        "every peer's LEASE file straight off storage, "
                        "so a DOWN server splits DOWN-with-lease-held "
                        "(exit 1: wait out the TTL) from "
                        "DOWN-lease-expired (exit 2: requests "
                        "orphaned, takeover needed)")


# doctor exit codes: 0 healthy; 1 unhealthy (unreachable/firing/
# degraded — or DOWN-with-lease-held: wait out the TTL); 2 an expired
# unreleased lease sits in --fleet-dir (orphaned ledger: page/arm
# takeover NOW). Distinct codes so a supervisor can wait on 1 and act
# on 2.
DOCTOR_TAKEOVER_EXIT_CODE = 2


def run_doctor(args) -> int:
    import json

    from .obs import aggregate, dashboard

    fleet = aggregate.scrape(args.urls, timeout=args.timeout)
    merged = aggregate.merge(fleet)
    lease_report = (aggregate.fleet_lease_report(args.fleet_dir)
                    if args.fleet_dir else None)
    healthy, reasons = aggregate.verdict(merged,
                                         lease_report=lease_report)
    if args.dashboard:
        with open(args.dashboard, "w") as f:
            f.write(dashboard.render_fleet(merged))
        print(f"# wrote {args.dashboard}", file=sys.stderr)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(aggregate.fleet_to_prometheus(merged))
        print(f"# wrote {args.metrics_out}", file=sys.stderr)
    if args.json:
        print(json.dumps({"healthy": healthy, "reasons": reasons,
                          **({"leases": lease_report}
                             if lease_report is not None else {}),
                          **{k: v for k, v in merged.items()
                             if k != "metrics"}}, indent=1))
    else:
        for s in merged["servers"]:
            degraded = bool(s.get("quarantined"))
            mark = ("ok" if s["ok"] and s["healthz"] == "ok"
                    and not s.get("firing") and not degraded
                    else ("DEGRADED" if degraded and s["ok"]
                          and s["healthz"] == "ok"
                          and not s.get("firing") else "UNHEALTHY"))
            aot = s.get("aot_cache")
            aot_col = (f" aot={aot['hits']}h/{aot['misses']}m"
                       f"/{aot['entries']}e" if aot else "")
            paused = s.get("admission_paused")
            rem_col = (f" quarantined={s.get('quarantined')}"
                       if s.get("quarantined") else "") + (
                       f" PAUSED({paused})" if paused else "")
            led_col = ""
            if s.get("restarts") is not None:
                led_col = (f" restarts={s.get('restarts')}"
                           f" recovered={s.get('recovered_requests')}"
                           f" ledger_lag_s={s.get('ledger_lag_s')}")
            pf = s.get("portfolio")
            pf_col = (f" portfolio={pf['active']}a/{pf['won']}w"
                      f"/{pf['cancelled_members']}cxl" if pf else "")
            # the predictive columns (obs/estimate): absent while no
            # request publishes an estimate (warmup / TTS_PROGRESS=0)
            eta_col = ""
            if s.get("progress_mean") is not None:
                eta_col = f" progress={s['progress_mean'] * 100:.1f}%"
            if s.get("eta_max_s") is not None:
                eta_col += f" eta_s={s['eta_max_s']:g}"
            # the capacity columns (obs/capacity): absent with
            # TTS_CAPACITY=0 or before a service-time estimate exists
            cap_col = ""
            if s.get("utilization") is not None:
                cap_col = (f" rho={s['utilization']:.2f}"
                           f" headroom={s['capacity_headroom']:.2f}")
            fo_col = ""
            if s.get("failover_mode") is not None or s.get("fenced"):
                fo_col = (f" failover={s.get('failover_mode')}"
                          f" epoch={s.get('lease_epoch')}"
                          f" peers_down={s.get('peers_down')}"
                          f" takeovers={s.get('takeovers')}") + (
                          " FENCED" if s.get("fenced") else "")
            print(f"{s['origin']:<24} {mark:<10} "
                  f"firing={s.get('firing')} "
                  f"queue={s.get('queue_depth')} "
                  f"busy={s.get('submeshes_busy')}/{s.get('submeshes')} "
                  f"requests={s.get('requests')}{eta_col}{cap_col}"
                  f"{aot_col}{rem_col}{pf_col}{led_col}{fo_col}")
        for r in lease_report or []:
            state = ("released" if r["released"] else
                     "EXPIRED" if r["expired"] else "live")
            print(f"lease {r['dir']}: {state} owner={r['owner']} "
                  f"epoch={r['epoch']} age={r['age_s']:g}s"
                  f"/ttl={r['ttl_s']:g}s")
        print("healthy" if healthy else
              "UNHEALTHY:\n  " + "\n  ".join(reasons))
    if healthy:
        return 0
    if lease_report and aggregate.needs_takeover(lease_report):
        return DOCTOR_TAKEOVER_EXIT_CODE
    return 1


def _capacity_parser(sub):
    p = sub.add_parser(
        "capacity",
        help="fleet capacity & utilization report (obs/capacity): "
             "scrape N servers' GET /capacity and print per-lane "
             "state/utilization, per-shape-class demand vs capacity "
             "(ρ, headroom, predicted queue wait) and the what-if "
             "submesh-partition advisor")
    p.add_argument("urls", nargs="+", metavar="URL",
                   help="server base URLs (http://host:port)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable documents instead of the "
                        "human tables")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="per-endpoint scrape timeout in seconds")


def run_capacity(args) -> int:
    import json

    from .obs import aggregate

    docs, rc = [], 0
    for url in args.urls:
        base = url.rstrip("/")
        origin = base.split("://", 1)[-1]
        try:
            _, body = aggregate._get(base + "/capacity", args.timeout)
            docs.append({"origin": origin, **json.loads(body)})
        except (OSError, ValueError) as e:
            docs.append({"origin": origin, "error": str(e)})
            rc = 1
    if args.json:
        print(json.dumps(docs, indent=1))
        return rc
    for doc in docs:
        if doc.get("error"):
            print(f"{doc['origin']}: UNREACHABLE ({doc['error']})")
            continue
        if not doc.get("enabled"):
            print(f"{doc['origin']}: capacity layer off "
                  "(TTS_CAPACITY=0)")
            continue
        rho = doc.get("utilization")
        print(f"{doc['origin']}: lanes={doc.get('healthy_lanes')}"
              f"/{doc.get('lanes')} devices={doc.get('devices')} "
              f"arrivals={doc.get('arrival_per_s', 0):.3f}/s "
              + (f"rho={rho:.2f} headroom={doc.get('headroom'):.2f}"
                 if rho is not None else "rho=— (no service estimate)")
              + (f" pred_wait_s={doc['predicted_wait_s']:.3f}"
                 if doc.get("predicted_wait_s") is not None else "")
              + (f" pred_req_per_s={doc['predicted_req_per_s']:.3f}"
                 if doc.get("predicted_req_per_s") is not None else ""))
        for ln in doc.get("lanes_detail") or []:
            secs = ln.get("seconds") or {}
            top = ", ".join(f"{k}={secs[k]:.1f}s" for k in sorted(
                secs, key=lambda k: -secs[k])[:3])
            print(f"  lane {ln.get('lane')}: {ln.get('state'):<13} "
                  f"exec={ln.get('utilization', 0) * 100:5.1f}%  "
                  f"[{top}]  conservation_err="
                  f"{ln.get('conservation_error_s'):.2e}s")
        for c in doc.get("classes") or []:
            srv_s = c.get("service_s")
            print(f"  class {c.get('shape')} tenant={c.get('tenant')}: "
                  f"lambda={c.get('arrival_per_s', 0):.3f}/s "
                  + (f"E[S]={srv_s:.3f}s rho={c.get('utilization'):.2f}"
                     if srv_s is not None else "E[S]=— (warming up)"))
        wi = doc.get("what_if") or []
        if wi:
            print("  what-if (same devices, n equal lanes):")
            for row in wi:
                cur = "  <- current" if row.get("current") else ""
                wait = row.get("predicted_wait_s")
                print(f"    {row['lanes']} lane(s) x "
                      f"{row['devices_per_lane']} dev: "
                      f"req/s={row['predicted_req_per_s']:.3f} "
                      f"rho={row['utilization']:.2f} "
                      + (f"wait_s={wait:.3f}" if wait is not None
                         else "wait_s=inf (saturated)") + cur)
    return rc


def _journey_parser(sub):
    p = sub.add_parser(
        "journey",
        help="reconstruct request journeys from durable state "
             "(obs/journey): one stitched cross-lifetime timeline per "
             "logical request, chained through ledger admits, "
             "failover takeovers and portfolio fan-outs — reads "
             "ledger/fleet dirs and the flight-recorder store "
             "straight off storage, no server required")
    p.add_argument("--ledger", action="append", default=[],
                   metavar="DIR",
                   help="request-ledger directory (repeatable)")
    p.add_argument("--fleet-dir", type=str, default=None,
                   help="shared fleet root (TTS_FLEET_DIR): read EVERY "
                        "peer ledger under it")
    p.add_argument("--store", type=str, default=None,
                   help="flight-recorder store directory "
                        "(TTS_OBS_STORE): fold its trace events into "
                        "each journey's timeline")
    p.add_argument("--tag", type=str, default=None,
                   help="only journeys whose tag (or any member rid) "
                        "matches")
    p.add_argument("--json", action="store_true",
                   help="machine-readable journeys instead of the "
                        "human report")


def run_journey(args) -> int:
    from .obs import journey as journey_mod

    if not args.ledger and not args.fleet_dir:
        print("journey: need --ledger and/or --fleet-dir",
              file=sys.stderr)
        return 2
    journeys = journey_mod.find_journeys(
        ledger_dirs=args.ledger or None, fleet_dir=args.fleet_dir,
        store=args.store, tag=args.tag)
    if args.json:
        print(journey_mod.to_json(journeys))
    elif not journeys:
        print("no journeys"
              + (f" matching tag {args.tag!r}" if args.tag else ""))
    else:
        for j in journeys:
            print(journey_mod.render_journey(j))
    # tag given but nothing matched: nonzero, so the CI leg's
    # one-journey assertion can't silently pass on an empty answer
    return 0 if journeys or not args.tag else 1


def _nq_parser(sub):
    p = sub.add_parser("nqueens", help="N-Queens backtracking")
    d = NQueensConfig()
    p.add_argument("-N", type=int, default=d.N)
    p.add_argument("-g", type=int, default=d.g)
    p.add_argument("-D", type=int, default=d.D)
    p.add_argument("--chunk", type=int, default=d.chunk)
    p.add_argument("--capacity", type=int, default=d.capacity)


def _print_pfsp_settings(args, machines, jobs, n_dev):
    print("=" * 49)
    print(f"TPU B&B ({n_dev} device(s) - balancing [{int(args.ws or args.L)}])")
    print(f"Resolution of PFSP Taillard's instance: ta{args.inst} "
          f"(m = {machines}, n = {jobs})")
    print("Initial upper bound: " + ("opt" if args.ub == 1 else "inf"))
    print("Lower bound function: " + {0: "lb1_d", 1: "lb1", 2: "lb2"}[args.lb])
    print("Branching rule: fwd")
    print("=" * 49)


def _print_results(optimum, tree, sol, elapsed, complete=True):
    print("=" * 49)
    print(f"Size of the explored tree: {tree}")
    print(f"Number of explored solutions: {sol}")
    label = "Optimal makespan" if complete else "Best makespan found (truncated run)"
    print(f"{label}: {optimum}")
    print(f"Elapsed time: {elapsed:.4f} [s]")
    print("=" * 49)


def run_pfsp(args) -> int:
    import jax

    from .engine import device, distributed
    from .problems import taillard
    from .utils import csv_stats

    p = taillard.processing_times(args.inst)
    jobs, machines = p.shape[1], p.shape[0]
    if args.capacity is None:
        args.capacity = device.default_capacity(jobs, machines)
    init_ub = taillard.optimal_makespan(args.inst) if args.ub == 1 else None
    n_dev = args.D if args.D > 0 else len(jax.devices())
    # resilience knobs travel as env so every run_segmented in the call
    # tree (direct, distributed.search's, a respawned campaign worker's)
    # sees the same policy
    from .utils import config as _cfg
    if getattr(args, "retry_attempts", None) is not None:
        _cfg.set_env("TTS_RETRY_ATTEMPTS", args.retry_attempts)
    if getattr(args, "segment_timeout", None) is not None:
        _cfg.set_env("TTS_SEG_TIMEOUT_S", args.segment_timeout)
    if getattr(args, "search_telemetry", False):
        # env, not a Python knob: init_state reads it at state
        # creation, and respawned campaign workers must inherit it
        _cfg.set_env("TTS_SEARCH_TELEMETRY", "1")
    if getattr(args, "faults", None):
        from .utils import faults
        faults.configure(args.faults)
    # -C composes with EVERY tier: single-device (hybrid.search),
    # single-device segmented (_run_pfsp_segmented's host session),
    # multi-device and the segmented/checkpointed flagship
    # (distributed.search host_fraction) — the reference runs CPU
    # workers beside both its multi-GPU and distributed engines.
    # --host-fraction/--host-threads make the tier a measured knob;
    # threads default to the reference's num_procs/deviceCount rule
    # (pfsp_multigpu_cuda.c:61-69).
    if args.C:
        host_fraction = (8 if args.host_fraction is None
                         else max(args.host_fraction, 0))
        host_threads = (max(1, (os.cpu_count() or 1) // max(n_dev, 1))
                        if args.host_threads is None
                        else max(args.host_threads, 1))
    else:
        host_fraction, host_threads = 0, 0
    _print_pfsp_settings(args, machines, jobs, n_dev)

    t0 = time.perf_counter()
    if args.segment_iters is not None or args.checkpoint is not None:
        if n_dev == 1:
            try:
                out, extras = _run_pfsp_segmented(args, p, init_ub,
                                                  host_fraction,
                                                  host_threads)
            except (RuntimeError, ValueError, OSError) as e:
                print(f"error: {e}", file=sys.stderr)
                return 1
            tree = int(out.tree) + extras["tree"]
            sol = int(out.sol) + extras["sol"]
            best = int(out.best)
            if extras["best"] is not None:
                best = min(best, extras["best"])
            complete = int(np.asarray(out.size).sum()) == 0
            per_device = {"tree": [int(out.tree)], "sol": [int(out.sol)],
                          "evals": [int(out.evals)],
                          "iters": [int(out.iters)],
                          "steals": [0], "recv": [0],
                          **extras["host"]}
        else:
            # distributed durability: segmented SPMD loop with stacked
            # checkpoint/resume and per-worker heartbeat
            def heartbeat(r):
                pw = (f" sizes={r.per_worker['size']}"
                      f" steals={r.per_worker['steals']}"
                      if r.per_worker else "")
                print(f"[segment {r.segment}] iters={r.iters} "
                      f"tree={r.tree} sol={r.sol} best={r.best} "
                      f"pool={r.pool_size}{pw} t={r.elapsed:.2f}s")

            try:
                res = distributed.search(
                    p, lb_kind=args.lb, init_ub=init_ub, n_devices=n_dev,
                    chunk=args.chunk, capacity=args.capacity,
                    balance_period=args.balance_period,
                    # balancing off (-w 0 -L 0): an unreachable transfer
                    # threshold keeps every plan empty (the cond-gated
                    # exchange then costs one all_gather) while the
                    # while-cond — termination, ceiling, segment checks —
                    # still runs every period
                    min_transfer=(None if (args.ws or args.L)
                                  else 2**30),
                    min_seed=args.m, max_rounds=args.max_iters,
                    segment_iters=args.segment_iters,
                    checkpoint_path=args.checkpoint, heartbeat=heartbeat,
                    checkpoint_every=getattr(args, "checkpoint_every", 1),
                    host_fraction=host_fraction,
                    host_threads=host_threads)
            except (RuntimeError, ValueError, OSError) as e:
                print(f"error: {e}", file=sys.stderr)
                return 1
            tree, sol, best = (res.explored_tree, res.explored_sol,
                               res.best)
            complete = res.complete
            per_device = {k: list(v) for k, v in res.per_device.items()}
    elif n_dev == 1 and args.C:
        # heterogeneous co-processing (-C 1): native host warm-up + the
        # compiled device loop while the pool feeds >= m parents (the
        # reference's -m offload threshold) + native multi-threaded drain
        # of the residue (reference: the CPU-worker tier and final drain
        # of pfsp_multigpu_cuda.c)
        from .engine import hybrid

        if args.max_iters is not None:
            print("error: --max-iters is not supported with -C 1",
                  file=sys.stderr)
            return 2
        res = hybrid.search(p, lb_kind=args.lb, init_ub=init_ub,
                            chunk=args.chunk, capacity=args.capacity,
                            drain_min=max(args.m, 1),
                            host_fraction=host_fraction,
                            host_threads=host_threads)
        tree, sol, best = res.explored_tree, res.explored_sol, res.best
        complete = res.complete
        per_device = {k: list(v) for k, v in res.per_device.items()}
    elif n_dev == 1:
        out = device.search(p, lb_kind=args.lb, init_ub=init_ub,
                            chunk=args.chunk, capacity=args.capacity,
                            max_iters=args.max_iters)
        tree, sol, best = out.explored_tree, out.explored_sol, out.best
        complete = out.complete
        per_device = {"tree": [tree], "sol": [sol], "evals": [out.evals],
                      "iters": [out.iters], "steals": [0], "recv": [0]}
    else:
        res = distributed.search(
            p, lb_kind=args.lb, init_ub=init_ub, n_devices=n_dev,
            chunk=args.chunk, capacity=args.capacity,
            balance_period=args.balance_period,
            min_transfer=(None if (args.ws or args.L) else 2**30),
            min_seed=args.m,
            max_rounds=args.max_iters,
            host_fraction=host_fraction,
            host_threads=host_threads)
        tree, sol, best = res.explored_tree, res.explored_sol, res.best
        complete = res.complete
        per_device = {k: list(v) for k, v in res.per_device.items()}
    elapsed = time.perf_counter() - t0

    _print_results(best, tree, sol, elapsed, complete=complete)
    if args.csv:
        _write_csv_with_phases(args, p, init_ub, n_dev, elapsed, tree, sol,
                               best, per_device, csv_stats)
    return 0


def _write_csv_with_phases(args, p, init_ub, n_dev, elapsed, tree, sol,
                           best, per_device, csv_stats):
    """CSV row with MEASURED phase-time attributions (utils/phase_timing):
    unit costs of the bound kernel / compaction / balance exchange timed
    on the real shapes, scaled by the run's counters — the reference's
    per-PU breakdown (PFSP_statistic.c:69-112) with real data, not the
    structural zeros of round 1."""
    import numpy as np

    from .engine import device as dev
    from .ops import batched
    from .problems import taillard
    from .utils import phase_timing

    jobs, machines = p.shape[1], p.shape[0]
    att = {}
    try:
        tables = batched.make_tables(p)
        pstate = dev.init_state(jobs, args.capacity, init_ub, p_times=p)
        prof = phase_timing.profile_phases(tables, pstate, args.lb,
                                           args.chunk)
        evals = per_device.get("evals", [0] * n_dev)
        iters = per_device.get("iters",
                               [max(1, int(e)) // (args.chunk * jobs)
                                for e in evals])
        t_bal = 0.0
        rounds = 0
        if n_dev > 1 and (args.ws or args.L):
            from .engine import distributed as dist
            from .ops import reference as ref
            from .parallel.mesh import worker_mesh

            adt = dev.aux_dtype(p)
            transfer_cap, min_transfer = dist.balance_defaults(
                args.chunk, jobs, machines, n_dev, args.m,
                aux_itemsize=adt.itemsize)
            # the profiled round must honor _balance_round's contract
            # limit <= capacity - D*transfer_cap with limit >= 1; a
            # too-small capacity is GROWN (the same pre-grow rule as
            # _DistDriver.seed) rather than clamped — a clamped limit
            # times a degenerate exchange whose writes land on live rows
            cap = args.capacity

            def _limit(c):
                return min(dev.row_limit(c, args.chunk, jobs),
                           c - n_dev * transfer_cap)

            while _limit(cap) < 1:
                cap *= 2
            limit = _limit(cap)
            fr = dist.Frontier(
                prmu=np.arange(jobs, dtype=np.int16)[None, :],
                depth=np.zeros(1, np.int16), tree=0, sol=0,
                best=best)
            fr.aux = ref.prefix_front_remain(
                p, fr.prmu, fr.depth)[:, :machines].astype(adt)
            leaves = dist._shard_frontier(fr, n_dev, cap, jobs,
                                          best, limit=limit)
            t_bal = phase_timing.profile_balance(
                worker_mesh(n_dev), leaves, transfer_cap, min_transfer,
                limit)
            rounds = int(np.max(iters)) // max(1, args.balance_period)
        att = phase_timing.attribute(prof, elapsed, evals, iters,
                                     balance_rounds=rounds,
                                     t_balance=t_bal)
        # the same numbers land in the global metrics registry, so a
        # co-running /metrics endpoint and the CSV row cannot disagree
        phase_timing.publish_attribution(att, inst=args.inst, lb=args.lb)
        per_device = dict(per_device)
        per_device.update({k: list(v) for k, v in att.items()})
    except Exception as e:  # profiling must never eat the results row
        print(f"warning: phase profiling failed ({e}); writing "
              "zero timing columns", file=sys.stderr)

    if n_dev == 1:
        csv_stats.write_single(
            args.csv, args.inst, args.lb, best, args.m, args.M, elapsed,
            float(att["kernel_time"][0]) if att else elapsed, tree, sol,
            gen_child_time=float(att["gen_child_time"][0]) if att else 0.0)
    elif getattr(args, "multihost", False):
        # the DCN tier writes the reference's dist_multigpu.csv schema
        # (PFSP_statistic.c:123-167)
        csv_stats.write_dist(args.csv, args.inst, args.lb, n_dev, args.C,
                             args.L, 1, best, args.m, args.M, args.T,
                             elapsed, tree, sol, per_device)
    else:
        # single-controller multi-device runs are the intra-node tier:
        # the reference's multigpu.csv schema (PFSP_statistic.c:69-112),
        # which its analysis scripts distinguish from the dist schema
        csv_stats.write_multi(args.csv, args.inst, args.lb, n_dev, args.C,
                              args.ws, best, args.m, args.M, args.T,
                              elapsed, tree, sol, per_device)


def _run_pfsp_segmented(args, p, init_ub, host_fraction: int = 0,
                        host_threads: int = 0):
    """Segmented single-device search with heartbeat + checkpoint/resume
    (the durability layer the reference lacks, SURVEY.md §5). With
    `host_fraction > 0` a native `-C` host session runs beside the
    segments — seeded from a warm-up share (fresh) or rows carved off
    the checkpointed pool (resume) — with incumbents merged at every
    segment boundary (engine/hybrid.HostSession).

    Returns (state, extras): host-tier tree/sol/counters to add to the
    device totals (all zero without a host tier)."""
    import os

    from .engine import checkpoint, device, distributed, hybrid
    from .ops import batched

    jobs = p.shape[1]
    tables = batched.make_tables(p)
    session = None
    warm_tree = warm_sol = 0
    h_prmu = np.zeros((0, jobs), np.int16)
    h_depth = np.zeros(0, np.int16)
    if args.checkpoint and checkpoint.resume_path(args.checkpoint):
        # load_resilient: a torn snapshot rolls back to its rotating
        # last-good sibling; a stacked (distributed) snapshot collapses
        # onto this single device via the same elastic reshard a
        # mesh-size change uses
        state, meta, _ = checkpoint.load_resilient(args.checkpoint,
                                                   p_times=p)
        state = checkpoint.collapse_to_single_device(state, args.chunk,
                                                     jobs)
        if args.grow_capacity:
            state = checkpoint.grow(state, args.grow_capacity)
        warm_tree = int(meta.get("warmup_tree", 0))
        warm_sol = int(meta.get("warmup_sol", 0))
        # a -C checkpoint carries the host tier's carved seed nodes;
        # resume re-seeds the session from them (or pushes them back
        # into the pool when resuming without -C) — see
        # engine/distributed.search for the same invariant
        saved_p = np.asarray(meta.get("host_prmu",
                                      np.zeros((0, jobs))), np.int16)
        saved_d = np.asarray(meta.get("host_depth", np.zeros(0)),
                             np.int16)
        if host_fraction > 0:
            if len(saved_d):
                h_prmu, h_depth = saved_p, saved_d
            else:
                state, h_prmu, h_depth = hybrid.pop_host_share(
                    state, host_fraction)
            if len(h_depth):
                session = hybrid.HostSession(
                    p, h_prmu, h_depth, args.lb, int(state.best),
                    n_threads=host_threads)
        elif len(saved_d):
            state = hybrid.restore_host_share(state, saved_p, saved_d, p)
        print(f"Resumed from {args.checkpoint} "
              f"(segment {int(meta.get('segment', 0))}, "
              f"iters {int(np.asarray(state.iters).max())}, "
              f"pool {int(np.asarray(state.size).sum())})")
    elif host_fraction > 0:
        # a host tier needs real nodes to seed: native warm-up frontier,
        # stride-split exactly like hybrid.search
        fr = distributed.bfs_warmup(p, args.lb, init_ub,
                                    target=4 * host_fraction)
        best0 = fr.best if init_ub is None else min(fr.best, int(init_ub))
        warm_tree, warm_sol = fr.tree, fr.sol
        dmask, h_prmu, h_depth = hybrid.split_host_share(
            fr.prmu, fr.depth, host_fraction)
        if len(h_depth):
            session = hybrid.HostSession(p, h_prmu, h_depth, args.lb,
                                         best0, n_threads=host_threads)
        state = device.init_state(jobs, args.grow_capacity or args.capacity,
                                  best0, prmu0=fr.prmu[dmask],
                                  depth0=fr.depth[dmask], p_times=p)
    else:
        state = device.init_state(jobs, args.grow_capacity or args.capacity,
                                  init_ub, p_times=p)

    seg_iters = args.segment_iters or 2048

    def run_fn(s, target):
        return device.run(tables, s, args.lb, args.chunk, max_iters=target)

    def heartbeat(r):
        print(f"[segment {r.segment}] iters={r.iters} tree={r.tree} "
              f"sol={r.sol} best={r.best} pool={r.pool_size} "
              f"t={r.elapsed:.2f}s")

    out = checkpoint.run_segmented(
        run_fn, state, segment_iters=seg_iters,
        checkpoint_path=args.checkpoint, heartbeat=heartbeat,
        checkpoint_every=getattr(args, "checkpoint_every", 1),
        max_total_iters=args.max_iters,
        checkpoint_meta={"warmup_tree": warm_tree, "warmup_sol": warm_sol,
                         "host_prmu": (h_prmu if session else
                                       np.zeros((0, jobs), np.int16)),
                         "host_depth": (h_depth if session else
                                        np.zeros(0, np.int16))},
        post_segment=(session.post_segment if session else None))

    extras = {"tree": warm_tree, "sol": warm_sol, "best": None,
              "host": {}}
    if session is not None:
        session.offer(int(np.asarray(out.best).min()))
        h_tree, h_sol, h_best, h_expanded = session.join()
        extras["tree"] += h_tree
        extras["sol"] += h_sol
        extras["best"] = h_best
        extras["host"] = {"host_tree": [h_tree], "host_sol": [h_sol],
                          "host_expanded": [h_expanded],
                          "exchanges": [session.exchanges],
                          "host_improved": [session.host_improved],
                          "dev_improved": [session.dev_improved]}
    return out, extras


def run_nqueens(args) -> int:
    import jax

    from .problems import nqueens as nq

    n_dev = args.D if args.D > 0 else len(jax.devices())
    print("=" * 49)
    print(f"TPU N-Queens ({n_dev} device(s))")
    print(f"Resolution of the {args.N}-Queens instance")
    print(f"  with {args.g} safety check(s) per evaluation")
    print("=" * 49)
    t0 = time.perf_counter()
    if n_dev == 1:
        out = nq.search(args.N, g=args.g, chunk=args.chunk,
                        capacity=args.capacity)
    else:
        out = nq.search_distributed(
            args.N, g=args.g, n_devices=n_dev, chunk=args.chunk,
            capacity=args.capacity)
    elapsed = time.perf_counter() - t0
    print("=" * 49)
    print(f"Size of the explored tree: {out.explored_tree}")
    print(f"Number of explored solutions: {out.explored_sol}")
    print(f"Elapsed time: {elapsed:.4f} [s]")
    print("=" * 49)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tpu_tree_search")
    ap.add_argument("--platform", type=str, default=None,
                    help="override the JAX platform (e.g. cpu for "
                         "debugging); must precede the subcommand")
    ap.add_argument("--multihost", action="store_true",
                    help="join a multi-host mesh via "
                         "jax.distributed.initialize() (coordinator/rank "
                         "discovered from the cluster env, e.g. SLURM); "
                         "the reference needs a separate MPI executable "
                         "for this tier (pfsp_dist_multigpu_cuda.c) — "
                         "here the same program runs, the mesh just "
                         "spans every host's devices over ICI + DCN")
    sub = ap.add_subparsers(dest="cmd", required=True)
    _pfsp_parser(sub)
    _nq_parser(sub)
    _solve_parser(sub)
    _serve_parser(sub)
    _client_parser(sub)
    _profile_parser(sub)
    _doctor_parser(sub)
    _capacity_parser(sub)
    _journey_parser(sub)
    sub.add_parser("devices",
                   help="describe attached devices (the reference's "
                        "gpu_info, common/gpu_util.cu:5-17)")
    rp = sub.add_parser("roofline",
                        help="analytic FLOP/byte bound-kernel model "
                             "(the reference's flop_lb*/bytes_per_inv_*, "
                             "PFSP_gpu_lib.cu:213-267)")
    rp.add_argument("-i", "--inst", type=int, default=21)
    rp.add_argument("-l", "--lb", type=int, default=1, choices=(0, 1, 2))
    rp.add_argument("--rate", type=float, default=None,
                    help="measured node-evals/s to compare to the ceiling")
    args = ap.parse_args(argv)
    if args.cmd == "doctor":
        # pure scraper: skip the compile cache / backend bootstrap —
        # the doctor must never touch (or wait for) an accelerator
        return run_doctor(args)
    if args.cmd == "capacity":
        # pure scraper, same stance as doctor
        return run_capacity(args)
    if args.cmd == "journey":
        # pure storage reader (stdlib-only, same stance as doctor)
        return run_journey(args)
    if args.platform:
        import os

        import jax
        os.environ["JAX_PLATFORMS"] = args.platform   # for children too
        jax.config.update("jax_platforms", args.platform)
    if args.multihost:
        import jax
        jax.distributed.initialize()
    # persistent compile cache: the reference's binaries are AOT-compiled
    # at build time; this is the JIT-world equivalent (first run compiles
    # ~45 s and caches to disk, every later process loads in ~1 s)
    from .utils import compile_cache
    compile_cache.enable()
    if args.cmd == "pfsp":
        return run_pfsp(args)
    if args.cmd == "solve":
        return run_solve(args)
    if args.cmd == "serve":
        return run_serve(args)
    if args.cmd == "client":
        return run_client(args)
    if args.cmd == "profile":
        return run_profile(args)
    if args.cmd == "devices":
        from .utils.device_info import print_device_info
        print_device_info()
        return 0
    if args.cmd == "roofline":
        from .problems import taillard
        from .utils import roofline
        jobs = taillard.nb_jobs(args.inst)
        machines = taillard.nb_machines(args.inst)
        print(roofline.report(args.lb, jobs, machines,
                              measured_rate=args.rate))
        return 0
    return run_nqueens(args)


if __name__ == "__main__":
    sys.exit(main())
