"""Run configuration.

One dataclass replaces the reference's three config tiers (SURVEY.md §5):
getopt CLI flags (PFSP_lib.c:173-320), compile-time size macros
(macro.h:9-11 — here just static shapes baked into jit), and site
makefiles (N/A: one toolchain). Reference flags keep their names and
defaults (PFSP_lib.c:175-185); TPU-specific knobs are documented inline.
"""

from __future__ import annotations

import dataclasses
import os

from ..tune import defaults as tune_defaults

_TRUTHY = ("1", "true", "on", "yes")


def _knob_default(name: str, site_default):
    """Resolve an accessor's default: the call site's explicit value
    wins, else the registry row's. TTS_* names MUST be registered
    (tools/tts_lint.py enforces the same at commit time; this raises at
    runtime so a typo'd knob name fails the first read, not silently
    never-applies). Non-TTS names pass through unchecked — the accessors
    stay usable for one-off vars without polluting the registry."""
    if name.startswith("TTS_"):
        knob = KNOBS.get(name)
        if knob is None:
            raise KeyError(
                f"unregistered knob {name!r}: every TTS_* env var must "
                "have a row in utils/config.KNOBS (the single-source "
                "registry tools/tts_lint.py checks)")
        if site_default is None:
            return knob.default
    return site_default


def env_flag(name: str, default: bool | None = None) -> bool:
    """Parse a boolean TTS_* env knob ('1'/'true'/'on'/'yes' = on;
    '0'/'false'/'off'/'no'/'' = off). One parser for every static
    feature flag so the accepted spellings cannot drift per call site."""
    default = bool(_knob_default(name, default) or False)
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw in _TRUTHY


def env_str(name: str, default: str | None = None) -> str | None:
    """String knob; '' and unset both resolve to the default (an empty
    path/spec knob in a fleet unit file means "off", not "here")."""
    default = _knob_default(name, default)
    return os.environ.get(name) or default


def env_int(name: str, default: int | None = None) -> int | None:
    """Integer knob. A malformed value falls back to the default — the
    repo-wide stance that a typo'd env knob must never take down the
    process (it degrades, and the lint-checked registry documents the
    real spelling)."""
    default = _knob_default(name, default)
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


def env_float(name: str, default: float | None = None) -> float | None:
    """Float knob; malformed values fall back like :func:`env_int`."""
    default = _knob_default(name, default)
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def env_ints(name: str, default: tuple = ()) -> tuple:
    """Comma-separated integer-list knob (the tuner's candidate
    ladders: TTS_TUNE_CHUNKS="64,256,1024"). Malformed lists fall back
    whole — a half-parsed candidate ladder is worse than the default."""
    if name.startswith("TTS_") and name not in KNOBS:
        raise KeyError(
            f"unregistered knob {name!r}: add a row to "
            "utils/config.KNOBS")
    raw = os.environ.get(name, "").strip()
    if not raw:
        return tuple(default)
    try:
        vals = tuple(int(t) for t in raw.split(",") if t.strip())
        return vals or tuple(default)
    except ValueError:
        return tuple(default)


def set_env(name: str, value) -> None:
    """The one sanctioned TTS_* env WRITE path (CLI flags propagating
    static knobs to respawned campaign workers / engine state init).
    Registration-checked like the readers, so a flag can't be spelled
    one way at the write site and another in the registry."""
    if name.startswith("TTS_") and name not in KNOBS:
        raise KeyError(
            f"unregistered knob {name!r}: add a row to "
            "utils/config.KNOBS")
    os.environ[name] = str(value)

# Resilience defaults — THE single source for engine/checkpoint.
# run_segmented's env fallbacks (TTS_RETRY_ATTEMPTS / TTS_RETRY_BASE_S /
# TTS_SEG_TIMEOUT_S) and PFSPConfig below both read these, so the
# documented knob and the actual behavior cannot drift apart. Module
# constants (not the dataclass) because engine code importing the
# dataclass for three scalars would be the wrong direction of coupling.
RETRY_ATTEMPTS_DEFAULT = 3
RETRY_BASE_S_DEFAULT = 0.5
SEGMENT_TIMEOUT_S_DEFAULT = 0.0   # 0 = watchdog off

# Search-service defaults (service/server.SearchServer). Module constants
# for the same reason as the retry knobs above: the service and the CLI
# `serve` entry both read them, and env overrides (TTS_SUBMESHES,
# TTS_QUEUE_DEPTH) must survive a campaign-driver respawn.
SERVICE_QUEUE_DEPTH_DEFAULT = 64      # admission control: reject beyond
SERVICE_SEGMENT_ITERS_DEFAULT = 512   # preemption/deadline granularity —
                                      # stop flags are honored at segment
                                      # boundaries, so this bounds the
                                      # service's reaction latency
SERVICE_CHECKPOINT_EVERY_DEFAULT = 4  # segments between periodic saves
                                      # (a stop/preempt always saves)
SERVICE_POLL_S_DEFAULT = 0.02         # scheduler poll period
SERVICE_RETRY_ATTEMPTS_DEFAULT = 2    # re-dispatches after a submesh
                                      # failure before a request FAILs
SERVICE_RETRY_BASE_S_DEFAULT = 0.2    # re-dispatch backoff base

# Observability defaults (tpu_tree_search/obs). Env-driven like the
# resilience knobs (they must survive campaign-worker respawns):
# TTS_TRACE_FILE appends the flight recorder's JSONL event log to a
# file, TTS_TRACE_RING bounds the in-memory ring buffer,
# TTS_SEARCH_TELEMETRY=1 (or --search-telemetry) compiles the
# on-device search-telemetry block into the loop
# (engine/telemetry.py — static flag, read at state init). The HTTP
# front-end is wired per entry point (`serve --http-port`), never
# ambiently — an open port must be an explicit operator choice.
OBS_TRACE_RING_DEFAULT = 16384        # ring-buffer records kept in RAM
OBS_RESOURCE_SAMPLE_S_DEFAULT = 1.0   # serve-session resource-sampler
                                      # cadence (obs/resource): device
                                      # bytes-in-use/peak + host RSS
                                      # gauges and memory trace lanes;
                                      # TTS_RESOURCE_SAMPLE_S overrides,
                                      # <= 0 disables the daemon thread
PROFILE_MAX_DURATION_S = 300.0        # POST /profile duration ceiling —
                                      # a typo'd duration must not pin
                                      # the profiler (and its artifact
                                      # growth) for hours
OBS_TRACE_MAX_MB_DEFAULT = 64         # tracelog JSONL sink rotation cap
                                      # (TTS_TRACE_MAX_MB; 0 disables):
                                      # at the cap the sink rolls to a
                                      # single `.1` sibling so a month-
                                      # long serve session cannot fill
                                      # the disk with its own recorder
OBS_METRIC_MAX_SERIES_DEFAULT = 2048  # per-metric label-set cap
                                      # (TTS_METRIC_MAX_SERIES): above
                                      # it new series are DROPPED and
                                      # counted in
                                      # tts_metrics_dropped_total — a
                                      # leaked per-request label must
                                      # degrade the metric, not the
                                      # process

# Fleet flight recorder (obs/store.py + obs/journey.py). TTS_OBS_STORE
# names the durable observability-store directory (usually inside the
# fleet/ledger root so it survives the host): metric snapshots and
# whitelisted trace events are appended as fsync'd CRC-stamped JSONL
# segments under PER-WRITER file names (obs-<writer>-NNNNNNNN.jsonl —
# the PR-16 quarantine rule, so N peers sharing the store never collide)
# and replayed at boot, so dashboards, health history and tts_* counters
# RESUME across restarts and takeovers instead of zeroing. Unset = off,
# bit-identical to the store-less stack (the sink, the sampler and the
# replay are all vacuous).
OBS_STORE_ENV = "TTS_OBS_STORE"
OBS_STORE_SEGMENT_RECORDS_DEFAULT = 4096  # TTS_OBS_STORE_SEGMENT_RECORDS
#                                           — records per segment before
#                                           rotation (the ledger's bound)
OBS_STORE_RETAIN_S_DEFAULT = 86400.0  # TTS_OBS_STORE_RETAIN_S — whole
#                                       segments whose newest record is
#                                       older than this are pruned at
#                                       rotation (time-series retention;
#                                       the ledger compacts state, the
#                                       store expires history)
OBS_STORE_QUEUE_DEFAULT = 4096        # TTS_OBS_STORE_QUEUE — bounded
#                                       sink-queue depth; a full queue
#                                       DROPS the sample (observability
#                                       must never block the scheduler)

# SLO burn-rate rules (obs/health.py slo_error_burn / slo_latency_burn).
# Classic multi-window burn: the error budget is TTS_SLO_ERROR_BUDGET
# (allowed bad fraction of terminals) and the burn rate is
# bad_fraction/budget over a window; the alert fires only when BOTH the
# fast and the slow window burn above TTS_SLO_BURN_THRESHOLD — fast
# alone is a blip, slow alone is stale history. Windows are computed
# over the durable store's terminal history (wall-clock stamped), so a
# budget spent across three restarts and a takeover still fires.
SLO_ERROR_BUDGET_DEFAULT = 0.01       # TTS_SLO_ERROR_BUDGET
SLO_LATENCY_TARGET_S_DEFAULT = 0.0    # TTS_SLO_LATENCY_TARGET_S — per-
#                                       request spent_s above this is a
#                                       latency violation (0 = latency
#                                       SLO off)
SLO_LATENCY_BUDGET_DEFAULT = 0.05     # TTS_SLO_LATENCY_BUDGET
SLO_BURN_FAST_S_DEFAULT = 300.0       # TTS_SLO_BURN_FAST_S (5m window)
SLO_BURN_SLOW_S_DEFAULT = 3600.0      # TTS_SLO_BURN_SLOW_S (1h window)
SLO_BURN_THRESHOLD_DEFAULT = 2.0      # TTS_SLO_BURN_THRESHOLD — burn
#                                       multiple both windows must
#                                       exceed to fire

# Operational-health defaults (obs/health.py — the SLO/anomaly rules
# engine every serve session runs). Env-driven (TTS_HEALTH_*) for the
# same respawn-survival reason as the knobs above; <= 0 interval
# disables the daemon. Threshold semantics are documented per rule in
# README.md's Operations section.
OBS_HEALTH_INTERVAL_S_DEFAULT = 2.0       # TTS_HEALTH_INTERVAL_S
HEALTH_QUEUE_WAIT_P99_S_DEFAULT = 60.0    # TTS_HEALTH_QUEUE_WAIT_P99_S
HEALTH_STALL_S_DEFAULT = 30.0             # TTS_HEALTH_STALL_S — max
                                          # heartbeat age of a RUNNING
                                          # request before `stall` fires
HEALTH_STALL_WARMUP_S_DEFAULT = 300.0     # TTS_HEALTH_STALL_WARMUP_S —
                                          # the stall limit BEFORE the
                                          # first heartbeat, when the
                                          # gap legitimately includes
                                          # an XLA trace+compile
HEALTH_MEM_FRAC_DEFAULT = 0.92            # TTS_HEALTH_MEM_FRAC —
                                          # in_use/limit above this
                                          # fires `mem_headroom`
HEALTH_COMPILE_STORM_DEFAULT = 6          # TTS_HEALTH_COMPILE_STORM —
                                          # executor-cache misses per
                                          # evaluation interval
HEALTH_PRUNING_MIN_RATE_DEFAULT = 0.0005  # TTS_HEALTH_PRUNING_MIN_RATE
HEALTH_PRUNING_MIN_NODES_DEFAULT = 100_000  # ...only judged past this
                                            # many evaluated children
HEALTH_AUDIT_WINDOW_S_DEFAULT = 300.0     # TTS_HEALTH_AUDIT_WINDOW_S —
                                          # how long an audit failure
                                          # keeps the `audit` rule firing

# Progress / ETA estimation (obs/estimate.py): online tree-size
# estimates published per request behind a warmup gate — both minimums
# must be met before the first gauge sample, so early wild estimates
# (one segment's branching factors extrapolated over the whole tree)
# never reach a dashboard. TTS_PROGRESS=0 removes the estimator layer
# entirely: no gauges, no snapshot keys, no checkpoint-meta key, no
# predictive rules — bit-identical to the pre-estimator server.
PROGRESS_WARMUP_SEGMENTS_DEFAULT = 3      # TTS_PROGRESS_WARMUP_SEGMENTS
PROGRESS_WARMUP_NODES_DEFAULT = 2000      # TTS_PROGRESS_WARMUP_NODES
PROGRESS_EWMA_DEFAULT = 0.3               # TTS_PROGRESS_EWMA — weight
                                          # of the newest segment's raw
                                          # estimate in the smoothed one

# Fleet capacity & utilization observability (obs/capacity.py): the
# lane-state ledger + shape-class demand/capacity model behind
# TTS_CAPACITY. TTS_CAPACITY=0 removes the layer entirely — no lane
# events/counters, no capacity gauges, no snapshot key, no saturation
# rule: bit-identical to the pre-capacity server.
CAPACITY_WINDOW_S_DEFAULT = 300.0         # TTS_CAPACITY_WINDOW_S —
                                          # arrival-rate sliding window
CAPACITY_EWMA_DEFAULT = 0.3               # TTS_CAPACITY_EWMA — weight
                                          # of the newest observation in
                                          # service-rate / demand EWMAs
HEALTH_SATURATION_DEFAULT = 0.85          # TTS_HEALTH_SATURATION —
                                          # sustained ρ above this fires
                                          # `saturation` (before the
                                          # queue_wait p99 rule can)
HEALTH_SATURATION_FOR_S_DEFAULT = 6.0     # TTS_HEALTH_SATURATION_FOR_S
                                          # — dwell before pending
                                          # becomes firing

# Raw-speed flags (both STATIC: read once per search/server, bit-
# identical node accounting on or off — see README's Performance
# section and tests/test_overlap.py's parity suite):
# TTS_OVERLAP=1 pipelines segmented execution — the next segment is
# dispatched (with donated pool carries) before the previous segment's
# counters are fetched, and checkpoint serialization+fsync moves to a
# bounded-queue writer thread — so the device never idles on the host
# between segments (tts_segment_gap_seconds -> ~0).
# TTS_SHARE_INCUMBENT=1 makes the search SERVICE share best-makespan
# incumbents across concurrent same-instance requests through a
# process-wide board (engine/incumbent.py): each segment boundary
# publishes the submesh's best and folds the global best in as the next
# segment's pruning ceiling (monotone-only, audited).
OVERLAP_FLAG = "TTS_OVERLAP"                  # default off
SHARE_INCUMBENT_FLAG = "TTS_SHARE_INCUMBENT"  # default off

# Zero-compile cold start (service/aot_cache.py + serve --aot-cache /
# --prewarm). TTS_AOT_CACHE names the disk directory persisted AOT
# executables live in (empty/unset = in-memory executor cache only);
# a restarted server deserializes previously-compiled loops from it
# instead of re-tracing+compiling (ledger `source=disk`). TTS_PREWARM
# is the boot pre-warm spec ("taillard,spool", explicit "JxM" tokens,
# or "0"/"off"/"no" as a kill-switch that disables pre-warm even when
# the --prewarm CLI flag is set) — executables for
# the standard shape families and the spool backlog are readied before
# the first request arrives.
AOT_CACHE_ENV = "TTS_AOT_CACHE"
PREWARM_ENV = "TTS_PREWARM"
AOT_WRITER_QUEUE_DEPTH = 2    # AOT-cache writer-thread back-pressure
                              # bound (the AsyncCheckpointWriter
                              # discipline: block, never drop/unbound)
PREWARM_CONCURRENCY_DEFAULT = 2   # TTS_PREWARM_CONCURRENCY — parallel
                                  # warm workers at boot; compiles are
                                  # CPU-heavy, so a small bound keeps
                                  # the boot window predictable
# the standard Taillard shape families (jobs, machines) — ta001-ta120;
# `serve --prewarm taillard` readies one executable per family per
# submesh (the instance VALUES are runtime args, so one warm per shape
# covers all ten instances of the class)
PREWARM_TAILLARD_FAMILIES = (
    (20, 5), (20, 10), (20, 20),
    (50, 5), (50, 10), (50, 20),
    (100, 5), (100, 10), (100, 20),
    (200, 10), (200, 20), (500, 20),
)
ASYNC_CKPT_QUEUE_DEPTH = 2    # writer-thread back-pressure bound: a
                              # dispatch thread outrunning the disk
                              # BLOCKS here instead of buffering
                              # unbounded snapshots (never drops one)
INCUMBENT_MAX_KEYS_DEFAULT = 4096  # TTS_INCUMBENT_MAX_KEYS — bound on
                                   # the board's distinct instance
                                   # keys; least-recently-updated
                                   # entries evict first (dropping an
                                   # entry only loses warm-start
                                   # tightening, never correctness) —
                                   # same bounded-observability stance
                                   # as TTS_METRIC_MAX_SERIES

# Adaptive dispatch (tpu_tree_search/tune + engine/ladder):
# TTS_LADDER=1 (STATIC, default off — off is bit-identical to the
# pre-ladder driver) enables chunk-ladder execution in the segmented
# distributed driver: 2-3 pre-built chunk rungs switched only at
# segment boundaries from the pool-occupancy signal, so ramp/drain run
# small-chunk steps instead of underfilled tuned-chunk ones.
# TTS_TUNE_CACHE names the persistent tuning-cache directory
# (tune/cache.TuningCache — fingerprint-checked, CRC-stamped, corrupt
# entries quarantined); TTS_TUNE=1 lets `serve --prewarm` PROBE cold
# shapes at boot (a warm cache replays with zero probes either way).
# Probe knobs for CI/small hosts: TTS_TUNE_CHUNKS / TTS_TUNE_PERIODS
# (comma lists), TTS_TUNE_WINDOW / TTS_TUNE_WARM (iterations).
LADDER_FLAG = "TTS_LADDER"
TUNE_CACHE_ENV = "TTS_TUNE_CACHE"
TUNE_ENV = "TTS_TUNE"
TUNE_WINDOW_ITERS_DEFAULT = 24    # TTS_TUNE_WINDOW — measured iters
                                  # per probe candidate
TUNE_WARM_ITERS_DEFAULT = 200     # TTS_TUNE_WARM — warm-up iters
                                  # before a probe's measured window

# Crash-safe serving (service/ledger.py + serve --ledger). TTS_LEDGER
# names the durable request-ledger directory: every request state
# transition (admit, dispatch, budget, preempt, release, exclusion,
# failure, quarantine/readmit, pause/resume, terminal) is journaled as an
# fsync'd CRC-stamped JSONL record BEFORE it is acknowledged, and a
# restarted server replays the ledger at boot — queued/active requests
# are re-admitted with budgets/exclusions/failure logs intact and
# resume from their checkpoints, terminal results re-serve
# idempotently, standing quarantines and admission pauses are
# restored. Unset = off (bit-identical to the pre-ledger server).
# TTS_DRAIN_TIMEOUT_S bounds the SIGTERM/SIGINT graceful drain (stop
# admission -> preempt at segment boundaries -> drain the checkpoint/
# AOT/ledger writers -> exit 0); past it the serve entry escalates to
# checkpoint-and-abort (the ledger makes even that abort recoverable).
LEDGER_ENV = "TTS_LEDGER"
DRAIN_TIMEOUT_S_DEFAULT = 30.0
LEDGER_BUDGET_EVERY_S_DEFAULT = 5.0   # seconds between journaled
#                                       budget heartbeats per RUNNING
#                                       request (bounds the spent_s a
#                                       hard kill can lose without
#                                       fsyncing at heartbeat rate)

# Fleet failover (service/lease.py + service/failover.py + serve
# --fleet-dir/--failover). Every server that opens a ledger also takes
# a LEASE on it: an fsync'd CRC-stamped lease file (owner id,
# monotonically increasing fencing epoch, TTL TTS_LEASE_TTL_S) renewed
# by a daemon thread. TTS_FLEET_DIR names the shared root peers scan
# for ledgers whose lease expired; TTS_FAILOVER=1 lets the
# FailoverWatcher EXECUTE the takeover protocol (epoch CAS bump,
# truncate-to-last-good, replay + re-admit on the survivor). The
# default (off) is OBSERVE-ONLY: expired peers are journaled
# (failover.peer_down) and surface on /alerts, zero takeovers run —
# the TTS_REMEDIATE rollout discipline. Fencing makes split-brain safe
# by construction: a stale owner discovers the bumped epoch at its
# next append/save/renewal and self-fences (typed LeaseLost, zero
# further commits).
FAILOVER_FLAG = "TTS_FAILOVER"     # default off (observe)
FLEET_DIR_ENV = "TTS_FLEET_DIR"
LEASE_TTL_S_DEFAULT = 10.0         # TTS_LEASE_TTL_S — lease expiry age;
#                                    renewals run at ~TTL/3, takeover
#                                    scans at ~TTL/2 (adoption inside
#                                    2x TTL, the drill's bound)

# Request megabatching (engine/megabatch.py + service batch-former +
# serve --megabatch). TTS_MEGABATCH=1 (STATIC per server; default off =
# bit-identical to the solo scheduler) makes the admission queue a
# BATCH-FORMER: queued requests group by (problem, table shape,
# lb_kind, engine knobs) and a group dispatches to one submesh as ONE
# vmapped compiled loop when it reaches TTS_BATCH_MAX members or its
# oldest member has waited TTS_BATCH_AGE_S seconds (a lone request
# age-closes as a batch of one and runs the ordinary solo path). Every
# batched request's node counts, optimum and telemetry block are
# bit-identical to its solo run (test-pinned).
MEGABATCH_FLAG = "TTS_MEGABATCH"
BATCH_MAX_DEFAULT = 8          # TTS_BATCH_MAX — close a batch at size
BATCH_AGE_S_DEFAULT = 0.25     # TTS_BATCH_AGE_S — or at this age

# Bound-portfolio racing (service/portfolio.py + request `portfolio: K`
# + client --portfolio). A request submitted with portfolio K fans out
# as K sibling sub-requests over DISTINCT configurations (bound tiers
# from the problem's lb_kinds ladder, per-tier tuned chunk plans from
# the Autotuner, chunk variants when tiers run out) that share ONE
# incumbent board via share_group — each sibling's incumbents tighten
# the others' pruning. The first sibling to finish with a PROOF wins:
# the parent finalizes DONE with the winner's result and every losing
# sibling is cancelled through the ordinary member-level stop path at
# its next segment boundary. TTS_PORTFOLIO sets a default K for
# requests that don't carry an explicit `portfolio` (0 = off, the
# default — a portfolio-less request takes the exact pre-portfolio
# path); TTS_PORTFOLIO_MAX caps K at admission.
PORTFOLIO_ENV = "TTS_PORTFOLIO"
PORTFOLIO_MAX_DEFAULT = 8      # TTS_PORTFOLIO_MAX — admission cap on K

# Self-healing (service/remediate.py + serve --remediate).
# TTS_REMEDIATE=1 lets the RemediationController EXECUTE its policy
# table (stall -> preempt+exclude, repeated localized failures ->
# submesh quarantine + canary readmit, cross-submesh failures ->
# dead-letter, compile_storm -> pause admission, mem_headroom ->
# shed + ladder demotion hint, audit -> checkpoint quarantine). The
# default (off) is OBSERVE-ONLY: detection and journaling run, zero
# actions are taken — the same bit-identical-off discipline as
# overlap/ladder. Every executed action is capped per rule per sliding
# window; the quarantine/dead-letter thresholds below are the
# containment geometry (failures localized to ONE submesh = hardware,
# quarantine it; failures FOLLOWING the request across >= K distinct
# submeshes = the request, dead-letter it).
REMEDIATE_FLAG = "TTS_REMEDIATE"              # default off (observe)
REMEDIATE_WINDOW_S_DEFAULT = 300.0            # TTS_REMEDIATE_WINDOW_S
REMEDIATE_MAX_PER_RULE_DEFAULT = 4            # TTS_REMEDIATE_MAX_PER_RULE
REMEDIATE_QUARANTINE_FAILS_DEFAULT = 3        # TTS_REMEDIATE_QUARANTINE_FAILS
REMEDIATE_DEADLETTER_SUBMESHES_DEFAULT = 3    # TTS_REMEDIATE_DEADLETTER_SUBMESHES
REMEDIATE_PROBE_S_DEFAULT = 30.0              # TTS_REMEDIATE_PROBE_S —
                                              # canary cooldown after a
                                              # quarantine/failed probe


# --------------------------------------------------------- knob registry
#
# THE single source of truth for every TTS_* environment knob. The
# static analyzer (tpu_tree_search/analysis/knobs.py, run by
# tools/tts_lint.py and the CI lint leg) enforces that (a) no module
# outside this file reads TTS_* from os.environ directly — everything
# goes through the env_* accessors above, which refuse unregistered
# names — and (b) every registered knob appears in README.md (the
# "Knob registry" table there is GENERATED from this dict by
# `tools/tts_lint.py --write-docs`; edit here, never there).
#
# `scope` partitions the table: "runtime" knobs configure the engine/
# service/obs stack proper; "bench", "tool" and "test" knobs configure
# bench.py, the tools/ drivers and the test suite.

@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    kind: str          # "flag" | "int" | "float" | "str" | "ints"
    default: object    # value when unset (None = no default / off)
    doc: str           # one line; lands in the generated README table
    scope: str = "runtime"


def _knob_table(*rows: Knob) -> dict:
    table = {}
    for k in rows:
        if k.name in table:
            raise ValueError(f"duplicate knob {k.name}")
        table[k.name] = k
    return table


KNOBS: dict[str, Knob] = _knob_table(
    # --- static engine flags (read once per search/server; off-modes
    #     are bit-identical by the tier-1 matrix contract)
    Knob("TTS_SEARCH_TELEMETRY", "flag", False,
         "compile the on-device search-telemetry block into the loop "
         "(static, read at state init; counts bit-identical on/off)"),
    Knob("TTS_OVERLAP", "flag", False,
         "pipelined segmented driver: async dispatch, donated carries, "
         "writer-thread checkpoints (segment gap -> ~0)"),
    Knob("TTS_SHARE_INCUMBENT", "flag", False,
         "cross-request incumbent board: concurrent same-instance "
         "requests tighten each other's pruning"),
    Knob("TTS_LADDER", "flag", False,
         "chunk-ladder execution: pre-built rungs switched at segment "
         "boundaries from pool occupancy"),
    Knob("TTS_DEBUG_STEP", "flag", False,
         "compile jax.debug taps into the device step (trace-time "
         "flag; debug builds only)"),
    # --- resilience
    Knob("TTS_RETRY_ATTEMPTS", "int", RETRY_ATTEMPTS_DEFAULT,
         "in-place retries of transient I/O / dispatch errors"),
    Knob("TTS_RETRY_BASE_S", "float", RETRY_BASE_S_DEFAULT,
         "exponential-backoff base for those retries (seconds)"),
    Knob("TTS_SEG_TIMEOUT_S", "float", SEGMENT_TIMEOUT_S_DEFAULT,
         "per-segment wall-clock watchdog (0 = off)"),
    Knob("TTS_FAULTS", "str", None,
         "deterministic fault-injection plan (utils/faults syntax; "
         "test/drill harness)"),
    # --- service
    Knob("TTS_SUBMESHES", "int", 1,
         "serve: submesh partition count (campaign respawn channel)"),
    Knob("TTS_QUEUE_DEPTH", "int", SERVICE_QUEUE_DEPTH_DEFAULT,
         "serve: admission-queue depth (reject beyond)"),
    Knob("TTS_AOT_CACHE", "str", None,
         "disk AOT executable cache directory (unset = in-memory "
         "executor cache only)"),
    Knob("TTS_PREWARM", "str", None,
         "boot pre-warm spec ('taillard,spool', explicit 'JxM' tokens; "
         "'0'/'off'/'no' kill-switch beats the CLI flag)"),
    Knob("TTS_PREWARM_CONCURRENCY", "int", PREWARM_CONCURRENCY_DEFAULT,
         "parallel pre-warm workers at boot"),
    Knob("TTS_INCUMBENT_MAX_KEYS", "int", INCUMBENT_MAX_KEYS_DEFAULT,
         "incumbent-board distinct-instance bound (LRU-evicted)"),
    # --- adaptive dispatch
    Knob("TTS_TUNE_CACHE", "str", None,
         "persistent tuning-cache directory (fingerprint-checked, "
         "CRC-stamped)"),
    Knob("TTS_TUNE", "flag", False,
         "allow boot-time probing of cold shapes during pre-warm"),
    Knob("TTS_TUNE_CHUNKS", "ints", None,
         "probe candidate chunk ladder (comma list; unset = the "
         "tuner's built-in pow2 ladder)"),
    Knob("TTS_TUNE_PERIODS", "ints", None,
         "probe candidate balance periods (comma list)"),
    Knob("TTS_TUNE_WINDOW", "int", TUNE_WINDOW_ITERS_DEFAULT,
         "measured iterations per probe candidate"),
    Knob("TTS_TUNE_WARM", "int", TUNE_WARM_ITERS_DEFAULT,
         "warm-up iterations before a probe's measured window"),
    Knob("TTS_TUNE_RUNGS", "flag", False,
         "tune(): probe the winner's ladder rungs for the rung "
         "admission profile the chunk ladder reads (extra compiles "
         "per probe)"),
    # --- observability
    Knob("TTS_TRACE_FILE", "str", None,
         "flight-recorder JSONL sink path (unset = ring buffer only)"),
    Knob("TTS_TRACE_RING", "int", OBS_TRACE_RING_DEFAULT,
         "flight-recorder in-RAM ring capacity (records)"),
    Knob("TTS_TRACE_MAX_MB", "float", OBS_TRACE_MAX_MB_DEFAULT,
         "sink rotation cap in MB (one .1 rollover kept; 0 disables)"),
    Knob("TTS_METRIC_MAX_SERIES", "int", OBS_METRIC_MAX_SERIES_DEFAULT,
         "per-metric label-set cap (new series beyond it drop, "
         "counted in tts_metrics_dropped_total)"),
    Knob("TTS_RESOURCE_SAMPLE_S", "float", OBS_RESOURCE_SAMPLE_S_DEFAULT,
         "resource-sampler cadence (device bytes + host RSS; <= 0 "
         "disables the daemon)"),
    # --- fleet flight recorder (obs/store.py + obs/journey.py;
    #     semantics per README "Flight recorder")
    Knob("TTS_OBS_STORE", "str", None,
         "durable observability-store directory (per-writer CRC JSONL "
         "segments, replayed at boot; unset = off, bit-identical)"),
    Knob("TTS_OBS_STORE_SEGMENT_RECORDS", "int",
         OBS_STORE_SEGMENT_RECORDS_DEFAULT,
         "obs store: records per segment before rotation"),
    Knob("TTS_OBS_STORE_RETAIN_S", "float", OBS_STORE_RETAIN_S_DEFAULT,
         "obs store: retention window — whole segments older than this "
         "are pruned at rotation"),
    Knob("TTS_OBS_STORE_QUEUE", "int", OBS_STORE_QUEUE_DEFAULT,
         "obs store: bounded sink-queue depth (full queue drops the "
         "sample, never blocks the scheduler)"),
    # --- SLO burn-rate rules (obs/health.py; multi-window burn over
    #     the durable store's terminal history)
    Knob("TTS_SLO_ERROR_BUDGET", "float", SLO_ERROR_BUDGET_DEFAULT,
         "error SLO: allowed failed fraction of terminal requests"),
    Knob("TTS_SLO_LATENCY_TARGET_S", "float",
         SLO_LATENCY_TARGET_S_DEFAULT,
         "latency SLO: per-request spent_s above this is a violation "
         "(0 = latency SLO off)"),
    Knob("TTS_SLO_LATENCY_BUDGET", "float", SLO_LATENCY_BUDGET_DEFAULT,
         "latency SLO: allowed violating fraction of terminals"),
    Knob("TTS_SLO_BURN_FAST_S", "float", SLO_BURN_FAST_S_DEFAULT,
         "burn-rate fast window (seconds)"),
    Knob("TTS_SLO_BURN_SLOW_S", "float", SLO_BURN_SLOW_S_DEFAULT,
         "burn-rate slow window (seconds)"),
    Knob("TTS_SLO_BURN_THRESHOLD", "float", SLO_BURN_THRESHOLD_DEFAULT,
         "burn multiple BOTH windows must exceed for the slo_* rules "
         "to fire"),
    # --- audit
    Knob("TTS_AUDIT", "str", "1",
         "node-conservation auditor: '1' on (default), '0' off, "
         "'full' adds checkpoint re-read verification"),
    Knob("TTS_AUDIT_CKPT", "flag", False,
         "checkpoint roundtrip verification alone (TTS_AUDIT=full "
         "implies it)"),
    Knob("TTS_AUDIT_HARD", "flag", False,
         "raise AuditError on any failed invariant (the CI mode)"),
    # --- health rules (thresholds; semantics per README Operations)
    Knob("TTS_HEALTH_INTERVAL_S", "float", OBS_HEALTH_INTERVAL_S_DEFAULT,
         "health-monitor evaluation interval (<= 0 disables daemon)"),
    Knob("TTS_HEALTH_QUEUE_WAIT_P99_S", "float",
         HEALTH_QUEUE_WAIT_P99_S_DEFAULT,
         "queue_wait rule: windowed p99 SLO threshold (seconds)"),
    Knob("TTS_HEALTH_STALL_S", "float", HEALTH_STALL_S_DEFAULT,
         "stall rule: max heartbeat age of a RUNNING request"),
    Knob("TTS_HEALTH_STALL_WARMUP_S", "float",
         HEALTH_STALL_WARMUP_S_DEFAULT,
         "stall rule: the limit BEFORE the first heartbeat (covers "
         "XLA trace+compile)"),
    Knob("TTS_HEALTH_MEM_FRAC", "float", HEALTH_MEM_FRAC_DEFAULT,
         "mem_headroom rule: in_use/limit firing fraction"),
    Knob("TTS_HEALTH_COMPILE_STORM", "float", HEALTH_COMPILE_STORM_DEFAULT,
         "compile_storm rule: unplanned fresh compiles per interval"),
    Knob("TTS_HEALTH_PRUNING_MIN_RATE", "float",
         HEALTH_PRUNING_MIN_RATE_DEFAULT,
         "pruning_collapse rule: minimum pruning rate"),
    Knob("TTS_HEALTH_PRUNING_MIN_NODES", "float",
         HEALTH_PRUNING_MIN_NODES_DEFAULT,
         "pruning_collapse rule: judged only past this many children"),
    Knob("TTS_HEALTH_AUDIT_WINDOW_S", "float",
         HEALTH_AUDIT_WINDOW_S_DEFAULT,
         "audit rule: how long a failure keeps the alert firing"),
    Knob("TTS_HEALTH_PERF_JSON", "str", None,
         "perf rule: path to a perf_sentry --json verdict file"),
    Knob("TTS_HEALTH_TENANT_OVERRIDES", "str", None,
         "per-tenant threshold overrides as JSON "
         '({"tenant": {"slo_latency_target_s": 30}}); overridden '
         "tenants get their own burn series and risk-rule judgment"),
    # --- progress / ETA estimation (obs/estimate.py; semantics per
    #     README "Progress & ETA")
    Knob("TTS_PROGRESS", "flag", True,
         "per-request online tree-size/progress/ETA estimation "
         "(observation-only; 0 = estimator layer absent, "
         "bit-identical)"),
    Knob("TTS_PROGRESS_WARMUP_SEGMENTS", "int",
         PROGRESS_WARMUP_SEGMENTS_DEFAULT,
         "progress: segments observed before estimates publish"),
    Knob("TTS_PROGRESS_WARMUP_NODES", "int",
         PROGRESS_WARMUP_NODES_DEFAULT,
         "progress: explored nodes required before estimates publish"),
    Knob("TTS_PROGRESS_EWMA", "float", PROGRESS_EWMA_DEFAULT,
         "progress: EWMA weight of the newest segment's raw estimate"),
    # --- fleet capacity & utilization (obs/capacity.py; semantics per
    #     README "Capacity & utilization")
    Knob("TTS_CAPACITY", "flag", True,
         "lane-state ledger + shape-class capacity model + saturation "
         "rule (observation-only; 0 = capacity layer absent, "
         "bit-identical)"),
    Knob("TTS_CAPACITY_WINDOW_S", "float", CAPACITY_WINDOW_S_DEFAULT,
         "capacity: sliding window for per-class arrival rates"),
    Knob("TTS_CAPACITY_EWMA", "float", CAPACITY_EWMA_DEFAULT,
         "capacity: EWMA weight of the newest service-rate/demand "
         "observation"),
    Knob("TTS_HEALTH_SATURATION", "float", HEALTH_SATURATION_DEFAULT,
         "saturation rule: sustained overall ρ firing threshold"),
    Knob("TTS_HEALTH_SATURATION_FOR_S", "float",
         HEALTH_SATURATION_FOR_S_DEFAULT,
         "saturation rule: dwell seconds before pending -> firing"),
    # --- crash-safe serving (service/ledger.py; semantics per README
    #     "Crash recovery & deployment")
    Knob("TTS_LEDGER", "str", None,
         "serve: durable request-ledger directory (write-ahead JSONL, "
         "replayed at boot; unset = off, bit-identical to today)"),
    Knob("TTS_DRAIN_TIMEOUT_S", "float", DRAIN_TIMEOUT_S_DEFAULT,
         "serve: SIGTERM/SIGINT graceful-drain budget before the "
         "checkpoint-and-abort escalation"),
    # --- request megabatching (engine/megabatch.py; semantics per
    #     README "Request megabatching")
    Knob("TTS_MEGABATCH", "flag", False,
         "serve: batch same-shape-class requests into one vmapped "
         "compiled loop (default off = the solo scheduler exactly)"),
    Knob("TTS_BATCH_MAX", "int", BATCH_MAX_DEFAULT,
         "megabatch: close a forming batch at this many members"),
    Knob("TTS_BATCH_AGE_S", "float", BATCH_AGE_S_DEFAULT,
         "megabatch: close a forming batch once its oldest member has "
         "waited this long (a lone request closes as a batch of one)"),
    # --- bound-portfolio racing (service/portfolio.py; semantics per
    #     README "Portfolio racing")
    Knob("TTS_PORTFOLIO", "int", 0,
         "serve: default portfolio width K for requests without an "
         "explicit `portfolio` (0 = off — a portfolio-less request "
         "takes the exact pre-portfolio path, bit-identical)"),
    Knob("TTS_PORTFOLIO_MAX", "int", PORTFOLIO_MAX_DEFAULT,
         "serve: admission cap on a request's portfolio width K "
         "(reject beyond)"),
    # --- fleet failover (service/lease.py + service/failover.py;
    #     semantics per README "High availability & failover")
    Knob("TTS_FLEET_DIR", "str", None,
         "serve: shared fleet root the FailoverWatcher scans for peer "
         "ledgers whose lease expired (unset = no watcher)"),
    Knob("TTS_FAILOVER", "flag", False,
         "execute ledger takeovers of expired peers (default: "
         "observe-only — peer_down detection and journaling run, zero "
         "takeovers)"),
    Knob("TTS_LEASE_TTL_S", "float", LEASE_TTL_S_DEFAULT,
         "ledger-lease expiry age in seconds (renewed at ~TTL/3; an "
         "unreachable owner is takeover-eligible past it)"),
    # --- self-healing (service/remediate.py; semantics per README
    #     "Self-healing")
    Knob("TTS_REMEDIATE", "flag", False,
         "execute the remediation policy table (default: observe-only "
         "— detection and journaling run, zero actions taken)"),
    Knob("TTS_REMEDIATE_WINDOW_S", "float", REMEDIATE_WINDOW_S_DEFAULT,
         "sliding window for the action rate valve and the "
         "localized-failure quarantine count"),
    Knob("TTS_REMEDIATE_MAX_PER_RULE", "int",
         REMEDIATE_MAX_PER_RULE_DEFAULT,
         "executed actions allowed per rule per window (reversals "
         "exempt); beyond it a flapping rule degrades to observe-only"),
    Knob("TTS_REMEDIATE_QUARANTINE_FAILS", "int",
         REMEDIATE_QUARANTINE_FAILS_DEFAULT,
         "dispatch failures localized to one submesh inside the window "
         "before it is quarantined (drained, held out, canary-probed)"),
    Knob("TTS_REMEDIATE_DEADLETTER_SUBMESHES", "int",
         REMEDIATE_DEADLETTER_SUBMESHES_DEFAULT,
         "distinct submeshes a request may fail on before it "
         "dead-letters as FAILED with its full failure_log"),
    Knob("TTS_REMEDIATE_PROBE_S", "float", REMEDIATE_PROBE_S_DEFAULT,
         "canary-probe cooldown: seconds after a quarantine (or a "
         "failed probe) before the synthetic micro-request retries"),
    # --- bench.py
    Knob("TTS_BENCH_INSTANCE", "int", 21,
         "bench: Taillard instance id", "bench"),
    Knob("TTS_BENCH_CHUNK", "int", None,
         "bench: chunk override (unset = measured-defaults table)",
         "bench"),
    Knob("TTS_BENCH_ITERS", "int", 2000,
         "bench: measured loop iterations", "bench"),
    Knob("TTS_BENCH_WARM", "int", None,
         "bench: warm-up iterations override", "bench"),
    Knob("TTS_BENCH_LB", "str", "1,2",
         "bench: comma list of bounds to measure", "bench"),
    Knob("TTS_BENCH_TUNED", "flag", False,
         "bench: resolve chunk/period through the Autotuner", "bench"),
    Knob("TTS_BENCH_SEGGAP", "flag", True,
         "bench: emit the segment-gap row", "bench"),
    Knob("TTS_BENCH_COLDSTART", "flag", True,
         "bench: emit the cold-start rows", "bench"),
    Knob("TTS_BENCH_RAMPDRAIN", "flag", True,
         "bench: emit the ramp/drain ladder rows", "bench"),
    Knob("TTS_BENCH_RAMP_JOBS", "int", 10,
         "bench: ramp/drain synthetic instance jobs", "bench"),
    Knob("TTS_BENCH_RAMP_CHUNK", "int", 1024,
         "bench: ramp/drain tuned-chunk rung", "bench"),
    Knob("TTS_BENCH_SERVE_RPS", "flag", True,
         "bench: emit the serve requests/s row (small-instance mix "
         "through one serve session)", "bench"),
    Knob("TTS_BENCH_SERVE_N", "int", 8,
         "bench: serve-rps request count", "bench"),
    Knob("TTS_BENCH_PORTFOLIO", "flag", True,
         "bench: emit the portfolio-racing speedup row (K-way race "
         "with a shared incumbent board vs the best member solo)",
         "bench"),
    Knob("TTS_BENCH_PORTFOLIO_K", "int", 3,
         "bench: portfolio-speedup race width", "bench"),
    Knob("TTS_BENCH_PORTFOLIO_JOBS", "int", 11,
         "bench: portfolio-speedup synthetic instance jobs (large "
         "enough that runs span many segments — the race only saves "
         "bound evals when losers cancel mid-tree)", "bench"),
    Knob("TTS_BENCH_HBM", "flag", True,
         "bench: emit the step-HBM-bytes row (compiled-loop "
         "memory_analysis temp bytes on every backend "
         "— a live peak-bytes delta reads ~0 once the warm run "
         "establishes the lifetime high-water)",
         "bench"),
    # --- tools/ drivers
    Knob("TTS_CAMPAIGN_OUT", "str", "/tmp/campaign.jsonl",
         "run_campaign: result JSONL path", "tool"),
    Knob("TTS_WORKDIR", "str", "/tmp",
         "run_campaign: checkpoint/workdir root", "tool"),
    Knob("TTS_LB", "int", 2, "run_campaign: bound kind", "tool"),
    Knob("TTS_CHUNK", "int", 32768, "run_campaign: pop chunk", "tool"),
    Knob("TTS_POOL_ROWS", "int", 0,
         "run_campaign: pool rows (0 = sized from the instance; "
         "formerly TTS_CAPACITY, renamed when the capacity "
         "observability layer claimed that name)",
         "tool"),
    Knob("TTS_BUDGET_S", "float", 7200.0,
         "run_campaign: per-instance execution budget", "tool"),
    Knob("TTS_SEG", "int", 2000,
         "run_campaign: segment iterations", "tool"),
    Knob("TTS_CKPT_EVERY", "int", 8,
         "run_campaign: segments between checkpoints", "tool"),
    Knob("TTS_UB", "str", "opt",
         "run_campaign: incumbent seed ('opt' | 'inf')", "tool"),
    Knob("TTS_STALL_GRACE", "float", 900.0,
         "run_campaign: supervisor stall grace (seconds)", "tool"),
    Knob("TTS_STALL_FACTOR", "float", 4.0,
         "run_campaign: stall limit as a multiple of segment time",
         "tool"),
    Knob("TTS_STALL_MIN", "float", 720.0,
         "run_campaign: stall limit floor (seconds)", "tool"),
    Knob("TTS_MAX_RESTARTS", "int", 50,
         "run_campaign: worker respawn budget", "tool"),
    Knob("TTS_DEAD_LIMIT", "int", 5,
         "run_campaign: consecutive no-progress restarts before an "
         "instance is declared dead", "tool"),
    Knob("TTS_TABLE_OUT", "str", "/tmp/single_device_table.jsonl",
         "run_single_device_table: output path", "tool"),
    Knob("TTS_BAL_CHUNK", "int", 32768,
         "bench_balance: chunk", "tool"),
    Knob("TTS_BAL_CAP", "int", 1 << 21,
         "bench_balance: pool capacity", "tool"),
    Knob("TTS_BAL_ROUNDS", "int", 20,
         "bench_balance: measured rounds", "tool"),
    Knob("TTS_BRACKET_REPS", "int", 256,
         "validate_attribution: bracket repetitions", "tool"),
    # --- test suite
    Knob("TTS_TEST_TPU", "flag", False,
         "tests: keep the attached TPU backend instead of the 8-device "
         "virtual CPU mesh", "test"),
    Knob("TTS_TEST_STALL_AT_SEG", "int", 0,
         "campaign kill-drill: worker self-stalls at this segment",
         "test"),
    Knob("TTS_OBS_ARTIFACT_DIR", "str", None,
         "tests: export serve-session trace artifacts here (the CI "
         "upload dir)", "test"),
)


@dataclasses.dataclass
class PFSPConfig:
    # --- reference flags (semantics per README.md:49-101)
    inst: int = 14        # -i Taillard instance id
    lb: int = 1           # -l bound: 0=lb1_d, 1=lb1, 2=lb2
    ub: int = 1           # -u 1: seed incumbent with known optimum; 0: inf
    m: int = 25           # -m min pool before offload -> min seed/worker;
                          #    with -C 1 also the host hand-off threshold
    M: int = 50000        # -M max offload chunk -> pop-chunk ceiling
    T: int = 5000         # -T CPU-thread chunk (accepted for CLI parity;
                          #    the native drain sizes itself from cpu_count)
    D: int = 0            # -D devices (0 = all addressable)
    C: int = 0            # -C heterogeneous co-processing: native host
                          #    warm-up + device loop + multi-threaded
                          #    native host drain (engine/hybrid.py)
    ws: int = 1           # -w intra-mesh balancing on/off
    L: int = 1            # -L inter-node balancing on/off (same collective
                          #    tier on TPU; ws==0 and L==0 disable balance)
    perc: float = 0.5     # -p steal fraction (steal-half = 0.5)
    # --- TPU engine knobs (defaults single-sourced in
    # tune/defaults.py — the measured table bench and serve also read;
    # the Autotuner's fallback tier)
    chunk: int = tune_defaults.CLI_CHUNK_DEFAULT
    #                         # parents popped per compiled step
    capacity: int = 1 << 20   # per-device pool rows
    balance_period: int = tune_defaults.BALANCE_PERIOD_DEFAULT
    #                         # steps between collective balance rounds
    csv: str | None = None    # append a reference-schema CSV row here
    # Resilience knobs deliberately do NOT live on this dataclass: the
    # override channel is env vars (TTS_RETRY_ATTEMPTS / TTS_RETRY_BASE_S
    # / TTS_SEG_TIMEOUT_S / TTS_FAULTS) or CLI flags, because the
    # campaign supervisor's worker subprocesses must inherit them across
    # respawns — a Python object cannot ride a respawn. The defaults are
    # the module constants above.

    @property
    def balancing_enabled(self) -> bool:
        return bool(self.ws or self.L)


@dataclasses.dataclass
class NQueensConfig:
    N: int = 14           # -N board size
    g: int = 1            # -g safety-check repetitions (work scaling)
    D: int = 0            # devices (0 = all)
    chunk: int = tune_defaults.CLI_CHUNK_DEFAULT
    capacity: int = 1 << 20
    balance_period: int = tune_defaults.BALANCE_PERIOD_DEFAULT
