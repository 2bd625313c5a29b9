"""Offline autotuner: probe the dispatch ladder once, replay forever.

Every perf round so far re-tuned the engine's dispatch knobs BY HAND:
chunk went 256 → 32768 → 65536 when the bf16 matmul changed the cost
structure, and balance_period=4 came from a one-off
tools/bench_balance_period.py sweep the ROADMAP warns cannot be
re-derived on the virtual mesh. The Autotuner retires that ritual:

- **Probe**: per (J×M shape family, lb kind, worker count), run short
  warmed probes (tune/probe.ProbeHarness — the validated same-state
  method) over a candidate chunk ladder, then a balance-period sweep
  at the winning chunk, and pick the best node-evals/s.
- **Persist**: the winner lands in the fingerprint-checked, CRC-stamped
  tuning cache (tune/cache.TuningCache) keyed by shape/bound/topology —
  a restarted server replays it with ZERO probe executions
  (``resolve(...)`` source="cache"; the probe ledger stays empty).
- **Fall back**: with no cache entry and probing not allowed (the
  request hot path), resolution returns the measured-defaults table
  (tune/defaults.py) — the tier that used to be three drifting
  hardcoded constants.

Consumption points: ``distributed.search(chunk=None, tuner=...)``,
``SearchServer(tune_cache_dir=...)`` (+ ``serve --tune-cache/--tune``),
``bench.py`` (TTS_BENCH_TUNED=1), and ``serve --prewarm`` (tune at
boot, warm the tuned shapes).

Observability: ``tts_tuner_probes_total``,
``tts_tuner_cache_{hits,misses}_total`` and ``tts_tuner_probe_seconds``
when a registry is supplied; ``snapshot()`` rides the server's
``/status`` under the ``tuner`` key; ``tools/tune_report.py`` renders
the cache directory.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..obs import tracelog
from . import defaults
from .cache import TuningCache
from .defaults import Params
from .probe import ProbeError, ProbeHarness

__all__ = ["Autotuner"]

# default candidate ladder for the chunk sweep (pow2 keeps every rung
# lane-aligned; TTS_TUNE_CHUNKS overrides, e.g. "64,256,1024" for the
# CPU CI smoke). The production span covers the serving default through
# the round-5 single-chip optimum.
CHUNK_CANDIDATES_DEFAULT = (256, 1024, 4096, 16384, 65536)
# balance periods swept at the winning chunk (the old
# bench_balance_period default set, trimmed to the plausible range)
PERIOD_CANDIDATES_DEFAULT = (1, 4, 16)


class Autotuner:
    """Cache → probe → defaults resolution of the dispatch knobs.

    `cache_dir` (or the TTS_TUNE_CACHE env) enables the persistent
    tier; without it the tuner still probes (results memoized
    in-process) and still falls back to the defaults table. All probe
    knobs have CI-friendly env overrides (TTS_TUNE_CHUNKS,
    TTS_TUNE_PERIODS, TTS_TUNE_WINDOW, TTS_TUNE_WARM)."""

    def __init__(self, cache_dir: str | os.PathLike | None = None,
                 registry=None, fingerprint_extra: dict | None = None,
                 chunks: tuple | None = None, periods: tuple | None = None,
                 window_iters: int | None = None,
                 warm_iters: int | None = None,
                 capacity: int | None = None, repeats: int = 2):
        self.cache = (TuningCache(cache_dir, registry=registry,
                                  fingerprint_extra=fingerprint_extra)
                      if cache_dir else None)
        from ..utils import config as _cfg
        self.chunks = tuple(chunks) if chunks else _cfg.env_ints(
            "TTS_TUNE_CHUNKS", CHUNK_CANDIDATES_DEFAULT)
        self.periods = tuple(periods) if periods else _cfg.env_ints(
            "TTS_TUNE_PERIODS", PERIOD_CANDIDATES_DEFAULT)
        self.window_iters = int(window_iters
                                or _cfg.env_int("TTS_TUNE_WINDOW")
                                or _cfg.TUNE_WINDOW_ITERS_DEFAULT)
        self.warm_iters = int(warm_iters
                              or _cfg.env_int("TTS_TUNE_WARM")
                              or _cfg.TUNE_WARM_ITERS_DEFAULT)
        self.capacity = int(capacity or 1 << 18)
        self.repeats = int(repeats)
        self.probes_run = 0          # guarded-by: self._lock
        #                              (probe executions this lifetime —
        #                              the zero-probe warm-boot assertion)
        self.ledger: list[dict] = []  # guarded-by: self._lock
        #                               (one record per probe execution)
        self._memo: dict[tuple, Params] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()
        self._probes_c = self._probe_h = None
        if registry is not None:
            self._probes_c = registry.counter(
                "tts_tuner_probes_total",
                "warmed probe executions (candidate measurements)")
            self._probe_h = registry.histogram(
                "tts_tuner_probe_seconds",
                "wall seconds per tuning sweep (all candidates of one "
                "shape)")

    # ------------------------------------------------------------- keys

    @staticmethod
    def key(jobs: int, machines: int, lb_kind: int,
            n_workers: int, problem: str = "pfsp",
            batch: int | None = None) -> tuple:
        # the problem name LEADS the key (PFSP entries keep their
        # pre-plugin cache identity — persisted caches stay valid).
        # A megabatched dispatch (batch > 1) appends a ("batch", B)
        # suffix: solo keys keep their exact persisted layout, and a
        # batched optimum can never be served from — or clobber — the
        # solo entry of the same shape.
        base = (str(problem), int(jobs), int(machines), int(lb_kind),
                int(n_workers))
        if batch is not None and int(batch) > 1:
            base = base + ("batch", int(batch))
        return base

    # --------------------------------------------------------- resolve

    def resolve(self, jobs: int, machines: int, lb_kind: int = 1,
                n_workers: int = 1, allow_probe: bool = False,
                p_times: np.ndarray | None = None,
                context: str = "serving",
                problem: str = "pfsp",
                batch: int | None = None) -> Params:
        """The three-tier lookup. ``allow_probe=False`` is the request
        hot path (cache else defaults — never seconds of probing while
        a client waits); ``allow_probe=True`` is the boot/bench path
        (cache else probe+persist else defaults). The probe harness is
        problem-generic (tune/probe.ProbeHarness drives the plugin's
        own step pipeline), so any registered problem probes when a
        table is supplied; a probe without one is PFSP-only (the
        synthetic-table fallback is a PFSP generator) and other
        problems fall through to defaults.

        ``batch`` (a megabatch dispatch's instance-axis width) rides
        the cache key and the defaults lookup: batched optima are their
        own entries, and the fallback is the batched defaults row —
        never the solo serving row (the probe harness is solo-only, so
        batched keys resolve cache-else-batched-defaults)."""
        key = self.key(jobs, machines, lb_kind, n_workers, problem,
                       batch=batch)
        if batch is not None and batch > 1:
            allow_probe = False
        with self._lock:
            memo = self._memo.get(key)
        if memo is not None:
            return memo
        if self.cache is not None:
            entry = self.cache.load(key)
            if entry is not None:
                rm = entry.get("rung_modes")
                params = Params(chunk=int(entry["chunk"]),
                                balance_period=int(entry["balance_period"]),
                                transfer_cap=entry.get("transfer_cap"),
                                source="cache",
                                evals_per_s=entry.get("evals_per_s"),
                                rung_modes=tuple(rm) if rm else None)
                with self._lock:
                    self._memo[key] = params
                return params
        if allow_probe:
            try:
                return self.tune(jobs, machines, lb_kind=lb_kind,
                                 n_workers=n_workers, p_times=p_times,
                                 problem=problem)
            except ProbeError as e:
                tracelog.event("tuner.probe_failed", jobs=jobs,
                               machines=machines, lb_kind=lb_kind,
                               problem=problem, error=repr(e))
        return defaults.params_for(context, jobs, machines,
                                   problem=problem, batch=batch)

    # ------------------------------------------------------------ tune

    def tune(self, jobs: int, machines: int, lb_kind: int = 1,
             n_workers: int = 1,
             p_times: np.ndarray | None = None,
             problem: str = "pfsp") -> Params:
        """Run the sweep for one shape family and persist the winner.

        Only the SHAPE of `p_times` matters (a synthetic table in the
        Taillard value range probes the same compiled program every
        real instance of the class runs); pass a real table to probe
        on committed traffic — REQUIRED for non-PFSP problems (the
        synthetic fallback is a PFSP generator). Raises ProbeError
        when no steady measurement state exists (callers fall back to
        defaults).

        Under TTS_TUNE_RUNGS, the winning chunk's LADDER rungs are
        probed too after the chunk/period winner is picked — BELOW the
        static rung floor as well — producing the rung admission
        profile (`Params.rung_modes`) that engine/ladder consumes for
        measured rung admission."""
        key = self.key(jobs, machines, lb_kind, n_workers, problem)
        if p_times is None:
            if problem != "pfsp":
                raise ProbeError(
                    f"probing problem {problem!r} needs its instance "
                    "table (the synthetic fallback generates PFSP "
                    "tables only)")
            from ..problems.pfsp import PFSPInstance
            p_times = PFSPInstance.synthetic(jobs=jobs,
                                             machines=machines,
                                             seed=0).p_times
        t0 = time.perf_counter()
        # the harness capacity must make EVERY candidate measurable:
        # a chunk's scratch margin (chunk*jobs) plus its balance
        # headroom must fit under the pool, or the top rungs of the
        # production ladder (65536 at 20 jobs needs ~2.6M rows) would
        # silently drop out of the sweep and the tuner could never
        # select the documented optimum — grow past the configured
        # floor as the candidate set demands
        capacity = self.capacity
        while capacity < 2 * max(self.chunks) * max(int(jobs), 4):
            capacity *= 2
        harness = ProbeHarness(
            p_times, lb_kind=lb_kind, capacity=capacity,
            warm_chunk=min(self.chunks), warm_iters=self.warm_iters,
            window_iters=self.window_iters, repeats=self.repeats,
            problem=problem)
        from ..engine import ladder as _ladder
        from ..utils import config as _cfg
        with tracelog.span("tuner.sweep", jobs=jobs, machines=machines,
                           lb_kind=lb_kind, n_workers=n_workers) as sp:
            results = []
            for c in self.chunks:
                try:
                    results.append(self._probe(
                        harness, c, defaults.BALANCE_PERIOD_DEFAULT))
                except ProbeError as e:
                    # a dropped candidate must be LOUD in the sweep
                    # record — a silent continue here once cost the
                    # whole top of the ladder
                    tracelog.event("tuner.candidate_dropped", chunk=c,
                                   error=repr(e))
                    continue
            if not results:
                raise ProbeError(
                    f"no chunk candidate of {self.chunks} is "
                    f"measurable at capacity {capacity}")

            # steady-state rates outrank ramp rates: an underfilled
            # candidate (pool < chunk at the window start) only wins
            # when every candidate is underfilled
            filled = [r for r in results if not r.underfilled]
            best_chunk = max(filled or results,
                             key=lambda r: r.evals_per_s)
            period_results = [best_chunk]
            for b in self.periods:
                if b == best_chunk.balance_period:
                    continue
                try:
                    period_results.append(self._probe(
                        harness, best_chunk.chunk, b))
                except ProbeError as e:
                    tracelog.event("tuner.candidate_dropped",
                                   balance_period=b, error=repr(e))
                    continue
            winner = max(period_results, key=lambda r: r.evals_per_s)
            sp.set(chunk=winner.chunk,
                   balance_period=winner.balance_period,
                   evals_per_s=winner.evals_per_s,
                   probes=len(results) + len(period_results) - 1)

            # --- rung admission profile: under TTS_TUNE_RUNGS, probe
            # the winning chunk's LADDER rungs — below the static rung
            # floor too (min_chunk=1), since measured admission
            # (engine/ladder.rungs_from_profile) subsumes the floor —
            # on the same warmed state. The profile persists with the
            # winner. Off by default: each rung is an extra compile,
            # and without a profile ladder admission uses the static
            # floors.
            rung_modes = []
            memo = {(r.chunk, r.balance_period): r
                    for r in results + period_results}
            rungs = (_ladder.rungs_for(winner.chunk, min_chunk=1)
                     if _cfg.env_flag("TTS_TUNE_RUNGS") else ())
            for c in rungs:
                try:
                    r = memo.get((c, winner.balance_period)) \
                        or self._probe(harness, c, winner.balance_period)
                except ProbeError as e:
                    tracelog.event("tuner.candidate_dropped", chunk=c,
                                   error=repr(e))
                    continue
                rung_modes.append({"chunk": int(c),
                                   "ms_per_iter": r.ms_per_iter,
                                   "evals_per_s": r.evals_per_s})
        sweep_s = time.perf_counter() - t0
        if self._probe_h is not None:
            self._probe_h.observe(sweep_s)
        payload = {
            "chunk": winner.chunk,
            "balance_period": winner.balance_period,
            "transfer_cap": None,    # derived from chunk at run time
            #   (the byte-budget rule prices it per topology; a probed
            #   1-worker cap would mis-size a production submesh)
            "evals_per_s": winner.evals_per_s,
            "sweep_seconds": round(sweep_s, 3),
            "rung_modes": rung_modes,
            "probes": [r.to_json()
                       for r in results + period_results[1:]],
        }
        if self.cache is not None:
            self.cache.store(key, payload,
                             key_repr="/".join(str(k) for k in key))
        params = Params(chunk=winner.chunk,
                        balance_period=winner.balance_period,
                        source="probe", evals_per_s=winner.evals_per_s,
                        rung_modes=(tuple(rung_modes) if rung_modes
                                    else None))
        with self._lock:
            self._memo[key] = params
        return params

    def _probe(self, harness: ProbeHarness, chunk: int,
               balance_period: int):
        r = harness.measure(chunk, balance_period)
        with self._lock:
            self.probes_run += 1
            self.ledger.append(r.to_json())
        if self._probes_c is not None:
            self._probes_c.inc()
        return r

    # ------------------------------------------------------------ read

    def snapshot(self) -> dict:
        """JSON-safe stats — status_snapshot()'s `tuner` key."""
        with self._lock:
            return {
                "probes_run": self.probes_run,
                "tuned_shapes": len(self._memo),
                "chunk_candidates": list(self.chunks),
                "period_candidates": list(self.periods),
                "cache": (self.cache.snapshot()
                          if self.cache is not None else None),
            }
