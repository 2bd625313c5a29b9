"""Compiled-executable cache for the search service, with a
compile-cost ledger and an optional disk-persistent AOT tier.

The distributed loop costs seconds to minutes to trace + compile (the
one-off cost utils/compile_cache amortizes ACROSS processes via XLA's
persistent disk cache). This cache is the IN-PROCESS tier above it: the
compiled callable itself, keyed by everything the trace specializes on —
problem kind, (jobs, machines), lb_kind, chunk, aux dtype, the submesh's
device identities, capacity and the balance knobs — and explicitly NOT
on the instance data (the problem tables are runtime arguments to the
compiled loop; see engine/distributed.build_dist_loop).

That key design is the serve-many-compile-once property: all ten
instances of a Taillard class (same jobs x machines) served at the same
bound on the same submesh share ONE trace and ONE executable — request 1
pays the compile, requests 2..10 start exploring immediately. The
hit/miss counters ride the server's JSON status snapshot so the reuse is
observable (and testable) in production, not assumed.

The LEDGER makes the compile cost itself observable: every entry
records its trace and compile wall seconds (measured on the entry's
first invocation via the jit AOT path — ``fn.lower(...).compile()`` —
so the cost is attributed to the entry, not smeared into whichever
request happened to arrive first) and, where the backend supports
``compiled.cost_analysis()``, the executable's FLOPs and
bytes-accessed. The ledger rides ``status_snapshot()`` (the
``compile_ledger`` key), feeds the ``tts_compile_seconds`` histogram
on ``/metrics``, and renders as a table via
``tools/compile_report.py``. When the AOT path is unsupported for a
program, the entry falls back to timing the first call (compile
dominated) and says so in its ``method`` field.

The AOT tier (service/aot_cache.AOTCache, injected by the server when
``probe()`` passes) makes the compile a once-per-KEY cost across
server LIFETIMES: a miss first tries a disk deserialize (~0.2 s on the
CPU test mesh, zero ``lower()``/``compile()`` calls) and only compiles
— then persists, off the hot path — when no loadable entry exists.
Each ledger entry records where its executable came from
(``source=disk|compile``) and the deserialize seconds, so the
restart-replay contract ("a redeploy does zero fresh compiles for
previously-served shapes") is assertable from the ledger alone.
:meth:`_Entry.warm` is the boot pre-warm hook: it readies the
executable from disk or an abstract-shape compile WITHOUT executing it
(engine/distributed._DistDriver.warm drives it with ShapeDtypeStruct
arguments).

Between this cache (same process), the AOT tier (same key across
processes) and compile_cache.enable() (XLA's persistent HLO cache), a
restarted server re-serves a warm traffic mix with sub-second loads
instead of ~45 s compiles.
"""

from __future__ import annotations

import threading
import time

import jax

from ..obs import tracelog

# Persistent-cache hits seen by each thread: jax records the event in
# the thread that compiles, so _compile_fresh can tell whether XLA's
# persistent cache served its executable (see AOTCache.store).
_XLA_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_seen = threading.local()


def _on_jax_event(event: str, **_) -> None:
    if event == _XLA_CACHE_HIT:
        _seen.hits = getattr(_seen, "hits", 0) + 1


jax.monitoring.register_event_listener(_on_jax_event)


class _Entry:
    """One cached loop: the built callable plus its cost record. The
    trace/compile (or disk-load) measurement happens on the FIRST
    invocation — or at :meth:`warm` time for pre-warmed entries (jit is
    lazy; at build() time there is nothing to measure yet)."""

    __slots__ = ("fn", "compiled", "record", "_lock", "_measured",
                 "_on_measured", "_on_fallback", "_aot", "_key")

    def __init__(self, fn, record: dict, on_measured, aot=None,
                 key: tuple = (), on_fallback=None):
        self.fn = fn
        self.compiled = None     # guarded-by: self._lock
        self.record = record
        # reentrant: _first_call runs under it and may book a fallback
        self._lock = threading.RLock()
        self._measured = False   # guarded-by: self._lock
        self._on_measured = on_measured
        self._on_fallback = on_fallback
        self._aot = aot
        self._key = key

    def __call__(self, *args):
        if not self._measured:
            with self._lock:
                if not self._measured:
                    return self._first_call(*args)
        if self.compiled is not None:
            try:
                return self.compiled(*args)
            except (TypeError, ValueError) as e:
                # AOT executables are stricter about argument layout
                # than jit; if a later call stops matching, fall back
                # to the jitted fn permanently (same trace -> the jit
                # cache compiles once more, correctness unaffected).
                # The downgrade is BOOKED: a disk/warm-sourced entry
                # that silently recompiled via jit would leave the
                # ledger claiming source=disk and the compile
                # invisible to the storm signal and the restart-replay
                # assertions.
                self._book_fallback(e)
        return self.fn(*args)

    def _book_fallback(self, error: Exception) -> None:
        with self._lock:
            if self.compiled is None:
                return                       # a racing call booked it
            self.compiled = None
            rec = self.record
            rec.update(fallback_from=rec.get("source"),
                       source="compile", method="jit_fallback")
            tracelog.event("executor.aot_fallback", key=rec["key"],
                           fallback_from=rec.get("fallback_from"),
                           error=repr(error))
            if self._on_fallback is not None:
                self._on_fallback(rec)

    def _load_from_disk(self) -> bool:   # holds: self._lock
        """Try the disk AOT tier (caller holds the lock). A hit readies
        `self.compiled` with ZERO lower()/compile() calls and books the
        entry as source=disk."""
        if self._aot is None:
            return False
        got = self._aot.load(self._key)
        if got is None:
            return False
        compiled, dt = got
        self.record.update(trace_s=0.0, compile_s=0.0, method="aot",
                           source="disk", deserialize_s=round(dt, 6))
        self._cost_analysis(compiled, self.record)
        self.compiled = compiled
        self._measured = True
        self._record_measured()
        return True

    def _compile_fresh(self, *args):
        """The jit AOT path — the ONLY place in the entry that traces
        or compiles (tests monkeypatch it to pin the zero-compile
        restart-replay contract). Returns (compiled, trace_s,
        compile_s); raises when the AOT path cannot handle the
        program/backend. Records in the ledger whether XLA's persistent
        compilation cache served the executable (``xla_cache_hit``)."""
        hits = getattr(_seen, "hits", 0)
        t0 = time.perf_counter()
        lowered = self.fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        self.record["xla_cache_hit"] = getattr(_seen, "hits", 0) > hits
        return compiled, t1 - t0, t2 - t1

    def warm(self, *abstract_args, via: str = "prewarm") -> str:
        """Ready the executable WITHOUT executing it (the boot
        pre-warm hook; `abstract_args` are jax.ShapeDtypeStructs).
        Returns how: "warm" (already measured — idempotent), "disk"
        (deserialized), "compile" (fresh compile, persisted), or
        "skipped" (the AOT path failed; the first real call takes the
        normal path and nothing is booked). `via` labels the ledger
        record ("prewarm" / "ladder" — any warm-initiated compile is
        PLANNED and excluded from the compile_storm signal)."""
        with self._lock:
            if self._measured:
                return "warm"
            if self._load_from_disk():
                return "disk"
            rec = self.record
            try:
                compiled, trace_s, compile_s = self._compile_fresh(
                    *abstract_args)
            except Exception as e:  # noqa: BLE001 — warming is an
                # optimization; a program the AOT path rejects still
                # serves (and measures) through the first-call path
                tracelog.event("executor.warm_skipped", key=rec["key"],
                               error=repr(e))
                return "skipped"
            rec.update(trace_s=round(trace_s, 6),
                       compile_s=round(compile_s, 6),
                       method="aot", source="compile", via=via)
            self._cost_analysis(compiled, rec)
            self.compiled = compiled
            self._measured = True
            self._record_measured()
            if self._aot is not None:
                self._aot.store(self._key, compiled, key_repr=rec["key"],
                                xla_cache_hit=rec.get("xla_cache_hit"))
            return "compile"

    def _first_call(self, *args):        # holds: self._lock
        rec = self.record
        if self._load_from_disk():
            try:
                return self.compiled(*args)
            except (TypeError, ValueError) as e:
                # same AOT-strictness net as __call__: a replayed
                # entry whose layout drifted in a way the fingerprint
                # missed must degrade to jit (booked), not fail the
                # request on its very first post-restart invocation
                self._book_fallback(e)
                return self.fn(*args)
        # ONLY lower/compile inside the try: a runtime failure of the
        # compiled loop itself must propagate to the service retry tier
        # (re-running it here would be a hidden second execution outside
        # the retry accounting) and must not be booked as compile cost
        try:
            compiled, trace_s, compile_s = self._compile_fresh(*args)
            rec.update(trace_s=round(trace_s, 6),
                       compile_s=round(compile_s, 6),
                       method="aot", source="compile")
            self._cost_analysis(compiled, rec)
            self.compiled = compiled
        except Exception:  # noqa: BLE001 — a backend/program that the
            # AOT path cannot handle still serves through plain jit
            self.compiled = compiled = None
        if compiled is not None:
            self._measured = True
            self._record_measured()
            if self._aot is not None:
                self._aot.store(self._key, compiled, key_repr=rec["key"],
                                xla_cache_hit=rec.get("xla_cache_hit"))
            return compiled(*args)
        # fallback: the first jit call IS trace+compile (+ one execute)
        t0 = time.perf_counter()
        out = self.fn(*args)
        rec.update(trace_s=0.0,
                   compile_s=round(time.perf_counter() - t0, 6),
                   method="first_call", source="compile")
        self._measured = True
        self._record_measured()
        return out

    def _record_measured(self) -> None:
        rec = self.record
        tracelog.event("executor.compile", key=rec["key"],
                       trace_s=rec["trace_s"],
                       compile_s=rec["compile_s"],
                       method=rec["method"], source=rec.get("source"),
                       deserialize_s=rec.get("deserialize_s"),
                       flops=rec.get("flops"))
        if self._on_measured is not None:
            self._on_measured(rec)

    @staticmethod
    def _cost_analysis(compiled, rec: dict) -> None:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                if ca.get("flops") is not None:
                    rec["flops"] = float(ca["flops"])
                if ca.get("bytes accessed") is not None:
                    rec["bytes_accessed"] = float(ca["bytes accessed"])
        except Exception:  # noqa: BLE001 — optional per backend
            pass
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                rec["temp_bytes"] = int(
                    getattr(mem, "temp_size_in_bytes", 0))
        except Exception:  # noqa: BLE001
            pass


class ExecutorCache:
    """Thread-safe get-or-build cache of compiled search loops.

    `get_or_build(key, build)` is the whole interface
    (engine/distributed._DistDriver consults it when a `loop_cache` is
    injected). Builds run under the lock: two requests racing to build
    the SAME key must not trace twice — and distinct keys are distinct
    submeshes or shapes, whose builds are cheap closures anyway (jit is
    lazy; XLA compilation happens at first call, outside the lock).

    `aot` (service/aot_cache.AOTCache, optional) is the disk tier:
    entries first try a deserialize and persist fresh compiles, so a
    restarted process replays this cache from disk. `compiles` /
    `planned_compiles` count TRUE fresh XLA compiles (total / initiated
    by pre-warm) — the health layer's compile_storm rule reads their
    difference so a boot-time cache replay or an operator-requested
    pre-warm never reads as a storm (see `storm_signal`).
    """

    def __init__(self, registry=None, aot=None):
        self._lock = threading.Lock()
        self._fns: dict[tuple, _Entry] = {}   # guarded-by: self._lock
        self.hits = 0                # guarded-by: self._lock
        self.misses = 0              # guarded-by: self._lock
        self.aot = aot
        self.compiles = 0            # guarded-by: self._lock
        #                              (fresh XLA compiles, any origin)
        self.planned_compiles = 0    # guarded-by: self._lock
        #                              (...of which pre-warm initiated)
        # optional metrics mirror (obs/metrics.Registry): the server
        # passes its per-server registry so /metrics exposes the same
        # hit/miss counts the JSON snapshot reports, plus the
        # compile-cost histogram the ledger feeds
        self._hits_c = self._misses_c = self._entries_g = None
        self._compile_h = None
        if registry is not None:
            self._hits_c = registry.counter(
                "tts_executor_cache_hits_total",
                "requests served from an already-compiled loop")
            self._misses_c = registry.counter(
                "tts_executor_cache_misses_total",
                "compiled-loop builds (traces/compiles paid)")
            self._entries_g = registry.gauge(
                "tts_executor_cache_entries",
                "distinct compiled loops held")
            self._entries_g.set_fn(lambda: len(self))
            self._compile_h = registry.histogram(
                "tts_compile_seconds",
                "trace+compile wall seconds per new executable")

    def _measured(self, record: dict) -> None:
        # disk-sourced entries paid a deserialize, not a compile: they
        # must feed neither the compile histogram nor the storm signal
        if record.get("source") != "compile":
            return
        with self._lock:
            self.compiles += 1
            # any warm-initiated compile is planned: boot pre-warm
            # ("prewarm") and chunk-ladder rung pre-readies ("ladder")
            if record.get("via"):
                self.planned_compiles += 1
        if self._compile_h is not None:
            self._compile_h.observe(record["trace_s"]
                                    + record["compile_s"])

    def _fallback(self, record: dict) -> None:
        """An AOT executable was downgraded to plain jit mid-lifetime
        (argument mismatch): the jit cache compiles once more, so the
        storm signal must count it — but there is no fresh AOT
        measurement to feed the compile histogram."""
        with self._lock:
            self.compiles += 1

    def storm_signal(self) -> int:
        """Fresh UNPLANNED compiles so far — the compile_storm rule's
        input (obs/health). Disk-cache replays and pre-warm compiles
        are excluded: a mass boot replay must not fire the alert."""
        with self._lock:
            return self.compiles - self.planned_compiles

    def get_or_build(self, key: tuple, build):
        with self._lock:
            entry = self._fns.get(key)
            if entry is not None:
                self.hits += 1
                if self._hits_c is not None:
                    self._hits_c.inc()
                return entry
            self.misses += 1
            if self._misses_c is not None:
                self._misses_c.inc()
            t0 = time.perf_counter()
            fn = build()
            record = {
                "key": _key_repr(key),
                "build_s": round(time.perf_counter() - t0, 6),
                # filled in on the entry's first invocation (or warm):
                # source records disk-deserialize vs fresh compile
                "trace_s": None, "compile_s": None, "method": None,
                "source": None, "deserialize_s": None,
                "created_unix": time.time(),
            }
            entry = self._fns[key] = _Entry(fn, record, self._measured,
                                            aot=self.aot, key=key,
                                            on_fallback=self._fallback)
            return entry

    def __len__(self) -> int:
        with self._lock:
            return len(self._fns)

    def snapshot(self) -> dict:
        """JSON-safe stats for the status API. (Schema frozen — the
        ledger rides status_snapshot()'s own `compile_ledger` key, see
        ledger_snapshot(); the disk tier's stats ride its `aot_cache`
        key.)"""
        with self._lock:
            return {"entries": len(self._fns), "hits": self.hits,
                    "misses": self.misses}

    def executables(self) -> list:
        """The executable of every entry that has run or been warmed,
        oldest first: a jax Compiled, or None for an entry that serves
        through plain jit (nothing to inspect)."""
        with self._lock:
            entries = sorted(self._fns.values(),
                             key=lambda e: e.record["created_unix"])
        return [e.compiled for e in entries
                if e.record.get("source") is not None]

    def ledger_snapshot(self) -> list[dict]:
        """Per-entry compile-cost records, oldest first. `trace_s` /
        `compile_s` are None until the entry's first invocation has
        measured them; `source` says disk|compile once it has."""
        with self._lock:
            entries = list(self._fns.values())
        return sorted((dict(e.record) for e in entries),
                      key=lambda r: r["created_unix"])


def _key_repr(key: tuple) -> str:
    """A stable human-readable form of a cache key (tuples of scalars
    by construction; keep it JSON-safe)."""
    return "/".join(str(k) for k in key)
