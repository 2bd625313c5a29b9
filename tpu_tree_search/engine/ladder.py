"""Chunk-ladder execution: pool-aware rung selection for the
segmented distributed driver.

The tuned chunk is only optimal at STEADY STATE: bench.py documents
that ramp and drain phases "pop underfilled chunks for hundreds of
steps" at the fixed big chunk — every one of those steps pays the full
chunk-wide bound kernels for parents that are not there. The ladder
pre-compiles 2–3 chunk rungs per executor key (each its own
ExecutorCache/AOT entry, so switching never retraces) and switches
rungs ONLY at segment boundaries, driven by the live pool-occupancy
signal the per-segment counter fetch already carries: ramp-up and
drain run small-chunk steps, the filled middle runs the tuned chunk.

Correctness story:

- Every rung's compiled loop is built against ONE unified usable-row
  limit (the minimum over rungs of each rung's scratch-margin +
  balance-headroom bound — engine/distributed._ladder_plan), so a
  state committed by any rung is in-bounds for every other rung and a
  switch in either direction can never clamp a block write onto live
  rows.
- Rung choice only changes which compiled program runs a segment —
  pool contents, counters and the incumbent ride the same SearchState
  untouched, so node accounting is exact across every switch (the
  audit invariants hold; tests pin TTS_AUDIT_HARD across switches).
- `TTS_LADDER` is a STATIC flag: off (the default) takes the
  pre-ladder single-driver path bit-identically; on, a fixed-incumbent
  run (ub=opt) explores the identical node set — the explored tree is
  order-independent when the incumbent never moves.
- The live rung rides checkpoint meta (``ladder_rung``): resume starts
  on the recorded rung instead of re-deriving it from a pool snapshot
  that the warm-up/occupancy heuristic would misread.

Observability: ``tts_ladder_switches_total{direction=up|down}`` in the
process-global registry and ``ladder.start`` / ``ladder.switch``
flight-recorder events (segment, pool, from/to chunks).
"""

from __future__ import annotations

import threading

from ..obs import metrics as obs_metrics
from ..obs import tracelog

__all__ = ["RungController", "rungs_for", "min_rung_for",
           "rungs_from_profile",
           "set_memory_pressure", "memory_pressure",
           "LADDER_FACTOR", "LADDER_RUNGS", "LADDER_MIN_CHUNK",
           "LADDER_MIN_CHUNK_LB2"]

# process-wide memory-pressure hint (the remediation controller's
# mem_headroom action raises it, the alert's resolution clears it).
# Under pressure the controller holds the smallest COVERING rung —
# the ramp-momentum bump one rung above covering is suppressed, so the
# next segments run the narrowest per-iteration scratch that still
# pops exactly what the tuned chunk would. Covering-rung pops are
# pool-limited identically across rungs, so node accounting stays
# bit-identical with the hint on or off — it trades only adaptation
# latency for headroom. A threading.Event, not a flag under a lock:
# the readers are per-segment host callbacks.
_MEM_PRESSURE = threading.Event()


def set_memory_pressure(on: bool) -> None:
    """Raise/clear the demote-the-ladder hint (service/remediate)."""
    if on:
        _MEM_PRESSURE.set()
    else:
        _MEM_PRESSURE.clear()


def memory_pressure() -> bool:
    return _MEM_PRESSURE.is_set()

# rung geometry: LADDER_RUNGS rungs, each LADDER_FACTOR× the previous,
# topped by the tuned chunk (pow2 factor keeps every rung lane-aligned
# like the tuned chunk itself); rungs below the floor collapse into
# it. chunk <= floor * FACTOR yields a single rung and the ladder
# degrades to the plain driver.
#
# The floor is MEASURED, per bound: sub-lane chunks compile to
# programs whose per-iteration cost INVERTS the ladder's premise —
# on the 8-dev CPU mesh the LB2 pair-sweep loop costs 220 ms/iter at
# chunk 64 vs 15 ms/iter at 256 (the prefilter tail vectorizes below
# the lane width); LB1 at 64 stays cheap (9.6 ms/iter). A rung that
# is slower per iteration than the tuned chunk is a pure loss, so LB2
# never rungs below 256 and the cheap bounds never below 64.
LADDER_FACTOR = 4
LADDER_RUNGS = 3
LADDER_MIN_CHUNK = 64
LADDER_MIN_CHUNK_LB2 = 256


def min_rung_for(lb_kind: int) -> int:
    """The measured per-bound rung floor (see the note above)."""
    return LADDER_MIN_CHUNK_LB2 if lb_kind == 2 else LADDER_MIN_CHUNK


def rungs_for(chunk: int, n_rungs: int = LADDER_RUNGS,
              factor: int = LADDER_FACTOR,
              min_chunk: int = LADDER_MIN_CHUNK) -> tuple[int, ...]:
    """The ascending rung chunks under (and including) `chunk`."""
    chunk = int(chunk)
    rungs = {max(min_chunk, chunk // factor ** k)
             for k in range(n_rungs)}
    return tuple(sorted(min(r, chunk) for r in rungs))


def _profile_rows(profile) -> dict:
    """Normalize a per-rung tuning profile (tune/defaults
    Params.rung_modes — a tuple of {"chunk", "ms_per_iter",
    "evals_per_s"} dicts, JSON-roundtripped through the TuningCache)
    into a chunk-keyed dict. Malformed rows are dropped, not fatal — a
    stale cache entry must degrade to the static floors, never crash a
    boot."""
    rows = {}
    for r in (profile or ()):
        try:
            rows[int(r["chunk"])] = r
        except (TypeError, KeyError, ValueError):
            continue
    return rows


def rungs_from_profile(chunk: int, profile,
                       n_rungs: int = LADDER_RUNGS,
                       factor: int = LADDER_FACTOR
                       ) -> tuple[int, ...] | None:
    """MEASURED rung admission — the per-shape subsumption of the
    static per-bound floor (min_rung_for): when the tuner probed this
    shape's rung ladder (Params.rung_modes, tune/tuner), a candidate
    rung joins the ladder iff its measured ms/iter beats the tuned top
    rung's. A rung slower per iteration than the tuned chunk is a pure
    loss — the ladder's premise; the PR-9 LB2>=256 floor encoded that
    statically from one measurement, here it is per-shape data. Returns None (caller
    falls back to the static floors) when the profile does not cover
    the top rung."""
    rows = _profile_rows(profile)
    chunk = int(chunk)
    top = rows.get(chunk)
    if top is None:
        return None
    top_ms = top.get("ms_per_iter")
    if not top_ms:
        return None
    rungs = {chunk}
    for k in range(1, n_rungs):
        c = max(1, chunk // factor ** k)
        row = rows.get(c)
        if row is None:
            continue
        ms = row.get("ms_per_iter")
        if ms and ms < top_ms:
            rungs.add(c)
    return tuple(sorted(rungs))


class RungController:
    """Owns the live rung index; the segmented driver's run_fn asks it
    for the current rung's driver and the heartbeat feeds it each
    segment's pool occupancy. Host-side only — nothing here is traced.

    Under overlap the next segment is dispatched before the previous
    segment's counters land, so the controller's signal lags one
    segment; a switch is therefore taken one boundary later than in
    sync mode — the accounting stays exact either way, only the
    adaptation latency differs.
    """

    def __init__(self, drivers: dict[int, object], n_workers: int):
        self.chunks = tuple(sorted(drivers))
        self.drivers = drivers
        self.n_workers = max(int(n_workers), 1)
        self.idx = len(self.chunks) - 1          # start on the tuned rung
        self.switches = {"up": 0, "down": 0}
        self._last_pool: int | None = None
        self._switch_c = obs_metrics.default().counter(
            "tts_ladder_switches_total",
            "chunk-ladder rung switches at segment boundaries")

    # ------------------------------------------------------------ state

    @property
    def current_chunk(self) -> int:
        return self.chunks[self.idx]

    def driver(self):
        return self.drivers[self.current_chunk]

    # ---------------------------------------------------------- control

    def start(self, pool_total: int, meta_rung: int | None = None) -> None:
        """Pick the initial rung: the checkpoint's recorded rung when
        resuming (`meta_rung`), else from the seed pool's occupancy."""
        if meta_rung is not None and int(meta_rung) in self.chunks:
            self.idx = self.chunks.index(int(meta_rung))
            source = "meta"
        else:
            self.idx = self._target(pool_total)
            source = "occupancy"
        self._last_pool = int(pool_total)
        tracelog.event("ladder.start", rung=self.current_chunk,
                       rungs=list(self.chunks), pool=int(pool_total),
                       source=source)

    def observe(self, pool_total: int, segment: int | None = None) -> None:
        """Feed one segment boundary's pool size; may switch the rung
        used for the NEXT dispatch."""
        target = self._target(pool_total)
        if (self._last_pool is not None
                and pool_total > 2 * max(self._last_pool, 1)
                and not memory_pressure()):
            # ramp momentum: the pool at least doubled inside the last
            # segment, so the boundary snapshot is already stale — go
            # one rung above covering to cut the chase (an explosive
            # warm-up otherwise costs one under-rung segment per
            # doubling). Suppressed under the remediation tier's
            # memory-pressure hint: covering is the demoted,
            # narrowest-scratch choice and pops identically
            target = min(target + 1, len(self.chunks) - 1)
        self._last_pool = int(pool_total)
        if target == self.idx:
            return
        direction = "up" if target > self.idx else "down"
        self.switches[direction] += 1
        tracelog.event("ladder.switch",
                       frm=self.current_chunk,
                       to=self.chunks[target],
                       direction=direction, segment=segment,
                       pool=int(pool_total))
        self._switch_c.inc(direction=direction)
        self.idx = target

    def _target(self, pool_total: int) -> int:
        """The SMALLEST rung that still covers the per-worker pool
        (the top rung when even it is outgrown). Covering means the
        rung pops exactly what the tuned chunk would have popped — a
        pool-limited pop either way — so the iteration count can NEVER
        inflate relative to the fixed-chunk driver; the ladder's win
        is purely the narrower per-iteration compute. (The earlier
        half-occupancy policy allowed pops smaller than the pool and
        measurably LOST on iteration inflation — 12 vs 8 iterations
        at 1024 on the small-instance drill.)"""
        per_worker = pool_total / self.n_workers
        for i, c in enumerate(self.chunks):
            if c >= per_worker:
                return i
        return len(self.chunks) - 1

    def snapshot(self) -> dict:
        return {"rungs": list(self.chunks),
                "current": self.current_chunk,
                "switches": dict(self.switches)}
