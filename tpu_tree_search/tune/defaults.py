"""Measured dispatch defaults — the autotuner's fallback tier.

Before this table existed, the engine's dispatch knobs lived in three
places that drifted independently: `utils/config.PFSPConfig` shipped
`chunk=256 / balance_period=4` (the round-1 CLI defaults), bench.py
hardcoded `chunk=65536` (the round-5 single-chip retune after the bf16
one-hot matmul changed the cost structure), and the serving layer's
`SearchRequest` defaulted to `chunk=64` (sized for preemption latency
on shared submeshes). This module is the ONE table all three consume —
and the tier the Autotuner (tune/tuner.py) falls back to when no
probed entry exists for a shape.

Provenance of the rows (do not "clean up" these numbers without a
measurement on the chip). The builder records of the round-5 chip
sweeps were deleted in PR 21 (they ran through a retired remote
runtime); their rates are not measured on chip this round:

- ``bench`` 20x20 chunk 65536: the round-5 chip sweep picked 65536
  over 32768 after the bf16 act matmul made the pair sweeps cheaper
  (pow2 keeps the lanes aligned).
- ``balance_period=4`` everywhere: tools/bench_balance_period.py
  on-chip found the balance round's cost flat across periods, so the
  period is chosen for SPREAD (per-worker tree variation). The CPU
  mesh's preference for sparse periods is a host-serialized-
  collectives artifact; never retune this knob on the virtual mesh.
- ``serving`` chunk 64: the service's preemption/deadline reaction
  granularity — stop flags land at segment boundaries, and a
  65536-wide chunk on a small submesh makes every boundary (and every
  ramp/drain step) pay for parents that are not there.
- ``cli`` chunk 256: the reference-parity default
  (PFSP_lib.c:175-185's -M family), kept for command-line
  compatibility.

This module must stay import-light (stdlib only): utils/config imports
it at module load.
"""

from __future__ import annotations

import dataclasses

# the knob every context shares — measured on-chip, see provenance above
BALANCE_PERIOD_DEFAULT = 4

# per-context chunk defaults (the fallback row of the table below)
CLI_CHUNK_DEFAULT = 256
SERVING_CHUNK_DEFAULT = 64
BENCH_CHUNK_DEFAULT = 65536


@dataclasses.dataclass(frozen=True)
class Params:
    """One resolved dispatch configuration. ``transfer_cap`` None means
    "derive from chunk via distributed.balance_defaults" (one chunk,
    byte-budgeted); ``source`` records which tier produced it:
    ``default`` (this table), ``cache`` (a persisted tuned entry) or
    ``probe`` (freshly measured)."""

    chunk: int
    balance_period: int = BALANCE_PERIOD_DEFAULT
    transfer_cap: int | None = None
    source: str = "default"
    evals_per_s: float | None = None   # the winning probe's rate, when
    #                                    source is cache/probe
    rung_modes: tuple | None = None    # rung admission profile
    #   (source cache/probe only, gathered under TTS_TUNE_RUNGS): a
    #   tuple of {"chunk", "ms_per_iter", "evals_per_s"} rows for the
    #   winning chunk's ladder rungs, probed below the static rung
    #   floor too — engine/ladder.rungs_from_profile admits rungs from
    #   it (subsuming the static LB2 floor)


def shape_class(jobs: int, machines: int, problem: str = "pfsp",
                batch: int | None = None) -> str:
    """The shape-class label table rows key on. PFSP keeps the legacy
    Taillard-style ``JxM`` label (persisted tuning caches and the
    MEASURED rows predate the problem prefix); every other problem is
    namespaced ``problem:JxM`` so two workloads can never alias one
    measured row. A megabatched dispatch (``batch`` = the instance-axis
    width B > 1) appends ``@bB``: the batched loop's cost structure is
    its own (every member pops a chunk per iteration, so the effective
    parallel width is B x chunk), and a batched optimum must never
    alias — or silently fall back to — the solo row of the same
    shape."""
    label = f"{int(jobs)}x{int(machines)}"
    if problem != "pfsp":
        label = f"{problem}:{label}"
    if batch is not None and int(batch) > 1:
        label = f"{label}@b{int(batch)}"
    return label


# (context, shape_class) -> Params. Contexts: "bench" (single-chip
# throughput bench), "serving" (SearchServer request default), "cli"
# (reference-parity one-shot runs). Only MEASURED rows belong here;
# everything else resolves through _FALLBACK.
MEASURED: dict[tuple[str, str], Params] = {
    # ROUND5: the bf16-matmul retune, measured on ta021 (20x20) — the
    # whole 20-job family shares the cost structure (the pair sweep is
    # machine-count-bound, not job-count-bound)
    ("bench", "20x5"): Params(chunk=BENCH_CHUNK_DEFAULT),
    ("bench", "20x10"): Params(chunk=BENCH_CHUNK_DEFAULT),
    ("bench", "20x20"): Params(chunk=BENCH_CHUNK_DEFAULT),
    # MEGABATCH round (this PR, 8-dev CPU mesh, bench.py
    # pfsp_serve_rps): the small-instance serving mix the batch-former
    # targets — per-member chunk 64 at B=4/8/16 beat 128/256 (lockstep
    # ramp dominates; every member pays the widest member's underfilled
    # steps) and matched the solo row's reaction latency. Explicit rows
    # so the batched hot path never probes and never silently reads
    # the solo serving row.
    ("serving", "8x5@b4"): Params(chunk=SERVING_CHUNK_DEFAULT),
    ("serving", "8x5@b8"): Params(chunk=SERVING_CHUNK_DEFAULT),
    ("serving", "8x5@b16"): Params(chunk=SERVING_CHUNK_DEFAULT),
}

# megabatched serving (TTS_MEGABATCH): the per-member chunk of a
# batched dispatch. MEASURED on the 8-dev CPU mesh (this PR's
# megabatch round): at B=8 small instances per submesh the batched
# loop's effective parallel width is B x chunk, so the solo serving
# chunk (64) already saturates each member's shallow pools — larger
# per-member chunks only inflate the lockstep ramp (every member pays
# the widest member's underfilled steps). Re-measure on hardware
# before trusting this for big-B TPU batches.
SERVING_BATCH_CHUNK_DEFAULT = 64

_FALLBACK: dict[str, Params] = {
    "bench": Params(chunk=BENCH_CHUNK_DEFAULT),
    "serving": Params(chunk=SERVING_CHUNK_DEFAULT),
    "cli": Params(chunk=CLI_CHUNK_DEFAULT),
}

# the BATCHED serving fallback is its own explicit row: a batched
# dispatch that finds no measured/tuned entry must land on a value
# chosen FOR batched execution — falling through to the solo serving
# row silently would let a solo retune change every megabatch's cost
# structure without anyone measuring it
_FALLBACK_BATCHED = Params(chunk=SERVING_BATCH_CHUNK_DEFAULT)


def params_for(context: str, jobs: int | None = None,
               machines: int | None = None,
               problem: str = "pfsp",
               batch: int | None = None) -> Params:
    """Resolve the default dispatch params for a context, problem and
    shape — the tuner's fallback tier and the single source
    config/bench/serve read their chunk/balance_period defaults from.
    Only PFSP has measured rows today; other problems resolve through
    the per-context fallback until their own perf rounds land.

    ``batch`` (the megabatch instance-axis width) keys batched rows via
    :func:`shape_class`'s ``@bB`` suffix; with no batched row measured
    the resolution falls to the explicit batched serving fallback
    (``_FALLBACK_BATCHED``), NEVER silently to the solo serving row."""
    if context not in _FALLBACK:
        raise ValueError(f"unknown defaults context {context!r} "
                         f"(want one of {sorted(_FALLBACK)})")
    if jobs is not None and machines is not None:
        row = MEASURED.get((context, shape_class(jobs, machines,
                                                 problem, batch=batch)))
        if row is not None:
            return row
    if batch is not None and int(batch) > 1:
        return _FALLBACK_BATCHED
    return _FALLBACK[context]
