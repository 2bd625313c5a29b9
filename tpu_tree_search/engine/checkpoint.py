"""Checkpoint / resume for long searches.

The reference has no checkpointing at all — a killed multi-day run loses
everything (SURVEY.md §5: "Checkpoint/resume: none"). Because the TPU
engine's entire search state is a handful of plain tensors (the pool
arrays, cursors, incumbent, counters), snapshotting is trivial and cheap:
one host fetch + one compressed npz per interval.

`run_segmented` is the production driver: it runs the compiled loop in
bounded segments (max_iters at a time), checkpointing, heartbeat-printing
(the reference's 5000-iteration progress print, pfsp_gpu_cuda.c:324-330)
and stall-detecting between segments — the failure-detection layer the
reference also lacks.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import queue
import threading
import time
import warnings
import zipfile
import zlib

import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import tracelog
from ..utils import faults
from ..utils.retry import retry_call
from . import telemetry as tele
from .device import SearchState


POOL_FIELDS = ("prmu", "depth", "aux")

# Checkpoint schema version, embedded in every file. Loaders accept
# every version <= CURRENT (older layouts upgrade on load: row-major
# pools transpose, pre-aux files reconstruct); a file from a NEWER
# schema fails loudly (CheckpointSchemaError) instead of being
# misparsed as garbage state.
#   1 (implicit): row-major full-pool snapshots, no aux, no meta
#   2: feature-major live-row snapshots + capacity/pool_layout meta
#   3: = 2 plus embedded CRC32 + explicit schema version
SCHEMA_VERSION = 3

LAST_GOOD_SUFFIX = ".prev"


class CheckpointCorrupt(RuntimeError):
    """The checkpoint file is torn/corrupt (bad zip, CRC mismatch,
    missing members). load_resilient treats this as 'skip to the
    last-good snapshot', never 'resume wrong state'."""


class CheckpointSchemaError(RuntimeError):
    """The checkpoint was written by a NEWER schema than this build
    reads. Not corruption — falling back to an older snapshot would
    silently discard valid progress, so this is never swallowed."""


class SegmentTimeout(RuntimeError):
    """A segment exceeded its wall-clock watchdog. Deliberately NOT a
    transient error: a hung device dispatch does not unhang on retry —
    the caller (campaign supervisor) must kill and respawn the process."""


class StaleCheckpointError(RuntimeError):
    """An epoch-stale save was refused: the file on disk carries a
    NEWER lease epoch than the writer (fleet failover — a peer adopted
    this checkpoint family; see service/lease.py). Deliberately NOT
    transient: the stale owner must self-fence, never retry into a
    clobber."""


def _transient_errors() -> tuple:
    """Error types worth retrying: host/filesystem I/O, injected faults,
    and the runtime's transport errors (XlaRuntimeError)."""
    errs = [OSError, faults.InjectedFault]
    try:
        from jax.errors import JaxRuntimeError
        errs.append(JaxRuntimeError)
    except ImportError:
        pass
    return tuple(errs)


TRANSIENT_ERRORS = _transient_errors()


def _retry(fn, what: str, attempts: int, base_s: float):
    """Run `fn` with exponential-backoff retry on transient errors
    (utils/retry.retry_call bound to this module's TRANSIENT_ERRORS).
    Non-transient exceptions (wrong answers, schema errors, timeouts)
    propagate immediately — retrying a deterministic failure only
    delays the loud abort."""
    return retry_call(fn, what=what, attempts=attempts, base_s=base_s,
                      transient=TRANSIENT_ERRORS)


def _with_watchdog(fn, timeout_s: float | None, what: str):
    """Run `fn` under a wall-clock watchdog: raises SegmentTimeout if it
    exceeds `timeout_s` (None/0 disables). The work runs on a daemon
    thread so a genuinely hung device call cannot also hang process
    exit — the supervisor's kill+respawn remains the recovery path; the
    timeout just converts a silent infinite wait into a loud error."""
    if not timeout_s or timeout_s <= 0:
        return fn()
    box: dict = {}
    # the caller's fault plan must ride into the worker thread: a
    # thread-SCOPED plan (faults.scoped — the service's per-request
    # injection) lives in thread-local state the daemon thread cannot
    # see, and injection points inside fn (host_fetch) would silently
    # stop firing whenever the watchdog is armed
    plan = faults.active()

    def target():
        try:
            with faults.scoped(plan):
                box["result"] = fn()
        except BaseException as e:      # noqa: BLE001 — re-raised below
            box["error"] = e

    th = threading.Thread(target=target, daemon=True,
                          name="tts-segment-watchdog")
    th.start()
    th.join(timeout_s)
    if th.is_alive():
        raise SegmentTimeout(
            f"{what} exceeded the {timeout_s:.1f}s wall-clock watchdog "
            "(hung device dispatch?); kill and resume from the last "
            "checkpoint")
    if "error" in box:
        raise box["error"]
    return box["result"]


def _to_np(x) -> np.ndarray:
    """Host copy of a (possibly multihost-sharded) array: plain asarray
    single-controller; allgather the global value under multi-controller
    (where np.asarray on non-addressable shards raises)."""
    if not getattr(x, "is_fully_addressable", True):
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def _fetch_many(xs: tuple, fire: bool = True) -> tuple:
    """One batched device->host fetch of several small arrays. Every
    separate np.asarray is a full host-device roundtrip;
    a single device_get puts all transfers in flight together, so the
    batch costs ~one latency instead of len(xs). Multihost shards fall
    back to the collective allgather path per leaf.

    `fire=False` skips the fault-injection hook: checkpoint-state
    fetches reuse this batching but were never an injection point (the
    resilience tests' fail_host_fetch budgets count HEARTBEAT fetches),
    and the budget must not drift when the save path batches too."""
    if fire:
        faults.fire("host_fetch")  # deterministic transient-error hook
    if any(not getattr(x, "is_fully_addressable", True) for x in xs):
        return tuple(_to_np(x) for x in xs)
    import jax
    return tuple(np.asarray(v) for v in jax.device_get(xs))


def _payload_crc(arrays: dict) -> int:
    """CRC32 over every stored array's name, dtype, shape and raw bytes
    (sorted by name, `meta_crc32` itself excluded) — the end-to-end
    integrity check a torn write or bit flip cannot survive. The zip
    layer's per-member CRCs already catch most damage; this one also
    covers damage the zip container cannot see (a member swapped in
    whole, an interrupted rewrite that left a stale-but-valid zip)."""
    crc = 0
    for name in sorted(arrays):
        if name == "meta_crc32":
            continue
        a = np.ascontiguousarray(np.asarray(arrays[name]))
        crc = zlib.crc32(name.encode(), crc)
        crc = zlib.crc32(str(a.dtype).encode(), crc)
        crc = zlib.crc32(np.asarray(a.shape, np.int64).tobytes(), crc)
        crc = zlib.crc32(a.tobytes(), crc)
    return crc & 0xFFFFFFFF


def last_good_path(path: str | pathlib.Path) -> pathlib.Path:
    """The rotating last-good snapshot that rides beside `path`."""
    path = pathlib.Path(path)
    return path.with_name(path.name + LAST_GOOD_SUFFIX)


def resume_path(path: str | pathlib.Path) -> pathlib.Path | None:
    """The file a resume should try first: `path` if present, else its
    last-good sibling (the current file vanished mid-rotation), else
    None (nothing to resume — a stale .tmp from an interrupted first
    save is NOT resumable: it was never fsync'd + renamed, so its
    contents carry no durability promise)."""
    path = pathlib.Path(path)
    if path.exists():
        return path
    prev = last_good_path(path)
    return prev if prev.exists() else None


# checkpoint size buckets (bytes): tests write ~kB snapshots, production
# pools compress to tens-of-MB..GB
_BYTES_BUCKETS = (1e4, 1e5, 1e6, 1e7, 1e8, 1e9)

# segment-gap buckets (seconds): sub-ms when overlapped, up to the cost
# of a full heartbeat + checkpoint round when not
GAP_BUCKETS = (0.0005, 0.002, 0.01, 0.05, 0.2, 1.0, 5.0, 30.0)
GAP_HELP = ("device-idle gap between consecutive segments: dispatch of "
            "segment N+1 minus results-ready of segment N, clamped at 0 "
            "(TTS_OVERLAP drives this to ~0)")


def save(path: str | pathlib.Path, state: SearchState,
         meta: dict | None = None):
    """Snapshot a search state — flight-recorded wrapper around
    :func:`_save_impl` (one `checkpoint.save` span carrying the written
    byte count, plus save-latency/bytes histograms in the metrics
    registry). See `_save_impl` for the format and durability story."""
    with tracelog.span("checkpoint.save", path=str(path)) as sp:
        _save_impl(path, state, meta)
        nbytes = 0
        try:
            nbytes = os.path.getsize(path)
        except OSError:
            pass          # non-writer multihost rank, or racing rotate
        sp.set(bytes=nbytes)
    _record_save_metrics(sp.dur, nbytes)


def _record_save_metrics(dur: float, nbytes: int) -> None:
    """Post-write bookkeeping shared by the sync :func:`save` and the
    async writer thread — one definition so the two drivers' series
    (names, help, buckets) can never drift."""
    reg = obs_metrics.default()
    reg.counter("tts_checkpoint_saves_total",
                "checkpoint snapshots written").inc()
    reg.histogram("tts_checkpoint_save_seconds",
                  "checkpoint save latency (fetch+compress+fsync)"
                  ).observe(dur)
    if nbytes:
        reg.histogram("tts_checkpoint_bytes", "checkpoint file size",
                      buckets=_BYTES_BUCKETS).observe(nbytes)


def _save_impl(path: str | pathlib.Path, state: SearchState,
               meta: dict | None = None):
    """Snapshot a search state (single-device or stacked distributed).

    Only the live pool rows (below the cursor) are fetched and written —
    rows above the cursor are garbage by the engine invariant, and a
    production pool is orders of magnitude larger than its live region
    (fetching + compressing the full arrays made checkpoints cost more
    than the segments they protected). The declared capacity is kept in
    the file so load() re-homes the rows into an identical pool.

    Torn-write-proof by construction: the bytes (with an embedded CRC32
    + schema version) go to a temp file that is flushed and fsync'd
    BEFORE any rename; the previous snapshot rotates to a `.prev`
    last-good sibling and the temp file renames into place. A crash at
    any point leaves either the old snapshot, the rotated last-good, or
    the new snapshot — never a half-written file under the resume path
    (load_resilient picks the newest loadable one).
    """
    arrays = snapshot_arrays(state, meta)
    if arrays is None:
        return                           # non-writer multihost rank
    _write_snapshot(path, arrays)


def snapshot_arrays(state: SearchState, meta: dict | None = None
                    ) -> dict | None:
    """Fetch a state's live rows and assemble the checkpoint payload
    (everything up to, but not including, the schema/CRC stamps). The
    host half of a save, split out so the async writer path can run it
    on the DISPATCH thread — while the device arrays are still valid —
    and hand the host arrays to the writer thread for the compress +
    fsync half (:func:`_write_snapshot`).

    The fetch is ONE batched device_get of every live-row slice — the
    per-leaf roundtrips (len(fields) host-device latencies) collapse
    to one.

    Returns None on non-writer multihost ranks: every rank must reach
    this point (the fetches are collective allgathers there), but only
    process 0 may write — concurrent writes + renames of one tmp file
    on a shared filesystem can corrupt or race the checkpoint."""
    sizes = np.atleast_1d(_to_np(state.size))
    n = int(sizes.max())
    leaves = tuple(x[..., :n] if f in POOL_FIELDS else x
                   for f, x in zip(SearchState._fields, state))
    arrays = dict(zip(SearchState._fields,
                      _fetch_many(leaves, fire=False)))
    arrays["meta_capacity"] = np.asarray(state.prmu.shape[-1])
    arrays["meta_pool_layout"] = np.asarray(1)   # 1 = feature-major
    if meta:
        reserved = {"capacity", "pool_layout", "schema_version", "crc32"} \
            & meta.keys()
        if reserved:
            raise ValueError(f"meta keys {sorted(reserved)} are reserved "
                             "by the checkpoint format")
        for k, v in meta.items():
            arrays[f"meta_{k}"] = np.asarray(v)
    import jax
    if jax.process_index() != 0:
        return None
    return arrays


def _existing_lease_epoch(path: pathlib.Path) -> int | None:
    """Best-effort peek of an on-disk snapshot's ``meta_lease_epoch``
    stamp. Absent file, absent stamp, or an unreadable file (mid-crash
    torso — load_resilient's problem, not the fence's) all yield None:
    the fence only refuses when it can PROVE the disk is newer."""
    try:
        with np.load(path) as z:
            if "meta_lease_epoch" in z.files:
                return int(z["meta_lease_epoch"])
    except Exception:  # noqa: BLE001 — any unreadable existing file
        return None    # means "nothing provably newer": proceed
    return None


def _write_snapshot(path: str | pathlib.Path, arrays: dict) -> None:
    """The durable half of a save: stamp schema + CRC, write to a temp
    file, fsync, rotate current -> `.prev` last-good, rename into
    place, fsync the directory. Pure host work on already-fetched
    arrays — exactly what the async checkpoint writer runs off the
    dispatch thread. Idempotent w.r.t. retry (stamps overwrite)."""
    arrays["meta_schema_version"] = np.asarray(SCHEMA_VERSION)
    arrays["meta_crc32"] = np.asarray(_payload_crc(arrays), np.uint32)
    path = pathlib.Path(path)
    # fencing (fleet failover): a save carrying a lease-epoch stamp
    # first peeks the on-disk file's stamp and REFUSES to overwrite a
    # newer one — a fenced-out stale owner can never clobber its
    # adopter's snapshot, even if timing slips. Saves without the
    # stamp (every non-fleet run) pay nothing.
    inc = arrays.get("meta_lease_epoch")
    if inc is not None:
        existing = _existing_lease_epoch(path)
        if existing is not None and existing > int(inc):
            raise StaleCheckpointError(
                f"{path}: on-disk checkpoint carries lease epoch "
                f"{existing} > writer's {int(inc)} — refusing the "
                "stale save")
    tmp = path.with_suffix(".tmp.npz")
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    # rotate current -> last-good, then temp -> current. Both renames
    # are atomic; a kill between them leaves no current file and
    # resume_path/load_resilient fall back to the last-good sibling.
    if path.exists():
        os.replace(path, last_good_path(path))
    os.replace(tmp, path)
    try:
        # fsync the directory so the renames themselves are durable
        # (without it a power loss can resurrect the pre-rename view)
        dfd = os.open(path.parent or pathlib.Path("."), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass   # not every filesystem supports directory fsync


class AsyncCheckpointWriter:
    """Single writer thread that takes checkpoint serialization + fsync
    off the segment dispatch thread (half of TTS_OVERLAP — see
    :func:`run_segmented`).

    Ordering and durability:

    - ONE thread, FIFO queue: writes land in submission order, so the
      current/``.prev`` rotation invariant of :func:`_write_snapshot`
      holds exactly as in the sync path — the last-good sibling is
      always the previous successfully written snapshot, never dropped
      or reordered;
    - the queue is BOUNDED (config.ASYNC_CKPT_QUEUE_DEPTH): a dispatch
      thread outrunning the disk blocks in :meth:`enqueue` —
      back-pressure, never an unbounded buffer of multi-MB snapshots
      and never a silently dropped write;
    - the host-fetch half (:func:`snapshot_arrays`) runs on the CALLING
      thread via :meth:`prepare` — the device arrays may be donated to
      the next segment's dispatch immediately afterwards — and only
      the compress + fsync + rotate half crosses the thread;
    - :meth:`drain` blocks until everything queued is ON DISK and
      re-raises the first writer-side error; every overlapped exit path
      drains before returning, so a returned state always has its final
      checkpoint durable (the same contract the sync path gives).

    The writer re-installs the submitting thread's fault plan and trace
    context (request id kept; ``submesh`` dropped so its spans render
    on a dedicated ``tts-ckpt-writer`` Perfetto lane) and runs the same
    post-write hooks the sync path runs, in the same order: the
    checkpoint-roundtrip audit — against counter sums captured at
    prepare() time, so the conservation check spans the async edge —
    and then the ``post_checkpoint`` fault injection."""

    def __init__(self, retry_attempts: int | None = None,
                 retry_base_s: float | None = None,
                 max_pending: int | None = None):
        from ..utils import config as _cfg
        if retry_attempts is None:
            retry_attempts = _cfg.env_int("TTS_RETRY_ATTEMPTS")
        if retry_base_s is None:
            retry_base_s = _cfg.env_float("TTS_RETRY_BASE_S")
        self.retry_attempts = retry_attempts
        self.retry_base_s = retry_base_s
        self._q: queue.Queue = queue.Queue(
            maxsize=max_pending or _cfg.ASYNC_CKPT_QUEUE_DEPTH)
        # the AOTCache close discipline, with TWO locks on purpose:
        # _close_lock makes the closed-check + enqueue atomic against
        # close() (a task slipped in AFTER the shutdown sentinel would
        # never run its task_done, hanging a later drain) — the writer
        # thread NEVER takes it, so a submit blocked on the bounded
        # queue while holding it still drains; _err_lock serializes the
        # error hand-off between the writer and the submitting side. A
        # single shared lock would deadlock: a producer holding it
        # while blocked in the full queue's put() and the writer's
        # error path wanting it before task_done() is an ABBA cycle
        # between the lock and the queue capacity.
        self._close_lock = threading.Lock()
        self._err_lock = threading.Lock()
        self._err: BaseException | None = None   # guarded-by: self._err_lock
        self._closed = False                     # guarded-by: self._close_lock
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tts-ckpt-writer")
        self._thread.start()

    # ------------------------------------------------- submitting side

    def prepare(self, path, state: SearchState, meta: dict | None = None,
                segment: int | None = None) -> dict | None:
        """Fetch + assemble the snapshot on the CALLING thread (the
        arrays must be read before the pools are donated onward).
        Returns the task for :meth:`enqueue` — or None when this rank
        must not write (non-writer multihost process)."""
        from ..obs import audit as obs_audit
        arrays = snapshot_arrays(state, meta)
        if arrays is None:
            return None
        sums = None
        if obs_audit.roundtrip_enabled():
            host = SearchState(*(arrays[f] for f in SearchState._fields))
            sums = obs_audit.state_sums(host)
        ctx = {**tracelog.current_context(), "submesh": None}
        return {"path": str(path), "arrays": arrays, "sums": sums,
                "segment": segment, "plan": faults.active(), "ctx": ctx}

    def enqueue(self, task: dict | None) -> None:
        """Queue a prepared task; blocks at the back-pressure bound.
        Re-raises the first pending writer-side error first (an
        earlier failed write must not be papered over by later ones)."""
        self._raise_pending()
        if task is None:
            return
        with self._close_lock:
            if self._closed:
                raise RuntimeError("AsyncCheckpointWriter is closed")
            self._q.put(task)

    def submit(self, path, state: SearchState, meta: dict | None = None,
               segment: int | None = None) -> None:
        """prepare() + enqueue() in one call."""
        self.enqueue(self.prepare(path, state, meta, segment=segment))

    def drain(self) -> None:
        """Block until every queued snapshot is on disk; re-raise the
        first writer-side error (a failed final save must fail the run,
        exactly as the sync path would)."""
        self._q.join()
        self._raise_pending()

    def close(self, raise_pending: bool = True) -> None:
        """Drain, stop the thread, optionally surface pending errors
        (False on exception-unwind paths, where masking the original
        error with a writer error would hide the root cause)."""
        with self._close_lock:
            was_closed = self._closed
            if not was_closed:
                self._closed = True
                self._q.put(None)
        if not was_closed:
            self._thread.join()
        if raise_pending:
            self._raise_pending()

    def _raise_pending(self) -> None:
        with self._err_lock:
            err, self._err = self._err, None
        if err is not None:
            raise err

    # ---------------------------------------------------- writer thread

    def _loop(self) -> None:
        while True:
            task = self._q.get()
            try:
                if task is None:
                    return
                self._write_one(task)
            except BaseException as e:  # noqa: BLE001 — surfaced at the
                with self._err_lock:    # next enqueue()/drain()
                    if self._err is None:
                        self._err = e
            finally:
                self._q.task_done()

    def _write_one(self, task: dict) -> None:
        path = task["path"]
        with faults.scoped(task["plan"]), \
                tracelog.get().context(**task["ctx"]):
            with tracelog.span("checkpoint.save", path=path,
                               async_write=True) as sp:
                _retry(lambda: _write_snapshot(path, task["arrays"]),
                       "checkpoint save", self.retry_attempts,
                       self.retry_base_s)
                nbytes = 0
                try:
                    nbytes = os.path.getsize(path)
                except OSError:
                    pass
                sp.set(bytes=nbytes)
            _record_save_metrics(sp.dur, nbytes)
            from ..obs import audit as obs_audit
            if task["sums"] is not None:
                # audit BEFORE the fault injection below, same order as
                # the sync do_save: the injected corruption is a
                # load-side drill, not a write-side failure
                obs_audit.check_checkpoint_roundtrip(path, task["sums"])
            faults.fire("post_checkpoint", segment=task["segment"],
                        path=path)


def load(path: str | pathlib.Path,
         p_times: np.ndarray | None = None) -> tuple[SearchState, dict]:
    """Load a snapshot, verifying integrity first. Pre-aux checkpoints
    (before the pool carried per-node [front | remain] tables) are
    upgraded on load by reconstructing aux from the live rows — pass the
    instance's `p_times` for that; without it such files raise a clear
    error.

    Raises CheckpointCorrupt on a torn/damaged file (bad zip, CRC
    mismatch, missing members — every read error, so a caller never
    resumes wrong state) and CheckpointSchemaError on a file written by
    a newer schema than this build reads."""
    with tracelog.span("checkpoint.load", path=str(path)):
        obs_metrics.default().counter(
            "tts_checkpoint_loads_total",
            "checkpoint load attempts").inc()
        return _load_impl(path, p_times=p_times)


def _load_impl(path: str | pathlib.Path,
               p_times: np.ndarray | None = None
               ) -> tuple[SearchState, dict]:
    path = pathlib.Path(path)
    try:
        with np.load(path) as z:
            # full materialization doubles as the zip-member CRC pass
            # (zipfile verifies each member's own CRC as it inflates)
            raw = {k: z[k] for k in z.files}
    except (zipfile.BadZipFile, zlib.error, OSError, EOFError, ValueError,
            KeyError) as e:
        # zipfile errors can embed whole raw headers — keep the reason
        # human-sized, the chained exception preserves the full detail
        reason = str(e)
        if len(reason) > 200:
            reason = reason[:200] + "... [truncated]"
        raise CheckpointCorrupt(
            f"checkpoint {path} is unreadable (torn write or "
            f"corruption): {reason}") from e
    version = int(raw.get("meta_schema_version", 2 if "meta_capacity"
                          in raw else 1))
    if version > SCHEMA_VERSION:
        raise CheckpointSchemaError(
            f"checkpoint {path} uses schema version {version}; this "
            f"build reads <= {SCHEMA_VERSION} — upgrade the reader, do "
            "not fall back to an older snapshot")
    if "meta_crc32" in raw:
        want = int(raw["meta_crc32"])
        got = _payload_crc(raw)
        if got != want:
            raise CheckpointCorrupt(
                f"checkpoint {path} failed its embedded CRC32 "
                f"(stored {want:#010x}, recomputed {got:#010x})")
    missing = [f for f in SearchState._fields
               if f not in ("aux", "telemetry") and f not in raw]
    if missing:
        raise CheckpointCorrupt(
            f"checkpoint {path} is missing state fields {missing} "
            "(truncated or partial write)")
    arrays = {f: raw[f] for f in SearchState._fields if f in raw}
    meta = {k[5:]: raw[k] for k in raw if k.startswith("meta_")}
    meta.pop("schema_version", None)
    meta.pop("crc32", None)
    feature_major = bool(meta.pop("pool_layout", 0))
    if not feature_major:
        # legacy row-major snapshot: transpose pool matrices on load; a
        # legacy aux held [front | remain] — the pool now carries only
        # front (remain is reconstructed in-kernel), so keep the first
        # half of its rows
        for f in ("prmu", "aux"):
            if f in arrays:
                arrays[f] = np.swapaxes(arrays[f], -1, -2).copy()
        if "aux" in arrays and arrays["aux"].shape[-2] > 0:
            m = arrays["aux"].shape[-2] // 2
            arrays["aux"] = arrays["aux"][..., :m, :].copy()
    if "capacity" in meta:
        # live-row snapshot: re-home into the declared capacity
        capacity = int(meta.pop("capacity"))
        for f in POOL_FIELDS:
            if f not in arrays:
                continue
            x = arrays[f]
            pad = capacity - x.shape[-1]
            if pad > 0:
                widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
                arrays[f] = np.pad(x, widths)
    if "aux" not in arrays:
        if p_times is None:
            raise ValueError(
                f"{path} is a pre-aux checkpoint; pass p_times to load() "
                "so the per-node pool tables can be reconstructed")
        from ..ops import reference as ref
        prmu = arrays["prmu"]            # feature-major (/, jobs, rows)
        depth = arrays["depth"]
        size = np.atleast_1d(arrays["size"])
        stacked = prmu.ndim == 3
        m = p_times.shape[0]
        aux = np.zeros(prmu.shape[:-2] + (m, prmu.shape[-1]), np.int32)
        for d in range(prmu.shape[0] if stacked else 1):
            n = int(size[d if stacked else 0])
            if stacked:
                aux[d, :, :n] = ref.prefix_front_remain(
                    p_times, prmu[d, :, :n].T, depth[d, :n])[:, :m].T
            else:
                aux[:, :n] = ref.prefix_front_remain(
                    p_times, prmu[:, :n].T, depth[:n])[:, :m].T
        arrays["aux"] = aux
    if "telemetry" not in arrays:
        # pre-telemetry snapshot: reconstruct a zeroed block at the
        # CURRENT flag's width (counters restart from the resume; the
        # saved pool/counter state is untouched either way)
        lead = (arrays["prmu"].shape[0],) if arrays["prmu"].ndim == 3 \
            else ()
        arrays["telemetry"] = np.zeros(lead + (tele.enabled_width(),),
                                       np.int64)
    state = SearchState(*(jnp.asarray(arrays[f])
                          for f in SearchState._fields))
    return state, meta


def load_resilient(path: str | pathlib.Path,
                   p_times: np.ndarray | None = None
                   ) -> tuple[SearchState, dict, pathlib.Path]:
    """Load `path`, falling back to its rotating last-good sibling when
    the current file is torn/corrupt (or missing after an interrupted
    rotation). Returns (state, meta, loaded_path) — callers that priced
    anything off the file (aux dtype, capacity) must use `loaded_path`,
    not `path`.

    A corrupt current snapshot costs at most the work since the
    PREVIOUS checkpoint; it never poisons the run. Only when every
    candidate is unreadable does this raise, listing what was tried.
    CheckpointSchemaError is deliberately not caught: a valid
    newer-schema file must not be silently shadowed by an older one."""
    path = pathlib.Path(path)
    candidates = [path, last_good_path(path)]
    errors = []
    for cand in candidates:
        if not cand.exists():
            errors.append(f"{cand}: missing")
            continue
        try:
            state, meta = load(cand, p_times=p_times)
        except CheckpointCorrupt as e:
            warnings.warn(
                f"skipping corrupt checkpoint {cand}: {e}",
                RuntimeWarning, stacklevel=2)
            errors.append(f"{cand}: {e}")
            tracelog.event("checkpoint.corrupt", path=str(cand),
                           error=str(e)[:200])
            obs_metrics.default().counter(
                "tts_checkpoint_corrupt_total",
                "torn/corrupt snapshots skipped on load").inc()
            if cand == path:
                # Quarantine the torn CURRENT file: leaving it in place
                # lets the next save() rotate it over the good
                # last-good, and a crash between save's two renames
                # would then leave nothing loadable at all. Renamed
                # aside (not unlinked) so the damage stays available
                # for forensics. Process 0 only — on a multi-controller
                # shared filesystem every process runs this resume path
                # and concurrent renames of one file race.
                try:
                    import jax
                    if jax.process_index() == 0:
                        os.replace(cand, str(cand) + ".corrupt")
                        tracelog.event("checkpoint.quarantine",
                                       path=str(cand) + ".corrupt")
                        obs_metrics.default().counter(
                            "tts_checkpoint_quarantines_total",
                            "torn current snapshots renamed aside").inc()
                except OSError:
                    pass
            continue
        if cand != path:
            warnings.warn(
                f"resuming from last-good snapshot {cand} (current "
                "checkpoint torn/missing); work since the previous "
                "checkpoint interval will be redone",
                RuntimeWarning, stacklevel=2)
            tracelog.event("checkpoint.rollback", path=str(cand),
                           wanted=str(path))
            obs_metrics.default().counter(
                "tts_checkpoint_rollbacks_total",
                "resumes served by the rotating last-good sibling").inc()
        return state, meta, cand
    raise CheckpointCorrupt(
        "no loadable checkpoint: " + "; ".join(errors))


def reshard_state(state: SearchState, new_workers: int,
                  squeeze: bool = False) -> SearchState:
    """Elastic resume: re-home an N-worker stacked snapshot (or a
    single-device one) onto `new_workers` pools, so a preempted job
    restarts on whatever slice is available (M < N and M > N both
    work — the failure mode real fleets actually have is "came back
    with a different topology").

    Host-side and lossless: every worker's live rows (rows [0, size) by
    the pool invariant) are concatenated and round-robin striped across
    the M new pools — the same water-filling split the balance
    exchange converges to (parallel/balance.waterfill_counts: per-pool
    counts differ by <= 1) and the same striping idiom as warm-up
    seeding (distributed._shard_frontier). Capacity doubles as needed
    so the widest stripe fits; callers with tighter usable-row limits
    (scratch margins, balance headroom) grow() further on top.

    Counter semantics across the reshard:
    - tree/sol/evals/sent/recv/steals: global totals preserved — summed
      onto worker 0 (only the totals are ever reported; per-worker
      attribution does not survive a topology change by definition);
    - iters: replicated at the old max, so a cumulative per-worker
      iteration ceiling keeps meaning "this much MORE work per worker";
    - best: min-replicated (the incumbent is global);
    - overflow: cleared — the resumed run's first step re-detects a
      genuinely over-full pool via the same lossless no-commit path.

    `squeeze=True` with new_workers=1 returns an UNSTACKED single-device
    state (the shape device.run expects) instead of a (1, ...) stack.
    """
    if new_workers < 1:
        raise ValueError(f"new_workers must be >= 1, got {new_workers}")
    if squeeze and new_workers != 1:
        raise ValueError("squeeze=True requires new_workers == 1")
    from ..parallel import balance as bal

    arrs = SearchState(*(np.asarray(x) for x in state))
    if arrs.prmu.ndim == 2:            # single-device snapshot: lift
        arrs = SearchState(*(a[None, ...] for a in arrs))
    if arrs.prmu.ndim != 3:
        raise ValueError(
            f"reshard_state needs a (D, jobs, capacity) stacked or "
            f"(jobs, capacity) single-device pool, got {arrs.prmu.shape}")
    D, jobs, capacity = arrs.prmu.shape
    A = arrs.aux.shape[1]
    M = new_workers
    if M != D:
        tracelog.event("elastic_reshard", old_workers=int(D),
                       new_workers=int(M))
        obs_metrics.default().counter(
            "tts_elastic_reshards_total",
            "checkpoints re-homed onto a different worker count").inc()
    sizes = np.atleast_1d(arrs.size).astype(np.int64)

    # concatenate live rows in worker order (bottom-to-top per pool)
    live_prmu = np.concatenate(
        [arrs.prmu[d, :, :sizes[d]] for d in range(D)], axis=1)
    live_depth = np.concatenate(
        [arrs.depth[d, :sizes[d]] for d in range(D)])
    live_aux = np.concatenate(
        [arrs.aux[d, :, :sizes[d]] for d in range(D)], axis=1)

    total = int(sizes.sum())
    counts = bal.waterfill_counts(total, M)
    while counts.max() > capacity:
        capacity *= 2

    prmu = np.zeros((M, jobs, capacity), arrs.prmu.dtype)
    depth = np.zeros((M, capacity), arrs.depth.dtype)
    aux = np.zeros((M, A, capacity), arrs.aux.dtype)
    for m in range(M):
        stripe = slice(m, None, M)     # round-robin, water-filled
        n = int(counts[m])
        prmu[m, :, :n] = live_prmu[:, stripe]
        depth[m, :n] = live_depth[stripe]
        aux[m, :, :n] = live_aux[:, stripe]

    def on_zero(total_val, dtype):
        v = np.zeros(M, dtype)
        v[0] = total_val
        return v

    # telemetry follows the tree/sol rule: global totals preserved,
    # merged onto worker 0 (counts summed, pool high-water maxed, the
    # incumbent ring replayed in iteration order — telemetry.merge)
    tw = arrs.telemetry.shape[-1]
    telem = np.zeros((M, tw), np.int64)
    if tw:
        telem[0] = tele.merge(arrs.telemetry)

    out = SearchState(
        telemetry=telem,
        prmu=prmu, depth=depth, aux=aux,
        size=counts.astype(np.int32),
        best=np.full(M, int(np.min(arrs.best)), np.int32),
        tree=on_zero(int(np.sum(arrs.tree)), np.int64),
        sol=on_zero(int(np.sum(arrs.sol)), np.int64),
        iters=np.full(M, int(np.max(arrs.iters)), np.int64),
        evals=on_zero(int(np.sum(arrs.evals)), np.int64),
        sent=on_zero(int(np.sum(arrs.sent)), np.int64),
        recv=on_zero(int(np.sum(arrs.recv)), np.int64),
        steals=on_zero(int(np.sum(arrs.steals)), np.int64),
        overflow=np.zeros(M, bool),
    )
    if squeeze:
        out = SearchState(*(a[0] for a in out))
    return SearchState(*(jnp.asarray(a) for a in out))


def collapse_to_single_device(state: SearchState, chunk: int,
                              jobs: int) -> SearchState:
    """Collapse a stacked (D, jobs, cap) snapshot onto ONE device: the
    elastic reshard to a single squeezed pool, pre-sized for the mesh
    run's TOTAL footprint (D x per-worker capacity — the one pool now
    carries every worker's rows and their future growth) and then
    doubled until the live rows clear the usable-row limit
    (device.row_limit's chunk*jobs scratch margin), so a nearly-full
    stacked snapshot cannot overflow on its first resumed segment.
    Shared by the CLI's and the campaign worker's resume paths — the
    sizing invariant lives in exactly one place."""
    from .device import row_limit

    shape = np.asarray(state.prmu).shape
    if len(shape) != 3:
        return state                     # already single-device
    stacked_total = int(shape[0] * shape[-1])
    out = reshard_state(state, 1, squeeze=True)
    grown = max(int(out.prmu.shape[-1]), stacked_total)
    need = int(np.asarray(out.size).max())
    while row_limit(grown, chunk, jobs) < max(need, 1):
        grown *= 2
    if grown != out.prmu.shape[-1]:
        out = grow(out, grown)
    return out


class PoolOverflow(RuntimeError):
    """Pool capacity exceeded; `.state` is the (resumable) search state."""

    def __init__(self, message: str, state: SearchState):
        super().__init__(message)
        self.state = state


def grow(state: SearchState, new_capacity: int) -> SearchState:
    """Re-home a search state — single-device (jobs, cap) or stacked
    distributed (D, jobs, cap) — into a larger pool, clearing the
    overflow flag(s): the recovery path after an overflow abort (load or
    fetch, grow, resume). Rows above each cursor are garbage by the pool
    invariant, so growth is zero-padding the row axis."""
    capacity = np.asarray(state.prmu).shape[-1]
    if new_capacity < capacity:
        raise ValueError(f"new_capacity {new_capacity} < current {capacity}")
    tracelog.event("pool.grow", capacity=int(capacity),
                   new_capacity=int(new_capacity))
    obs_metrics.default().counter(
        "tts_pool_grows_total", "lossless overflow pool growths").inc()
    pad = new_capacity - capacity

    def pad_rows(x):
        x = np.asarray(x)
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        return jnp.asarray(np.pad(x, widths))

    ovf = np.zeros_like(np.asarray(state.overflow))
    return state._replace(prmu=pad_rows(state.prmu),
                          depth=pad_rows(state.depth),
                          aux=pad_rows(state.aux),
                          overflow=jnp.asarray(ovf))


@dataclasses.dataclass
class SegmentReport:
    segment: int
    iters: int
    tree: int
    sol: int
    best: int
    pool_size: int
    elapsed: float
    # distributed runs: per-worker live sizes / cumulative steal counts /
    # incumbents / explored+eval counters (the heartbeat surface the
    # reference's "Still Idle" print, dist:663-668, only hints at, and
    # the inputs the live phase attribution needs — see
    # utils/phase_timing.publish_attribution); None on single-device runs
    per_worker: dict | None = None
    evals: int = 0               # cumulative bound evaluations (total)
    # cumulative on-device search telemetry (telemetry.summarize dict:
    # depth-bucketed popped/branched/pruned, bound histograms, pool
    # high-water, steal flow, incumbent ring, pruning rate); None when
    # the state carries no telemetry block (TTS_SEARCH_TELEMETRY off)
    telemetry: dict | None = None


class _ReportFolder:
    """Per-segment report assembly shared by the sync and overlapped
    segment drivers: fold a fetched counter/telemetry block into the
    per-worker stats dict, the per-segment ``search.telemetry`` delta
    event, the SegmentReport, the explored-node throughput counter and
    the no-progress stall check. ONE implementation, so the on/off
    bit-parity the overlap feature promises extends to everything the
    two drivers record — a schema or semantics change cannot land in
    one driver and silently drift the other."""

    def __init__(self, state: SearchState, t0: float, stall_limit: int,
                 start_iters: int):
        self.t0 = t0
        self.stall_limit = stall_limit
        self.stalls = 0
        self.last = (start_iters, -1, -1)
        # resumed states carry cumulative totals; throughput metrics
        # must count only THIS run's progress. Telemetry width via
        # .shape, never np.asarray: materializing a state leaf here
        # raises on multihost runs (non-addressable shards — the
        # hazard _to_np exists for)
        self.prev_tree = int(np.atleast_1d(_to_np(state.tree)).sum())
        self.tele_w = int(state.telemetry.shape[-1])
        # search-telemetry deltas start from the INCOMING block (a
        # resumed checkpoint's counts must not re-report as segment-1
        # activity)
        self.prev_tele = (
            tele.merge(np.atleast_2d(_to_np(state.telemetry)))
            if self.tele_w else None)
        self.prev_evals = np.atleast_1d(_to_np(state.evals)).copy()
        self.nodes_c = obs_metrics.default().counter(
            "tts_nodes_explored_total",
            "explored-node throughput (segment deltas)")

    def fold(self, fetched: tuple, seg: int) -> SegmentReport:
        (f_iters, f_tree, f_sol, sizes, f_best, f_steals, _f_ovf,
         f_evals) = fetched[:8]
        iters = int(f_iters.max())
        tree = int(f_tree.sum())
        sol = int(f_sol.sum())
        size = int(sizes.sum())
        per_worker = None
        if sizes.ndim:                      # stacked distributed state
            per_worker = {"size": sizes.tolist(),
                          "steals": f_steals.tolist(),
                          "best": f_best.tolist(),
                          "iters": f_iters.tolist(),
                          "evals": f_evals.tolist()}
        tele_summary = None
        if self.tele_w:
            # cumulative summary for the report + a per-segment DELTA
            # event for the trace — the time series Perfetto counter
            # tracks and tools/search_report.py render
            merged = tele.merge(np.atleast_2d(fetched[8]))
            tele_summary = tele.summarize(merged)
            deltas = tele.delta_counts(merged, self.prev_tele)
            evals_d = np.atleast_1d(f_evals) - self.prev_evals
            ev = {}
            if sizes.ndim:
                ev = {"workers": int(sizes.shape[0]),
                      "evals_pw": evals_d.tolist()}
            tracelog.event(
                "search.telemetry", segment=seg, **deltas, pool=size,
                pool_hw=tele_summary["pool_highwater"],
                best=int(f_best.min()),
                improvements=tele_summary["improvements"], **ev)
            self.prev_tele = merged
            self.prev_evals = np.atleast_1d(f_evals).copy()
        # per-segment DELTA, so the counter is live throughput, not the
        # cumulative totals a resumed checkpoint would double-report
        self.nodes_c.inc(max(tree - self.prev_tree, 0))
        self.prev_tree = tree
        return SegmentReport(
            segment=seg, iters=iters, tree=tree, sol=sol,
            best=int(f_best.min()), pool_size=size,
            elapsed=time.perf_counter() - self.t0,
            per_worker=per_worker, evals=int(f_evals.sum()),
            telemetry=tele_summary)

    def check_stall(self, report: SegmentReport) -> None:
        key = (report.iters, report.tree, report.sol)
        if key == self.last:
            self.stalls += 1
            if self.stalls >= self.stall_limit:
                raise RuntimeError(
                    f"search stalled: no progress across {self.stalls} "
                    f"segments (iters={report.iters}, "
                    f"pool={report.pool_size})")
        else:
            self.stalls = 0
        self.last = key


def run_segmented(run_fn, state: SearchState, segment_iters: int = 2048,
                  checkpoint_path: str | None = None,
                  checkpoint_every: int = 1,
                  heartbeat=print, max_segments: int | None = None,
                  max_total_iters: int | None = None,
                  stall_limit: int = 3,
                  raise_on_overflow: bool = True,
                  checkpoint_meta: dict | None = None,
                  post_segment=None,
                  should_stop=None,
                  retry_attempts: int | None = None,
                  retry_base_s: float | None = None,
                  segment_timeout_s: float | None = None,
                  overlap: bool = False,
                  grow_fn=None,
                  stop_pending=None):
    """Drive `run_fn(state, target_total_iters) -> state` to exhaustion in
    bounded segments.

    `run_fn` receives a CUMULATIVE iteration ceiling (matching
    `device.run(..., max_iters=...)`'s semantics: the loop condition is
    `state.iters < max_iters`), not an increment. Targets are offset by the
    incoming state's iteration count, so resuming from a loaded checkpoint
    works.

    - checkpoints every `checkpoint_every` segments when a path is given;
    - calls `post_segment(state) -> state` after each segment, BEFORE the
      heartbeat/checkpoint, so cross-tier effects (the `-C` host
      session's incumbent merge) land in both (engine/hybrid.HostSession);
    - calls `heartbeat(SegmentReport)` after each segment;
    - stops early (after checkpointing) when `should_stop(SegmentReport)`
      returns True — the wall-budget hook for campaign drivers;
    - `checkpoint_meta` may be a CALLABLE returning the meta dict, re-
      evaluated at every save (live values like cumulative wall time);
    - raises RuntimeError after `stall_limit` consecutive segments with no
      progress (tree/sol/iters all unchanged) — a compiled-loop stall is a
      bug, not a state, so fail loudly rather than spin (the reference's
      equivalent symptom is its 10-second "Still Idle" print, dist:663-668);
    - on pool overflow the search state is incomplete: raises RuntimeError
      (after checkpointing, so the state is recoverable) unless
      `raise_on_overflow=False`, in which case the caller must check
      `state.overflow` before trusting the counters.

    Resilience (the layer the reference lacks end to end): segment
    execution, checkpoint writes and the per-segment scalar fetch are
    retried `retry_attempts` times with exponential backoff
    (`retry_base_s * 2^k`) on TRANSIENT errors only (I/O, runtime
    transport, injected faults — see TRANSIENT_ERRORS); a
    `segment_timeout_s` wall-clock watchdog converts a hung device
    dispatch into a loud SegmentTimeout (never retried — the
    supervisor's kill+respawn is the recovery for hangs). Defaults read
    TTS_RETRY_ATTEMPTS (3), TTS_RETRY_BASE_S (0.5) and
    TTS_SEG_TIMEOUT_S (0 = off). Deterministic fault injection for all
    of these lives in utils/faults.py (TTS_FAULTS).

    Overlap (`overlap=True`, the driver side of TTS_OVERLAP —
    engine/distributed.search resolves the flag and supplies the
    hooks): `run_fn` must then be an ASYNC dispatch (returns the next
    state's futures without blocking — _DistDriver.run_async, pool
    leaves donated) and execution pipelines: segment N+1 is dispatched
    BEFORE segment N's counters are fetched, so the heartbeat always
    consumes the PREVIOUS segment's report while the device computes,
    and the device-idle gap between segments (the new
    `tts_segment_gap_seconds` histogram; both modes record it) drops
    to ~0. Checkpoint serialization + fsync move to a bounded-queue
    AsyncCheckpointWriter thread; only the live-row host fetch stays on
    the dispatch thread (checkpoint segments therefore dispatch after
    that fetch — the one per-`checkpoint_every` synchronization the
    format's rotation invariants require). `grow_fn(state) -> state`
    is the lossless overflow recovery (fetch + grow + recommit);
    `stop_pending() -> bool` is a report-free stop probe that skips
    speculative dispatch when a stop was already requested. Exit
    conditions are evaluated one segment later than the sync path
    (the in-flight speculative segment is drained, never discarded —
    it no-ops when the pool is empty or overflowed), so a stop request
    costs at most one extra segment; totals at exhaustion are
    bit-identical to overlap-off. Incompatible with `post_segment`
    (the host-tier merge mutates state the pipeline has already
    donated) — callers must force overlap off alongside a host tier.

    The resilience contract under overlap is NARROWER than sync's: a
    transient error in segment EXECUTION cannot be retried in place —
    the failed dispatch's input pools were donated, so there is no
    prior state to re-run and the retry wrapper around the counter
    fetch can only re-observe the poisoned output. In-place retries
    cover the host-side I/O edges (fetch, save); recovery from a
    failed segment is the OUTER tier's job — checkpoint re-dispatch
    (the service's re-queue path, `load_resilient` standalone), which
    is exactly what the durability layer exists for. Runs that need
    in-place execution retries (no checkpoint, no supervisor) should
    keep overlap off.
    """
    from ..utils import config as _cfg
    if retry_attempts is None:
        retry_attempts = _cfg.env_int("TTS_RETRY_ATTEMPTS")
    if retry_base_s is None:
        retry_base_s = _cfg.env_float("TTS_RETRY_BASE_S")
    if segment_timeout_s is None:
        segment_timeout_s = _cfg.env_float("TTS_SEG_TIMEOUT_S")
    import jax
    if jax.process_count() > 1:
        # Multi-controller: run_fn, save and the scalar fetch all
        # contain COLLECTIVES (process_allgather, the SPMD loop). A
        # per-process retry re-enters its collective alone while the
        # other processes have moved on — mismatched collective order
        # is a distributed hang, strictly worse than the transient it
        # retries. Fail loudly instead; multihost recovery is
        # restart-the-job-level (every process resumes from the shared
        # checkpoint), not retry-in-place. The same reasoning disables
        # overlap: speculative dispatch would reorder collectives
        # against the allgather-bearing fetches.
        retry_attempts = 1
        overlap = False
    if overlap:
        if post_segment is not None:
            raise ValueError(
                "overlap=True is incompatible with post_segment (the "
                "host-tier merge mutates state the pipeline has already "
                "donated); run the host tier with overlap off")
        return _run_segmented_overlap(
            run_fn, state, segment_iters=segment_iters,
            checkpoint_path=checkpoint_path,
            checkpoint_every=checkpoint_every, heartbeat=heartbeat,
            max_segments=max_segments, max_total_iters=max_total_iters,
            stall_limit=stall_limit, raise_on_overflow=raise_on_overflow,
            checkpoint_meta=checkpoint_meta, should_stop=should_stop,
            retry_attempts=retry_attempts, retry_base_s=retry_base_s,
            segment_timeout_s=segment_timeout_s, grow_fn=grow_fn,
            stop_pending=stop_pending)
    t0 = time.perf_counter()
    seg = 0
    start_iters = int(_to_np(state.iters).max())
    folder = _ReportFolder(state, t0, stall_limit, start_iters)
    # device-idle accounting shared with the overlapped driver: the gap
    # between segment N's results landing on the host and segment N+1's
    # dispatch is time the device spends waiting on the host (heartbeat,
    # checkpoint, stop checks) — the exact interval TTS_OVERLAP removes
    gap_hist = obs_metrics.default().histogram(
        "tts_segment_gap_seconds", GAP_HELP, buckets=GAP_BUCKETS)
    results_ready_t = None

    def meta_now(seg):
        base = checkpoint_meta() if callable(checkpoint_meta) \
            else dict(checkpoint_meta or {})
        return {**base, "segment": seg}

    def do_save(s, seg_no):
        _retry(lambda: save(checkpoint_path, s, meta=meta_now(seg_no)),
               "checkpoint save", retry_attempts, retry_base_s)
        # audit hook (TTS_AUDIT=full / TTS_AUDIT_CKPT=1): re-read the
        # snapshot and require bit-identical counters — BEFORE the
        # fault injection below, which may corrupt the file on purpose
        # to exercise the load-side rollback
        from ..obs import audit as obs_audit
        if obs_audit.roundtrip_enabled():
            obs_audit.check_checkpoint_roundtrip(checkpoint_path, s)
        # torn-write / corruption injection targets the just-written
        # file — the load-side rollback to last-good is what it tests
        faults.fire("post_checkpoint", segment=seg_no,
                    path=checkpoint_path)

    def final_save(s, seg):
        # every exit path must leave a CURRENT checkpoint — with
        # checkpoint_every > 1, returning without this leaves the file
        # up to checkpoint_every-1 segments stale and a planned
        # stop-then-resume silently redoes that work
        if checkpoint_path and seg % checkpoint_every != 0:
            do_save(s, seg)

    while True:
        target = start_iters + (seg + 1) * segment_iters
        if max_total_iters is not None:
            target = min(target, start_iters + max_total_iters)
        faults.fire("segment_start", segment=seg + 1)
        # run_fn is functional (the incoming state is untouched on
        # failure), so a retried segment redoes identical work; the
        # watchdog wraps each attempt separately
        prev_state = state
        if results_ready_t is not None:
            gap_hist.observe(max(0.0, time.monotonic() - results_ready_t))
        with tracelog.span("segment", segment=seg + 1) as seg_span:
            state = _retry(
                lambda: _with_watchdog(
                    lambda: run_fn(prev_state, target),
                    segment_timeout_s, f"segment {seg + 1}"),
                "segment execution", retry_attempts, retry_base_s)
            if post_segment is not None:
                state = post_segment(state)
            seg += 1
            # ONE batched host fetch for every per-segment scalar: each
            # separate fetch is a full host-device roundtrip
            # the watchdog must cover this fetch too: dispatch is ASYNC,
            # so a hung device computation lets run_fn return its
            # futures instantly and the block happens HERE, waiting on
            # the results
            fetched = _retry(
                lambda: _with_watchdog(
                    lambda: _fetch_many(
                        (state.iters, state.tree, state.sol,
                         state.size, state.best, state.steals,
                         state.overflow, state.evals)
                        + ((state.telemetry,) if folder.tele_w
                           else ())),
                    segment_timeout_s, f"segment {seg} result fetch"),
                "per-segment host fetch", retry_attempts, retry_base_s)
            results_ready_t = time.monotonic()
            f_ovf = fetched[6]
            seg_span.set(iters=int(fetched[0].max()),
                         tree=int(fetched[1].sum()),
                         sol=int(fetched[2].sum()),
                         pool=int(fetched[3].sum()),
                         best=int(fetched[4].min()))
        # fold AFTER the span closes so the `segment` span record still
        # precedes its search.telemetry event in the record stream
        report = folder.fold(fetched, seg)
        iters, size = report.iters, report.pool_size
        obs_metrics.default().histogram(
            "tts_segment_seconds",
            "segment wall latency (execute+fetch)"
            ).observe(seg_span.dur)
        if heartbeat is not None:
            heartbeat(report)
        if checkpoint_path and seg % checkpoint_every == 0:
            do_save(state, seg)
        # preemption injection point: fires at the END of segment k,
        # after any checkpoint that segment wrote. Deliberately NOT
        # checkpoint-aligned — real preemptions are not either; with
        # checkpoint_every > 1 the on-disk snapshot may be up to
        # checkpoint_every-1 segments older and recovery redoes that
        # interval (the kill-then-resume-elsewhere shape elastic
        # resume exists for)
        faults.fire("post_segment", segment=seg)
        if bool(f_ovf.any()):
            final_save(state, seg)
            if raise_on_overflow:
                hint = (f"resume from {checkpoint_path} with a larger "
                        "capacity" if checkpoint_path else
                        "rerun with a larger capacity, or catch "
                        "PoolOverflow and grow() its .state")
                raise PoolOverflow(
                    f"pool overflow at segment {seg} (pool={size}): search "
                    f"incomplete; {hint}", state)
            return state
        if size == 0:
            final_save(state, seg)
            return state
        if should_stop is not None and should_stop(report):
            final_save(state, seg)
            return state
        folder.check_stall(report)
        if max_segments is not None and seg >= max_segments:
            final_save(state, seg)
            return state
        if (max_total_iters is not None
                and iters >= start_iters + max_total_iters):
            final_save(state, seg)
            return state


def _run_segmented_overlap(run_fn, state: SearchState, *, segment_iters,
                           checkpoint_path, checkpoint_every, heartbeat,
                           max_segments, max_total_iters, stall_limit,
                           raise_on_overflow, checkpoint_meta,
                           should_stop, retry_attempts, retry_base_s,
                           segment_timeout_s, grow_fn, stop_pending):
    """The pipelined segment driver behind `run_segmented(overlap=True)`.

    Pipeline shape (see run_segmented's docstring for the contract):
    segment N+1 is dispatched — donated carries, so the in-flight state
    is never copied — BEFORE segment N's counter block is fetched; the
    heartbeat then consumes segment N's report while the device runs
    N+1. Exit conditions found in segment N's report drain the
    in-flight segment (a no-op when the pool is empty or overflowed —
    the compiled loop's condition re-checks both) instead of discarding
    it, so node accounting is bit-identical to the sync driver.
    Checkpoint segments synchronize only for the live-row host fetch;
    compression + fsync run on the AsyncCheckpointWriter thread.

    `segment` spans are emitted with EXPLICIT [dispatch, results-ready]
    timestamps (tracelog.span_at): consecutive spans overlap in wall
    time exactly when the device ran back-to-back, which is what the
    search_report gap table and the tts_segment_gap_seconds histogram
    measure."""
    t0 = time.perf_counter()
    seg = 0
    start_iters = int(_to_np(state.iters).max())
    folder = _ReportFolder(state, t0, stall_limit, start_iters)
    reg = obs_metrics.default()
    gap_hist = reg.histogram("tts_segment_gap_seconds", GAP_HELP,
                             buckets=GAP_BUCKETS)
    seg_hist = reg.histogram("tts_segment_seconds",
                             "segment wall latency (execute+fetch)")
    writer = (AsyncCheckpointWriter(retry_attempts=retry_attempts,
                                    retry_base_s=retry_base_s)
              if checkpoint_path else None)

    def target_for(k: int) -> int:
        t = start_iters + k * segment_iters
        if max_total_iters is not None:
            t = min(t, start_iters + max_total_iters)
        return t

    def meta_now(seg_no):
        base = checkpoint_meta() if callable(checkpoint_meta) \
            else dict(checkpoint_meta or {})
        return {**base, "segment": seg_no}

    def fetch_counters(cur, seg_no):
        # the ONLY per-segment fetch on the hot path: the small
        # counter/telemetry block (the full state is fetched solely on
        # checkpoint segments, via the writer's prepare())
        return _retry(
            lambda: _with_watchdog(
                lambda: _fetch_many(
                    (cur.iters, cur.tree, cur.sol, cur.size, cur.best,
                     cur.steals, cur.overflow, cur.evals)
                    + ((cur.telemetry,) if folder.tele_w else ())),
                segment_timeout_s, f"segment {seg_no} result fetch"),
            "per-segment host fetch", retry_attempts, retry_base_s)

    try:
        faults.fire("segment_start", segment=1)
        dispatch_t = time.monotonic()
        cur = run_fn(state, target_for(1))
        halting = False
        results_ready_t = None
        while True:
            seg += 1
            this_dispatch_t = dispatch_t
            is_ckpt = bool(checkpoint_path) \
                and seg % checkpoint_every == 0

            def can_speculate():
                return (not halting
                        and (max_segments is None or seg < max_segments)
                        and target_for(seg + 1) > target_for(seg)
                        and not (stop_pending is not None
                                 and stop_pending()))

            spec = spec_t = None
            next_fired = False   # fired segment_start for seg+1 yet?
            if not is_ckpt and can_speculate():
                faults.fire("segment_start", segment=seg + 1)
                next_fired = True
                spec_t = time.monotonic()
                spec = run_fn(cur, target_for(seg + 1))

            fetched = fetch_counters(cur, seg)
            prev_ready_t = results_ready_t
            results_ready_t = time.monotonic()
            (f_iters, f_tree, f_sol, sizes, f_best, f_steals, f_ovf,
             f_evals) = fetched[:8]

            # lossless overflow recovery, pipelined edition: the
            # speculative segment no-oped on the overflow flag, so
            # adopt it, grow every pool, and re-run the SAME segment
            # target from exactly where the loop stopped
            while bool(f_ovf.any()) and grow_fn is not None:
                if spec is not None:
                    cur, spec = spec, None
                cur = run_fn(grow_fn(cur), target_for(seg))
                fetched = fetch_counters(cur, seg)
                results_ready_t = time.monotonic()
                (f_iters, f_tree, f_sol, sizes, f_best, f_steals,
                 f_ovf, f_evals) = fetched[:8]

            if is_ckpt:
                # synchronization point: the live rows must be read
                # before the pools are donated to the next dispatch —
                # prepare() on this thread, then dispatch, then hand
                # the compress+fsync to the writer (enqueue may block
                # on back-pressure, but the device is already running)
                task = _retry(
                    lambda: _with_watchdog(
                        lambda: writer.prepare(
                            checkpoint_path, cur, meta_now(seg),
                            segment=seg),
                        segment_timeout_s,
                        f"segment {seg} checkpoint fetch"),
                    "checkpoint state fetch", retry_attempts,
                    retry_base_s)
                if can_speculate():
                    faults.fire("segment_start", segment=seg + 1)
                    next_fired = True
                    spec_t = time.monotonic()
                    spec = run_fn(cur, target_for(seg + 1))
                writer.enqueue(task)

            tracelog.span_at("segment", this_dispatch_t,
                             results_ready_t, segment=seg,
                             iters=int(f_iters.max()),
                             tree=int(f_tree.sum()),
                             sol=int(f_sol.sum()),
                             pool=int(sizes.sum()),
                             best=int(f_best.min()), overlapped=True)
            if prev_ready_t is not None:
                gap_hist.observe(max(0.0, this_dispatch_t - prev_ready_t))
            seg_hist.observe(max(results_ready_t - this_dispatch_t, 0.0))
            report = folder.fold(fetched, seg)
            iters, size = report.iters, report.pool_size
            if heartbeat is not None:
                heartbeat(report)
            faults.fire("post_segment", segment=seg)

            overflow_exit = bool(f_ovf.any())
            exit_now = halting or overflow_exit or size == 0
            if not exit_now and should_stop is not None \
                    and should_stop(report):
                exit_now = True
            if not exit_now and max_segments is not None \
                    and seg >= max_segments:
                exit_now = True
            if not exit_now and max_total_iters is not None \
                    and iters >= start_iters + max_total_iters:
                exit_now = True
            if exit_now:
                if spec is not None:
                    # drain the in-flight speculative segment first: a
                    # no-op on an empty/overflowed pool, at most one
                    # segment of extra work on a stop request — its
                    # output is the state the exit below must persist
                    halting = True
                    cur, dispatch_t = spec, spec_t
                    continue
                if checkpoint_path and seg % checkpoint_every != 0:
                    writer.submit(checkpoint_path, cur, meta_now(seg),
                                  segment=seg)
                if writer is not None:
                    writer.drain()
                if overflow_exit and raise_on_overflow:
                    hint = (f"resume from {checkpoint_path} with a "
                            "larger capacity" if checkpoint_path else
                            "rerun with a larger capacity, or catch "
                            "PoolOverflow and grow() its .state")
                    raise PoolOverflow(
                        f"pool overflow at segment {seg} (pool={size}): "
                        f"search incomplete; {hint}", cur)
                return cur
            folder.check_stall(report)
            if spec is not None:
                cur, dispatch_t = spec, spec_t
            else:
                if not next_fired:
                    # an abandoned speculation (overflow recovery)
                    # already fired this segment's injection point;
                    # firing again would double-spend fault budgets
                    # and break overlap-vs-sync injection parity
                    faults.fire("segment_start", segment=seg + 1)
                dispatch_t = time.monotonic()
                cur = run_fn(cur, target_for(seg + 1))
    finally:
        if writer is not None:
            # success paths drained above; this is the unwind valve —
            # never mask an in-flight exception with a writer error
            writer.close(raise_pending=False)
