"""Disk-persistent AOT executable cache: zero-compile cold start.

The ExecutorCache (service/executors.py) makes compiles a once-per-key
cost *within* a server lifetime; this module makes them a once-per-key
cost *across* lifetimes. A restarted or freshly autoscaled SearchServer
deserializes the compiled SPMD loop from disk (~0.2 s on the CPU test
mesh) instead of re-tracing and re-compiling it (seconds to minutes) —
the same shape-of-win a serving stack gets from a persistent compilation
cache, and the jit-world equivalent of the reference engine paying its
CUDA kernel load once per binary. The compile-storm a redeploy used to
be becomes a directory of file reads.

Serialization rides the jit AOT path: the executor's first compile goes
through ``fn.lower(...).compile()`` already (the PR-5 ledger), and the
resulting ``jax.stages.Compiled`` round-trips through
``jax.experimental.serialize_executable`` (the pickle form of
``jax.export``'s executable serialization on this pin — the loaded
program performs ZERO ``lower()``/``compile()`` calls). Not every
backend/pin can round-trip a program, so :func:`probe` compiles and
reloads a trivial jitted function ONCE per process; when it fails, the
cache degrades to in-memory-only (the pre-PR-8 behavior) instead of
serving maybe-wrong bytes.

Safety model — a stale entry can never load into the wrong runtime:

- **Key**: the file name is a digest of the FULL ExecutorCache key
  (problem kind, shape, bound, chunk, aux dtype, submesh device ids,
  capacity, balance knobs, row limit, donation variant) — everything
  the trace specializes on.
- **Format**: the header's ``v`` names the payload layout
  (:data:`FORMAT`); an entry of another layout is ignored like a
  fingerprint mismatch, and this runtime's compile overwrites it.
- **Fingerprint**: each entry's header embeds :func:`runtime_fingerprint`
  (jax/jaxlib versions, platform, device topology/kind, process count,
  telemetry block width) and is IGNORED on mismatch — the telemetry
  flag changes the traced state shapes without changing the key, and a
  jaxlib bump invalidates the serialized executable wholesale.
- **Integrity**: entries are written with the checkpoint layer's
  discipline — temp file + fsync + atomic rename, a CRC32 stamp over
  the payload — and a corrupt/truncated entry is QUARANTINED (renamed
  ``*.corrupt``, never loaded, counted) and recompiled, mirroring
  ``checkpoint.load_resilient``.
- **Hot path**: persistence happens on a single bounded-queue writer
  thread (the ``AsyncCheckpointWriter`` pattern from PR 7) — the
  serving thread never waits on serialize + fsync; ``drain()`` exists
  for tests and shutdown.

Observability: ``tts_aot_cache_{hits,misses,errors}_total`` counters and
a ``tts_deserialize_seconds`` histogram when a registry is supplied;
``snapshot()`` rides ``status_snapshot()``'s ``aot_cache`` key (the
``doctor`` CLI surfaces it); the executor ledger records per-entry
``source=disk|compile`` and ``deserialize_s``
(tools/compile_report.py renders both).
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import pickle
import queue
import struct
import threading
import time
import zlib

from ..obs import tracelog
from ..utils import config as cfg

__all__ = ["AOTCache", "probe", "runtime_fingerprint"]

MAGIC = b"TTSAOT1\n"
_HDR_LEN = struct.Struct("<Q")
QUARANTINE_SUFFIX = ".corrupt"
# payload layout: 2 = (payload, in_tree, out_tree, device ids); 1 had
# no device ids and loaded a submesh program onto the wrong devices
FORMAT = 2


def _cache_served_breaks() -> bool:
    import jax
    return jax.default_backend() == "cpu"


_probe_lock = threading.Lock()
_probe_result: bool | None = None


def runtime_fingerprint(extra: dict | None = None) -> dict:
    """Everything OUTSIDE the ExecutorCache key that a serialized
    executable depends on. Two processes whose fingerprints differ must
    never exchange entries: the bytes encode the XLA version's program
    format, the device assignment, and state shapes the static
    telemetry flag bakes in."""
    import jax
    import jaxlib

    from ..engine import telemetry as tele

    devices = jax.devices()
    fp = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "device_count": len(devices),
        "device_kinds": sorted({d.device_kind for d in devices}),
        "process_count": jax.process_count(),
        # static compile-in flags: they change the traced state
        # SHAPES/dtypes without appearing in the executor key —
        # telemetry width (zero-width leaf when off) and x64 (the
        # counter block and max_iters are int64-or-int32 with it)
        "telemetry_width": tele.enabled_width(),
        "x64": bool(jax.config.jax_enable_x64),
    }
    if extra:
        fp.update(extra)
    return fp


def probe() -> bool:
    """ONE per-process capability check: can this jax/backend pin
    round-trip a compiled program through serialize + deserialize and
    still execute it? False => the cache must stay in-memory-only
    (callers construct no AOTCache); never raises."""
    global _probe_result
    with _probe_lock:
        if _probe_result is None:
            _probe_result = _probe_impl()
        return _probe_result


def _serialize(compiled) -> bytes:
    """Pickle a compiled program with the ids of the devices it was
    compiled for, in its device-assignment order: a plain
    ``deserialize_and_load`` places a submesh program on the first
    devices of the process and fails at execution."""
    from jax.experimental import serialize_executable as se
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((*se.serialize(compiled), ids))


def _deserialize(blob: bytes):
    import jax
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree, ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids])


def _probe_impl() -> bool:
    """Round-trip a program sharded over the process's LAST devices in
    reverse order (one device where there is only one), the placement a
    non-first submesh gets."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devs = jax.devices()[-2:][::-1]
        mesh = Mesh(np.array(devs), ("w",))
        x = jax.device_put(jnp.arange(4 * len(devs), dtype=jnp.int32),
                           NamedSharding(mesh, PartitionSpec("w")))
        compiled = jax.jit(lambda x: x * 2 + 1).lower(x).compile()
        loaded = _deserialize(_serialize(compiled))
        ok = bool((loaded(x) == compiled(x)).all())
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        tracelog.event("aot_cache.probe", supported=False, error=repr(e))
        return False
    tracelog.event("aot_cache.probe", supported=ok)
    return ok


def _key_digest(key: tuple) -> str:
    """Stable digest of an ExecutorCache key (tuples of scalars by
    construction). The FINGERPRINT deliberately stays out of the name:
    the header check is what rejects a wrong-runtime entry, so a runtime
    upgrade OVERWRITES stale entries at the same path instead of
    stranding them forever."""
    raw = json.dumps([str(k) for k in key]).encode()
    return hashlib.sha256(raw).hexdigest()[:32]


class AOTCache:
    """Disk tier under the ExecutorCache. ``load(key)`` returns a ready
    ``jax.stages.Compiled`` (or None); ``store(key, compiled)`` queues
    persistence on the writer thread. Construct only when :func:`probe`
    says the pin can round-trip (the server does this gating)."""

    ENTRIES_TTL_S = 5.0   # entries() rescans the dir at most this often

    def __init__(self, root: str | os.PathLike, registry=None,
                 fingerprint_extra: dict | None = None,
                 max_pending: int | None = None):
        self.root = pathlib.Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.fingerprint = runtime_fingerprint(fingerprint_extra)
        self.hits = 0            # guarded-by: self._lock
        self.misses = 0          # guarded-by: self._lock
        #                          (no entry on disk for the key)
        self.mismatches = 0      # guarded-by: self._lock
        #                          (entry present, wrong-runtime header)
        self.errors = 0          # guarded-by: self._lock
        #                          (corrupt/unreadable/unserializable)
        self.quarantined = 0     # guarded-by: self._lock
        self.writes = 0          # guarded-by: self._lock
        # deliberately UNguarded (atomic tuple swap, staleness is fine
        # for a stats field): see entries()
        self._entries_cache: tuple | None = None
        self._lock = threading.Lock()
        self._hits_c = self._misses_c = self._errors_c = None
        self._deser_h = None
        if registry is not None:
            self._hits_c = registry.counter(
                "tts_aot_cache_hits_total",
                "executables deserialized from the disk AOT cache "
                "(zero compiles paid)")
            self._misses_c = registry.counter(
                "tts_aot_cache_misses_total",
                "disk AOT cache lookups with no loadable entry "
                "(absent or wrong-runtime fingerprint)")
            self._errors_c = registry.counter(
                "tts_aot_cache_errors_total",
                "corrupt/unreadable/unserializable AOT cache entries "
                "(corrupt ones are quarantined, never loaded)")
            self._deser_h = registry.histogram(
                "tts_deserialize_seconds",
                "disk AOT cache deserialize+load wall seconds per hit")
        # single FIFO writer thread, bounded queue: persistence stays
        # off the serving thread; a serve burst outrunning the disk
        # blocks in store() rather than buffering unbounded payloads
        # (the AsyncCheckpointWriter discipline — writes are one per
        # fresh compile, so the bound is essentially never felt)
        self._q: queue.Queue = queue.Queue(
            maxsize=max_pending or cfg.AOT_WRITER_QUEUE_DEPTH)
        self._closed = False     # guarded-by: self._close_lock
        # makes store()'s closed-check + enqueue atomic against
        # close(): without it a racing store() could enqueue AFTER the
        # shutdown sentinel — its task_done never runs, so a later
        # drain() (q.join) would hang forever. The writer thread never
        # takes this lock, so a store() blocked on the bounded queue
        # while holding it still drains (close() just waits its turn).
        self._close_lock = threading.Lock()
        self._thread = threading.Thread(target=self._writer_loop,
                                        daemon=True,
                                        name="tts-aot-writer")
        self._thread.start()

    # ---------------------------------------------------------- paths

    def path_for(self, key: tuple) -> pathlib.Path:
        return self.root / f"{_key_digest(key)}.aot"

    # ----------------------------------------------------------- load

    def load(self, key: tuple):
        """Deserialize the entry for `key`, or None. Returns
        ``(compiled, deserialize_s)`` on a hit. Never raises: a corrupt
        entry is quarantined + counted, a wrong-fingerprint entry is
        ignored + counted, and the caller compiles as if the cache
        were empty."""
        path = self.path_for(key)
        t0 = time.perf_counter()
        try:
            blob = path.read_bytes()
        except FileNotFoundError:
            self._count("_misses_c", "misses")
            return None
        except OSError as e:
            # an entry that EXISTS but cannot be read (EACCES, EIO on
            # a failing mount) is an ERROR, not a miss: booking it as
            # a miss would leave an operator staring at a dir full of
            # entries, misses incrementing, and zero error signal
            self._count("_errors_c", "errors")
            tracelog.event("aot_cache.read_error", path=path.name,
                           error=repr(e))
            return None
        # timer spans the WHOLE hit cost — on fleet/network storage the
        # read of a multi-MB entry can dominate validate+load, and an
        # operator debugging a slow warm restart needs the real number
        payload = self._validate(path, blob)
        if payload is None:
            return None
        try:
            compiled = _deserialize(payload)
        except Exception as e:  # noqa: BLE001 — bytes are CRC-clean but
            # the runtime rejects them (a drift the fingerprint missed):
            # this entry will never load better, quarantine it
            self._quarantine(path, f"deserialize failed: {e!r}")
            return None
        dt = time.perf_counter() - t0
        self._count("_hits_c", "hits")
        if self._deser_h is not None:
            self._deser_h.observe(dt)
        tracelog.event("aot_cache.hit", path=path.name,
                       deserialize_s=round(dt, 6))
        return compiled, dt

    def _validate(self, path: pathlib.Path, blob: bytes) -> bytes | None:
        """Header + CRC discipline; returns the payload or None (counted
        and, for corruption, quarantined)."""
        try:
            if blob[:len(MAGIC)] != MAGIC:
                raise ValueError("bad magic")
            off = len(MAGIC)
            (hdr_len,) = _HDR_LEN.unpack_from(blob, off)
            off += _HDR_LEN.size
            header = json.loads(blob[off:off + hdr_len].decode())
            off += hdr_len
            payload = blob[off:]
            if len(payload) != int(header["payload_len"]):
                raise ValueError("truncated payload")
            if zlib.crc32(payload) != int(header["payload_crc32"]):
                raise ValueError("payload CRC mismatch")
        except Exception as e:  # noqa: BLE001 — torn/truncated/garbled
            self._quarantine(path, repr(e))
            return None
        if (header.get("v") != FORMAT
                or header.get("fingerprint") != self.fingerprint):
            # a DIFFERENT runtime's entry (jax bump, topology change,
            # telemetry flag flip) or payload layout: valid bytes,
            # wrong world — ignore it (this runtime's compile will
            # overwrite it) but never load it
            with self._lock:
                self.mismatches += 1
            self._count("_misses_c", "misses")
            tracelog.event("aot_cache.mismatch", path=path.name,
                           theirs=header.get("fingerprint"),
                           ours=self.fingerprint)
            return None
        return payload

    def _quarantine(self, path: pathlib.Path, error: str) -> None:
        self._count("_errors_c", "errors")
        # per-writer unique target (same discipline as store()'s temp
        # name): N processes quarantining corrupt incarnations of the
        # SAME entry must not os.replace over each other's forensic
        # copy — the suffix stays last so sweeps/tests keep matching.
        # The existence loop is raceless: only THIS thread mints names
        # under this pid-tid prefix
        base = f"{path.name}.{os.getpid()}-{threading.get_ident()}"
        qpath = str(path.with_name(base + QUARANTINE_SUFFIX))
        n = 0
        while os.path.exists(qpath):
            n += 1
            qpath = str(path.with_name(f"{base}.{n}{QUARANTINE_SUFFIX}"))
        try:
            os.replace(path, qpath)
            with self._lock:
                self.quarantined += 1
            self._entries_cache = None   # one fewer .aot on disk
        except OSError:
            qpath = None
        tracelog.event("aot_cache.quarantine", path=path.name,
                       quarantined_to=qpath, error=error)

    # ---------------------------------------------------------- store

    def store(self, key: tuple, compiled, key_repr: str = "",
              xla_cache_hit: bool = False) -> None:
        """Persist a freshly compiled executable: serialize it here,
        before its caller first runs it, and queue the bytes (the writer
        thread does CRC + atomic write). On XLA:CPU a serialization
        that races the executable's first run fails now and then
        ("`LessThan` is not serializable"). Serialization failures are
        counted, never raised — a program the pin cannot serialize
        still serves from memory.

        An executable that XLA's persistent compilation cache served
        (`xla_cache_hit`) stays in memory on XLA:CPU: its bytes load,
        then fail at execution ("Function ... not found"), so a
        restarted server's request would fail."""
        if xla_cache_hit and _cache_served_breaks():
            tracelog.event("aot_cache.skip_xla_cache_hit", key=key_repr)
            return
        try:
            payload = _serialize(compiled)
        except Exception as e:  # noqa: BLE001 — per-program capability:
            # the probe passing does not guarantee EVERY program
            # round-trips on this pin; fall back to in-memory-only for
            # this entry
            self._count("_errors_c", "errors")
            tracelog.event("aot_cache.serialize_unsupported",
                           key=key_repr, error=repr(e))
            return
        with self._close_lock:
            if self._closed:
                return
            self._q.put({"path": self.path_for(key), "payload": payload,
                         "key_repr": key_repr})

    def drain(self) -> None:
        """Block until every queued entry is on disk (tests/shutdown)."""
        self._q.join()

    def close(self) -> None:
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._q.put(None)
        self._thread.join()

    def _writer_loop(self) -> None:
        while True:
            task = self._q.get()
            try:
                if task is None:
                    return
                self._write(task)
            except Exception as e:  # noqa: BLE001 — persistence is an
                # optimization; its failure must never kill the writer
                self._count("_errors_c", "errors")
                tracelog.event("aot_cache.store_failed", error=repr(e))
            finally:
                self._q.task_done()

    def _write(self, task: dict) -> None:
        path: pathlib.Path = task["path"]
        payload = task["payload"]
        header = json.dumps({
            "v": FORMAT, "fingerprint": self.fingerprint,
            "key": task["key_repr"], "created_unix": time.time(),
            "payload_len": len(payload),
            "payload_crc32": zlib.crc32(payload),
        }).encode()
        # unique per-writer temp name: two processes sharing one cache
        # dir (the autoscale fleet scenario) both compiling this key
        # must not interleave bytes in a shared temp file — each
        # renames its OWN complete entry; last replace wins, both valid
        tmp = path.with_name(
            f".{path.name}.{os.getpid()}-{threading.get_ident()}.tmp")
        try:
            with open(tmp, "wb") as f:
                f.write(MAGIC)
                f.write(_HDR_LEN.pack(len(header)))
                f.write(header)
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic: readers see old bytes
            #                        or new, never a torn mix
            self._entries_cache = None   # count may have changed
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        with self._lock:
            self.writes += 1
        tracelog.event("aot_cache.store", path=path.name,
                       bytes=len(payload), key=task["key_repr"])

    # ----------------------------------------------------------- read

    def _count(self, counter_attr: str, field: str) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)
        c = getattr(self, counter_attr)
        if c is not None:
            c.inc()

    def entries(self) -> int:
        """Entry-file count, rescanned at most every ENTRIES_TTL_S:
        /status polls at 1 Hz must not pay a directory scan each time
        on slow fleet storage (the count only moves on writes, plus
        other processes sharing the dir — a few seconds stale is fine
        for a stats field)."""
        now = time.monotonic()
        cached = self._entries_cache
        if cached is not None and now - cached[0] < self.ENTRIES_TTL_S:
            return cached[1]
        try:
            n = sum(1 for p in self.root.iterdir()
                    if p.suffix == ".aot")
        except OSError:
            n = 0
        self._entries_cache = (now, n)
        return n

    def snapshot(self) -> dict:
        """JSON-safe stats — status_snapshot()'s `aot_cache` key (the
        doctor CLI surfaces it per server)."""
        # the directory listing can be slow on fleet/network storage:
        # keep it OUTSIDE the stats lock the load/store paths need
        n_entries = self.entries()
        with self._lock:
            return {"dir": str(self.root), "entries": n_entries,
                    "hits": self.hits, "misses": self.misses,
                    "mismatches": self.mismatches,
                    "errors": self.errors,
                    "quarantined": self.quarantined,
                    "writes": self.writes}
