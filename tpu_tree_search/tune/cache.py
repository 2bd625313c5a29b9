"""Persistent tuning cache: probe once per (shape, bound, topology).

The Autotuner's probes cost real device time (warmed measurement
windows over a candidate ladder); this cache makes them a once-per-key
cost ACROSS process lifetimes, exactly like service/aot_cache.py makes
compiles one: a restarted/autoscaled server replays its tuned dispatch
knobs from disk with ZERO probe executions.

Same safety model as the AOT cache, scaled to JSON-sized entries:

- **Key**: the file name is a digest of the tuning key (problem kind,
  jobs, machines, lb kind, worker count) — everything the optimum
  specializes on besides the runtime.
- **Fingerprint**: each entry's header embeds the device
  platform/topology fingerprint (:func:`tuning_fingerprint`); a
  wrong-runtime entry (a TPU optimum read on the CPU mesh, a topology
  change) is IGNORED — and overwritten by the next probe — but never
  consumed. The chunk optimum moved 256 → 32768 → 65536 across
  hardware/kernel changes; a cache that served a
  stale platform's winner would silently re-introduce exactly the
  drift the tuner exists to kill.
- **Integrity**: entries are written temp + fsync + atomic rename with
  a CRC32 stamp over the payload; a corrupt/truncated entry is
  QUARANTINED (renamed ``*.corrupt``, never loaded, counted) and
  re-probed — the checkpoint/AOT discipline.

Writes are synchronous (entries are a few hundred bytes and happen
once per cold shape — no writer thread needed); loads never raise.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import struct
import threading
import time
import zlib

from ..obs import tracelog

__all__ = ["TuningCache", "tuning_fingerprint"]

MAGIC = b"TTSTUNE1\n"
_HDR_LEN = struct.Struct("<Q")
QUARANTINE_SUFFIX = ".corrupt"


def tuning_fingerprint(extra: dict | None = None) -> dict:
    """The device platform/topology identity a tuned optimum is only
    valid on. Narrower than the AOT cache's runtime fingerprint on
    purpose: serialized executables break on a jax/jaxlib bump, but a
    measured chunk optimum survives one — it breaks when the HARDWARE
    (or the mesh shape) changes."""
    import jax

    devices = jax.devices()
    fp = {
        "platform": jax.default_backend(),
        "device_count": len(devices),
        "device_kinds": sorted({d.device_kind for d in devices}),
        "process_count": jax.process_count(),
    }
    if extra:
        fp.update(extra)
    return fp


def _key_digest(key: tuple) -> str:
    """Stable digest of a tuning key (tuples of scalars). The
    fingerprint stays OUT of the name so a runtime change overwrites
    stale entries in place instead of stranding them (the aot_cache
    rule)."""
    raw = json.dumps([str(k) for k in key]).encode()
    return hashlib.sha256(raw).hexdigest()[:32]


class TuningCache:
    """Disk tier under the Autotuner. ``load(key)`` returns the stored
    payload dict (or None — absent, wrong-fingerprint, or corrupt);
    ``store(key, payload)`` persists atomically."""

    ENTRIES_TTL_S = 5.0   # entries() rescans the dir at most this often

    def __init__(self, root: str | os.PathLike, registry=None,
                 fingerprint_extra: dict | None = None):
        self.root = pathlib.Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.fingerprint = tuning_fingerprint(fingerprint_extra)
        self.hits = 0            # guarded-by: self._lock
        self.misses = 0          # guarded-by: self._lock
        self.mismatches = 0      # guarded-by: self._lock
        self.errors = 0          # guarded-by: self._lock
        self.quarantined = 0     # guarded-by: self._lock
        self.writes = 0          # guarded-by: self._lock
        # deliberately UNguarded (atomic tuple swap; staleness is fine
        # for a stats field): see entries()
        self._entries_cache: tuple | None = None
        self._lock = threading.Lock()
        self._hits_c = self._misses_c = None
        if registry is not None:
            self._hits_c = registry.counter(
                "tts_tuner_cache_hits_total",
                "tuned dispatch params replayed from the tuning cache "
                "(zero probes paid)")
            self._misses_c = registry.counter(
                "tts_tuner_cache_misses_total",
                "tuning-cache lookups with no loadable entry (absent, "
                "wrong-fingerprint, or quarantined corrupt)")

    # ---------------------------------------------------------- paths

    def path_for(self, key: tuple) -> pathlib.Path:
        return self.root / f"{_key_digest(key)}.tune"

    # ----------------------------------------------------------- load

    def load(self, key: tuple) -> dict | None:
        """The stored payload for `key`, or None. Never raises: corrupt
        entries quarantine, wrong-fingerprint entries are ignored (the
        next probe overwrites them), and the caller probes as if the
        cache were empty."""
        path = self.path_for(key)
        try:
            blob = path.read_bytes()
        except FileNotFoundError:
            self._count("_misses_c", "misses")
            return None
        except OSError as e:
            self._count("_misses_c", "errors")
            tracelog.event("tuner_cache.read_error", path=path.name,
                           error=repr(e))
            return None
        try:
            if blob[:len(MAGIC)] != MAGIC:
                raise ValueError("bad magic")
            off = len(MAGIC)
            (hdr_len,) = _HDR_LEN.unpack_from(blob, off)
            off += _HDR_LEN.size
            header = json.loads(blob[off:off + hdr_len].decode())
            off += hdr_len
            payload_raw = blob[off:]
            if len(payload_raw) != int(header["payload_len"]):
                raise ValueError("truncated payload")
            if zlib.crc32(payload_raw) != int(header["payload_crc32"]):
                raise ValueError("payload CRC mismatch")
            payload = json.loads(payload_raw.decode())
        except Exception as e:  # noqa: BLE001 — torn/truncated/garbled
            self._quarantine(path, repr(e))
            return None
        if header.get("fingerprint") != self.fingerprint:
            with self._lock:
                self.mismatches += 1
            self._count("_misses_c", "misses")
            tracelog.event("tuner_cache.mismatch", path=path.name,
                           theirs=header.get("fingerprint"),
                           ours=self.fingerprint)
            return None
        self._count("_hits_c", "hits")
        tracelog.event("tuner_cache.hit", path=path.name,
                       key=header.get("key"))
        return payload

    def _quarantine(self, path: pathlib.Path, error: str) -> None:
        self._count("_misses_c", "errors")
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
            self._entries_cache = None   # one fewer .tune on disk
        except OSError:
            qpath = None
        tracelog.event("tuner_cache.quarantine", path=path.name,
                       quarantined_to=qpath, error=error)

    # ---------------------------------------------------------- store

    def store(self, key: tuple, payload: dict, key_repr: str = "") -> None:
        """Persist `payload` for `key`: CRC stamp, temp + fsync +
        atomic rename (readers see old bytes or new, never torn).
        Synchronous — entries are a few hundred bytes, written once
        per cold shape."""
        payload_raw = json.dumps(payload, sort_keys=True).encode()
        header = json.dumps({
            "v": 1, "fingerprint": self.fingerprint, "key": key_repr,
            "created_unix": time.time(),
            "payload_len": len(payload_raw),
            "payload_crc32": zlib.crc32(payload_raw),
        }).encode()
        path = self.path_for(key)
        tmp = path.with_name(
            f".{path.name}.{os.getpid()}-{threading.get_ident()}.tmp")
        try:
            with open(tmp, "wb") as f:
                f.write(MAGIC)
                f.write(_HDR_LEN.pack(len(header)))
                f.write(header)
                f.write(payload_raw)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        with self._lock:
            self.writes += 1
        self._entries_cache = None       # count may have changed
        tracelog.event("tuner_cache.store", path=path.name,
                       key=key_repr, bytes=len(payload_raw))

    # ----------------------------------------------------------- read

    def _count(self, counter_attr: str, field: str) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)
        c = getattr(self, counter_attr)
        if c is not None:
            c.inc()

    def entries(self) -> int:
        """Entry-file count, rescanned at most every ENTRIES_TTL_S —
        status_snapshot() reaches here at poll frequency and must not
        pay a directory scan per tick on slow fleet storage (the
        aot_cache rule; invalidated on write/quarantine)."""
        now = time.monotonic()
        cached = self._entries_cache
        if cached is not None and now - cached[0] < self.ENTRIES_TTL_S:
            return cached[1]
        try:
            n = sum(1 for p in self.root.iterdir()
                    if p.suffix == ".tune")
        except OSError:
            n = 0
        self._entries_cache = (now, n)
        return n

    def snapshot(self) -> dict:
        """JSON-safe stats — status_snapshot()'s `tuner` cache view."""
        n = self.entries()
        with self._lock:
            return {"dir": str(self.root), "entries": n,
                    "hits": self.hits, "misses": self.misses,
                    "mismatches": self.mismatches,
                    "errors": self.errors,
                    "quarantined": self.quarantined,
                    "writes": self.writes}
