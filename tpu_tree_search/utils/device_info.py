"""Device/host introspection (the reference's gpu_info,
common/gpu_util.cu:5-17, re-expressed for the JAX device model).

Two jobs, both read-only:

- memory introspection: :func:`memory_snapshot` (per-device
  bytes-in-use/peak/limit, with a live-array fallback for backends
  like CPU whose ``memory_stats()`` returns nothing) and
  :func:`host_rss_bytes` — the read path under
  ``obs/resource.ResourceSampler``'s gauges and memory lanes;
- human-readable :func:`describe_devices` / :func:`print_device_info`
  (the CLI ``devices`` subcommand).

Profiling does NOT live here any more: the trace-around-a-block helper
moved to ``obs/profiler.trace`` (one-at-a-time session semantics; no
direct ``jax.profiler`` calls outside ``obs/``).
"""

from __future__ import annotations

import os

import jax


def describe_devices() -> list[dict]:
    """One record per addressable device (platform, kind, process, memory
    stats when the backend exposes them)."""
    out = []
    for d in jax.devices():
        rec = {
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", "?"),
            "process": getattr(d, "process_index", 0),
        }
        try:
            stats = d.memory_stats()
            if stats:
                rec["bytes_in_use"] = stats.get("bytes_in_use")
                rec["bytes_limit"] = stats.get("bytes_limit")
        except Exception:
            pass
        out.append(rec)
    return out


def _live_array_bytes() -> dict:
    """Live jax-array bytes per device id — the memory fallback for
    backends whose memory_stats() reports nothing (the CPU mesh the
    test suite runs on). Sharded arrays charge each shard to its own
    device."""
    out: dict = {}
    try:
        arrays = jax.live_arrays()
    except Exception:  # noqa: BLE001 — introspection must never raise
        return out
    for a in arrays:
        try:
            for s in a.addressable_shards:
                out[s.device.id] = out.get(s.device.id, 0) \
                    + int(getattr(s.data, "nbytes", 0))
        except Exception:  # noqa: BLE001 — deleted/donated arrays race
            continue
    return out


def memory_snapshot() -> list[dict]:
    """Per-device memory record for the resource sampler: ``id``,
    ``platform``, ``bytes_in_use`` (backend-reported, else live-array
    bytes), ``peak_bytes_in_use``/``bytes_limit`` when the backend
    reports them (None keys are omitted)."""
    fallback = None
    out = []
    for d in jax.devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        rec = {"id": int(d.id), "platform": d.platform}
        if stats:
            rec["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
            for src, dst in (("peak_bytes_in_use", "peak_bytes_in_use"),
                             ("bytes_limit", "bytes_limit")):
                if stats.get(src) is not None:
                    rec[dst] = int(stats[src])
        else:
            if fallback is None:
                fallback = _live_array_bytes()
            rec["bytes_in_use"] = int(fallback.get(d.id, 0))
        out.append(rec)
    return out


def host_rss_bytes() -> int | None:
    """This process's resident set size in bytes (Linux /proc, with a
    getrusage fallback); None when neither source exists."""
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        return rss_pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        rss_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss_kib) * 1024      # peak, not current — best effort
    except Exception:  # noqa: BLE001
        return None


def print_device_info() -> None:
    for rec in describe_devices():
        line = (f"Device {rec['id']}: {rec['platform']} ({rec['kind']}) "
                f"process {rec['process']}")
        if rec.get("bytes_limit"):
            line += (f", HBM {(rec.get('bytes_in_use') or 0) / 2**30:.2f}/"
                     f"{rec['bytes_limit'] / 2**30:.2f} GiB")
        print(line)
