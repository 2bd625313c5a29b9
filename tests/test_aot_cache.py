"""Zero-compile cold start: the disk-persistent AOT executable cache.

The contract, pinned deterministically on the virtual 8-device CPU
mesh:

- a restarted process (fresh ExecutorCache + AOTCache over the same
  directory) replays previously-compiled loops from disk with ZERO
  ``lower()``/``compile()`` calls (``_Entry._compile_fresh`` is
  instrumented to prove it) and bit-identical search results;
- executor-ready latency with a warm cache is >= 5x faster than a cold
  compile (the acceptance bar; measured ~8-10x here);
- a fingerprint-mismatched entry (wrong runtime) is IGNORED and
  recompiled — never loaded — and the recompile overwrites it;
- a corrupt or truncated entry is QUARANTINED (renamed ``*.corrupt``),
  recompiled to bit-identical results, and never loaded again;
- donated vs non-donated loop variants are keyed (and persisted)
  separately;
- boot pre-warm is idempotent, bounded, covers the spool backlog, and
  a pre-warmed shape's first request pays no compile;
- when serialization is unsupported (per-program or probe-wide) the
  cache degrades to in-memory-only, loudly but harmlessly;
- the health layer's compile_storm rule does NOT fire on a boot-time
  disk replay (true unplanned compiles still fire it);
- on XLA:CPU an executable served by XLA's persistent compilation
  cache is never persisted (its bytes would load and then fail).

Each test runs with XLA's persistent cache off (a CLI test earlier in
the same worker turns it on): the contracts above are about the disk
tier against fresh compiles.
"""

import contextlib
import json
import os
import sys
import time

import jax
import pytest

from tpu_tree_search.engine import distributed
from tpu_tree_search.parallel.mesh import worker_mesh
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.service import SearchRequest, SearchServer
from tpu_tree_search.service.aot_cache import (AOTCache, probe,
                                               runtime_fingerprint)
from tpu_tree_search.service import aot_cache as aot_mod
from tpu_tree_search.service import executors as ex_mod
from tpu_tree_search.service.executors import ExecutorCache
from tpu_tree_search.utils import compile_cache

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))

KW = dict(chunk=8, capacity=1 << 12, min_seed=4)


def small(seed, jobs=7, machines=3):
    return PFSPInstance.synthetic(jobs=jobs, machines=machines,
                                  seed=seed)


def run_search(p, cache, mesh=None, **kw):
    args = {**KW, **kw}
    got = distributed.search(p, lb_kind=args.pop("lb_kind", 1),
                             mesh=mesh or worker_mesh(4),
                             loop_cache=cache, **args)
    return (got.explored_tree, got.explored_sol, got.best)


@pytest.fixture(autouse=True)
def _no_xla_cache():
    with compile_cache.disabled():
        yield


@contextlib.contextmanager
def xla_cache(path):
    """XLA's persistent compilation cache on, in `path`, caching every
    program however fast it compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    old = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, (True, str(path), 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for n, v in zip(names, old):
            jax.config.update(n, v)
        compilation_cache.reset_cache()


@pytest.fixture
def no_fresh_compiles(monkeypatch):
    """Instrument the ONLY trace/compile door in the executor entry;
    the test asserts the recorded list stays empty. (A plain raise
    would be swallowed by the first-call fallback and hide the compile
    it was meant to catch.)"""
    calls = []
    orig = ex_mod._Entry._compile_fresh

    def spy(self, *args):
        calls.append(self.record.get("key"))
        return orig(self, *args)

    monkeypatch.setattr(ex_mod._Entry, "_compile_fresh", spy)
    return calls


def test_probe_supported_on_this_pin():
    """The pinned jax round-trips executables on the CPU backend (when
    this starts failing after a pin bump, the cache degrades to
    in-memory-only by design — see the fallback test below)."""
    assert probe() is True


def test_restart_replay_zero_compiles_bit_identical(tmp_path,
                                                    no_fresh_compiles):
    inst = small(5, jobs=8)
    root = tmp_path / "aot"

    # lifetime 1: cold — compiles (exactly one fresh compile), persists
    aot1 = AOTCache(root)
    c1 = ExecutorCache(aot=aot1)
    ref = run_search(inst.p_times, c1)
    assert no_fresh_compiles and len(no_fresh_compiles) == 1
    led1 = c1.ledger_snapshot()
    assert [e["source"] for e in led1] == ["compile"]
    aot1.drain()
    assert aot1.snapshot()["writes"] == 1
    aot1.close()
    no_fresh_compiles.clear()

    # lifetime 2: fresh in-process caches over the same dir — the
    # restarted server. ZERO lower()/compile() calls, ledger says disk,
    # results bit-identical.
    aot2 = AOTCache(root)
    c2 = ExecutorCache(aot=aot2)
    got = run_search(inst.p_times, c2)
    assert got == ref
    assert no_fresh_compiles == []
    led2 = c2.ledger_snapshot()
    assert [e["source"] for e in led2] == ["disk"]
    assert led2[0]["deserialize_s"] > 0
    assert led2[0]["trace_s"] == 0.0 and led2[0]["compile_s"] == 0.0
    snap = aot2.snapshot()
    assert snap["hits"] == 1 and snap["errors"] == 0
    assert c2.storm_signal() == 0       # a replay is not a compile
    aot2.close()


def test_executor_ready_latency_warm_5x_faster(tmp_path):
    """The acceptance bar: executor-ready latency on the CPU test mesh
    drops >= 5x with a warm cache dir (measured ~8-10x; the margin
    absorbs CI noise). Production shapes compile for minutes while the
    deserialize stays sub-second, so the real-world ratio is larger."""
    p = small(0, jobs=20, machines=10).p_times
    mesh = worker_mesh(8)
    root = tmp_path / "aot"

    def executor_ready(expect):
        # fresh in-process caches each time: every warm measurement is
        # a true restart (disk entry only), never a memo hit
        aot = AOTCache(root)
        cache = ExecutorCache(aot=aot)
        t0 = time.perf_counter()
        how = distributed.prewarm(p, chunk=64, capacity=1 << 14,
                                  mesh=mesh, loop_cache=cache)
        dt = time.perf_counter() - t0
        assert how == expect
        aot.drain()
        aot.close()
        return dt

    cold = executor_ready("compile")
    # best-of-3 on the warm side: the ~0.1 s deserialize is small
    # enough that one unlucky scheduler stall under a loaded test
    # process can halve the measured ratio; the minimum is the honest
    # capability number (the cold compile is seconds — one sample is
    # stable)
    warm = min(executor_ready("disk") for _ in range(3))
    ratio = cold / warm
    assert ratio >= 5.0, f"warm only {ratio:.1f}x faster: " \
                         f"cold={cold:.3f}s warm={warm:.3f}s"


def test_fingerprint_mismatch_ignored_never_loaded(tmp_path,
                                                   no_fresh_compiles):
    inst = small(3, jobs=8)
    root = tmp_path / "aot"

    # runtime A persists an entry
    aot_a = AOTCache(root, fingerprint_extra={"sim_runtime": "A"})
    ca = ExecutorCache(aot=aot_a)
    ref = run_search(inst.p_times, ca)
    aot_a.drain()
    aot_a.close()
    assert len(no_fresh_compiles) == 1
    no_fresh_compiles.clear()

    # runtime B (injected fingerprint drift — the jax-bump/telemetry-
    # flip simulation) must IGNORE it and recompile, bit-identically
    aot_b = AOTCache(root, fingerprint_extra={"sim_runtime": "B"})
    cb = ExecutorCache(aot=aot_b)
    got = run_search(inst.p_times, cb)
    assert got == ref
    assert len(no_fresh_compiles) == 1          # recompiled, once
    assert [e["source"] for e in cb.ledger_snapshot()] == ["compile"]
    snap = aot_b.snapshot()
    assert snap["mismatches"] == 1 and snap["hits"] == 0
    # a mismatch is not corruption: nothing quarantined, and B's
    # recompile OVERWRITES the stale entry (latest runtime wins)
    assert snap["quarantined"] == 0
    aot_b.drain()
    aot_b.close()
    no_fresh_compiles.clear()

    # runtime B restarted: its own entry now loads
    aot_b2 = AOTCache(root, fingerprint_extra={"sim_runtime": "B"})
    cb2 = ExecutorCache(aot=aot_b2)
    assert run_search(inst.p_times, cb2) == ref
    assert no_fresh_compiles == []
    assert aot_b2.snapshot()["hits"] == 1
    aot_b2.close()


def test_xla_cache_served_executable_not_persisted(tmp_path):
    """XLA:CPU serializes an executable that its persistent cache
    served into bytes that load and then fail at execution ("Function
    ... not found"). The disk tier must keep such an executable in
    memory, so that a restart compiles instead of failing."""
    inst = small(8, jobs=8)
    root = tmp_path / "aot"
    with xla_cache(tmp_path / "xla"):
        ref = run_search(inst.p_times, ExecutorCache())   # fills XLA's
        aot1 = AOTCache(root)
        c1 = ExecutorCache(aot=aot1)
        assert run_search(inst.p_times, c1) == ref
        (rec,) = c1.ledger_snapshot()
        assert rec["source"] == "compile" and rec["xla_cache_hit"]
        aot1.drain()
        assert aot1.snapshot()["writes"] == 0
        aot1.close()
        aot2 = AOTCache(root)
        c2 = ExecutorCache(aot=aot2)
        assert run_search(inst.p_times, c2) == ref
        assert [e["source"] for e in c2.ledger_snapshot()] == ["compile"]
        aot2.close()


def test_old_format_entry_ignored_not_quarantined(tmp_path,
                                                   no_fresh_compiles):
    """An entry written in another payload layout (header ``v``) is a
    wrong-world entry, like a fingerprint mismatch: ignored, counted,
    recompiled over — never loaded, never quarantined as corrupt."""
    inst = small(5, jobs=8)
    root = tmp_path / "aot"
    aot1 = AOTCache(root)
    ref = run_search(inst.p_times, ExecutorCache(aot=aot1))
    aot1.drain()
    aot1.close()
    no_fresh_compiles.clear()

    (entry,) = [p for p in root.iterdir() if p.suffix == ".aot"]
    blob = entry.read_bytes()
    off = len(aot_mod.MAGIC)
    (hdr_len,) = aot_mod._HDR_LEN.unpack_from(blob, off)
    off += aot_mod._HDR_LEN.size
    header = json.loads(blob[off:off + hdr_len])
    assert header["v"] == aot_mod.FORMAT
    old = json.dumps({**header, "v": aot_mod.FORMAT - 1}).encode()
    entry.write_bytes(aot_mod.MAGIC + aot_mod._HDR_LEN.pack(len(old))
                      + old + blob[off + hdr_len:])

    aot2 = AOTCache(root)
    assert run_search(inst.p_times, ExecutorCache(aot=aot2)) == ref
    assert len(no_fresh_compiles) == 1
    snap = aot2.snapshot()
    assert snap["mismatches"] == 1 and snap["hits"] == 0
    assert snap["quarantined"] == 0 and snap["errors"] == 0
    aot2.close()


@pytest.mark.parametrize("damage", ["flip", "truncate"])
def test_corrupt_entry_quarantined_and_recompiled(tmp_path, damage,
                                                  no_fresh_compiles):
    inst = small(4, jobs=8)
    root = tmp_path / "aot"
    aot1 = AOTCache(root)
    ref = run_search(inst.p_times, ExecutorCache(aot=aot1))
    aot1.drain()
    aot1.close()
    no_fresh_compiles.clear()

    (entry,) = [p for p in root.iterdir() if p.suffix == ".aot"]
    blob = bytearray(entry.read_bytes())
    if damage == "flip":
        blob[len(blob) // 2] ^= 0xFF            # payload bit-flip
        entry.write_bytes(bytes(blob))
    else:
        entry.write_bytes(bytes(blob[:len(blob) // 2]))  # torn write

    aot2 = AOTCache(root)
    c2 = ExecutorCache(aot=aot2)
    got = run_search(inst.p_times, c2)
    assert got == ref                            # bit-identical recompile
    assert len(no_fresh_compiles) == 1
    snap = aot2.snapshot()
    assert snap["errors"] == 1 and snap["quarantined"] == 1
    assert snap["hits"] == 0
    # the poisoned bytes are parked beside the cache, never loadable
    quarantined = [p for p in root.iterdir()
                   if p.name.endswith(".corrupt")]
    assert len(quarantined) == 1
    aot2.drain()     # the recompile re-persisted a clean entry
    assert aot2.snapshot()["writes"] == 1
    aot2.close()
    no_fresh_compiles.clear()

    aot3 = AOTCache(root)
    assert run_search(inst.p_times, ExecutorCache(aot=aot3)) == ref
    assert no_fresh_compiles == []
    assert aot3.snapshot()["hits"] == 1
    aot3.close()


def test_repeat_quarantines_keep_distinct_forensic_copies(tmp_path):
    """Quarantine targets are per-writer unique AND counter-suffixed:
    corrupt incarnations of the SAME entry quarantined twice (same
    process, or N servers racing on shared fleet storage) keep both
    forensic copies instead of os.replace-ing over each other."""
    root = tmp_path / "aot"
    aot = AOTCache(root)
    key = ("probe", "key")
    for round_ in range(2):
        aot.path_for(key).write_bytes(b"\xffnot-an-entry" * 4)
        assert aot.load(key) is None
    quarantined = sorted(p.name for p in root.iterdir()
                         if p.name.endswith(".corrupt"))
    assert len(quarantined) == 2, quarantined
    assert len(set(quarantined)) == 2
    assert aot.snapshot()["quarantined"] == 2
    aot.close()


def test_donated_variant_keyed_separately(tmp_path):
    p = small(0, jobs=8).p_times
    mesh = worker_mesh(4)
    aot = AOTCache(tmp_path / "aot")
    cache = ExecutorCache(aot=aot)
    assert distributed.prewarm(p, chunk=8, capacity=4096, mesh=mesh,
                               loop_cache=cache,
                               donate=False) == "compile"
    assert distributed.prewarm(p, chunk=8, capacity=4096, mesh=mesh,
                               loop_cache=cache,
                               donate=True) == "compile"
    ledger = cache.ledger_snapshot()
    assert len(ledger) == 2
    assert [("donate" in e["key"]) for e in ledger] == [False, True]
    aot.drain()
    assert aot.snapshot()["writes"] == 2         # two distinct files
    assert aot.snapshot()["entries"] == 2
    # idempotent: warming again is a no-op on both variants
    assert distributed.prewarm(p, chunk=8, capacity=4096, mesh=mesh,
                               loop_cache=cache, donate=True) == "warm"
    aot.close()


def test_prewarm_boot_idempotent_spool_and_first_request(tmp_path):
    """serve-boot pre-warm: explicit JxM + spool-backlog shapes are
    readied per submesh before any request; a second boot pass is a
    no-op; the first request of a pre-warmed shape pays no compile."""
    from tpu_tree_search.service import spool

    inst = small(7, jobs=7)
    spool_dir = tmp_path / "spool"
    spool.submit_file(spool_dir, {"p_times": inst.p_times.tolist(),
                                  "lb": 1, "chunk": 8,
                                  "capacity": 4096, "min_seed": 4})
    with SearchServer(n_submeshes=2, workdir=tmp_path / "wd",
                      segment_iters=256,
                      aot_cache_dir=tmp_path / "aot",
                      share_incumbent=False) as srv:
        s1 = srv.prewarm_boot(spec="spool", spool_dir=spool_dir,
                              concurrency=1)
        assert s1["shapes"] == 1 and s1["warms"] == 2   # per submesh
        assert s1["by"]["compile"] == 2 and s1["errors"] == 0
        # idempotent: the same boot pass again readies nothing new
        s2 = srv.prewarm_boot(spec="spool", spool_dir=spool_dir)
        assert s2["by"] == {"disk": 0, "compile": 0, "warm": 2,
                            "skipped": 0}
        assert len(srv.cache) == 2
        # planned compiles never read as a storm
        assert srv.cache.storm_signal() == 0
        # the pre-warmed shape's first request: in-memory hit, no
        # further build — warm capacity existed before it arrived
        misses0 = srv.cache.snapshot()["misses"]
        rid = srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                       **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "DONE"
        assert srv.cache.snapshot()["misses"] == misses0
        assert srv.status_snapshot()["aot_cache"]["writes"] == 2


def test_server_restart_replay_end_to_end(tmp_path, no_fresh_compiles):
    """The acceptance demo at the service level: a restarted
    SearchServer re-serves a previously-served shape with zero fresh
    compiles (ledger source=disk) and bit-identical results."""
    inst = small(9, jobs=8)
    aot_dir = tmp_path / "aot"

    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd1",
                      segment_iters=256, aot_cache_dir=aot_dir,
                      share_incumbent=False) as srv:
        rid = srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                       **KW))
        rec = srv.result(rid, timeout=300)
        assert rec.state == "DONE"
        ref = (rec.result.explored_tree, rec.result.explored_sol,
               rec.result.best)
        assert [e["source"] for e in
                srv.status_snapshot()["compile_ledger"]] == ["compile"]
    assert len(no_fresh_compiles) == 1
    no_fresh_compiles.clear()

    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd2",
                      segment_iters=256, aot_cache_dir=aot_dir,
                      share_incumbent=False) as srv2:
        rid = srv2.submit(SearchRequest(p_times=inst.p_times,
                                        lb_kind=1, **KW))
        rec = srv2.result(rid, timeout=300)
        assert rec.state == "DONE"
        assert (rec.result.explored_tree, rec.result.explored_sol,
                rec.result.best) == ref
        snap = srv2.status_snapshot()
        assert [e["source"] for e in snap["compile_ledger"]] == ["disk"]
        assert snap["aot_cache"]["hits"] == 1
    assert no_fresh_compiles == []


def test_serialize_unsupported_per_program_fallback(tmp_path,
                                                    monkeypatch):
    """A program the pin cannot serialize still serves from memory:
    store counts an error, writes nothing, and the search is green."""
    from jax.experimental import serialize_executable as se

    def boom(compiled):
        raise TypeError("cannot serialize this program (simulated)")

    monkeypatch.setattr(se, "serialize", boom)
    inst = small(2, jobs=8)
    aot = AOTCache(tmp_path / "aot")
    cache = ExecutorCache(aot=aot)
    ref = run_search(inst.p_times, cache)
    aot.drain()
    snap = aot.snapshot()
    assert snap["writes"] == 0 and snap["errors"] == 1
    assert snap["entries"] == 0
    # the in-memory entry still serves the next same-shape request
    assert run_search(inst.p_times, cache) == ref
    assert cache.snapshot()["hits"] >= 1
    aot.close()


def test_probe_failure_degrades_to_memory_only(tmp_path, monkeypatch):
    """When the capability probe says the pin cannot round-trip a
    program, the server constructs NO disk tier (aot is None, the
    snapshot says so) and serves exactly as before PR 8."""
    monkeypatch.setattr(aot_mod, "_probe_result", False)
    inst = small(1, jobs=7)
    with SearchServer(n_submeshes=1, workdir=tmp_path / "wd",
                      segment_iters=256,
                      aot_cache_dir=tmp_path / "aot",
                      share_incumbent=False) as srv:
        assert srv.aot is None
        rid = srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                       **KW))
        assert srv.result(rid, timeout=300).state == "DONE"
        assert srv.status_snapshot()["aot_cache"] is None
    assert not (tmp_path / "aot").exists()


def test_compile_storm_rule_ignores_replay_counts_fresh(tmp_path):
    """The health satellite: a boot-time mass disk replay must not
    fire compile_storm; the same number of true unplanned compiles
    must."""
    import types

    from tpu_tree_search.obs import health as obs_health

    p = small(0, jobs=8).p_times
    mesh = worker_mesh(4)
    root = tmp_path / "aot"
    # seed the disk with both lb variants
    aot0 = AOTCache(root)
    c0 = ExecutorCache(aot=aot0)
    for lb in (1, 2):
        distributed.prewarm(p, lb_kind=lb, chunk=8, capacity=4096,
                            mesh=mesh, loop_cache=c0)
    aot0.drain()
    aot0.close()

    def monitor_for(cache):
        th = obs_health.Thresholds(compile_storm=2)
        return obs_health.HealthMonitor(
            server=types.SimpleNamespace(cache=cache), rules=[
                r for r in obs_health.default_rules(th)
                if r.name == "compile_storm"],
            thresholds=th, interval_s=0, autostart=False)

    # restarted lifetime: 2 disk replays inside one interval -> quiet
    aot1 = AOTCache(root)
    c1 = ExecutorCache(aot=aot1)
    mon = monitor_for(c1)
    mon.evaluate_now()                               # baseline
    for lb in (1, 2):
        distributed.prewarm(p, lb_kind=lb, chunk=8, capacity=4096,
                            mesh=mesh, loop_cache=c1)
    snap = mon.evaluate_now()
    assert snap["firing"] == 0
    assert [e["source"] for e in c1.ledger_snapshot()] == ["disk"] * 2
    aot1.close()

    # same count of TRUE unplanned compiles (no disk tier, request
    # path) -> fires
    c2 = ExecutorCache()
    mon2 = monitor_for(c2)
    mon2.evaluate_now()
    for lb in (1, 2):
        run_search(p, c2, lb_kind=lb)
    snap = mon2.evaluate_now()
    assert snap["firing"] == 1
    assert c2.storm_signal() == 2


def test_compile_report_renders_source_and_deserialize(tmp_path):
    import compile_report

    inst = small(6, jobs=8)
    root = tmp_path / "aot"
    aot1 = AOTCache(root)
    run_search(inst.p_times, ExecutorCache(aot=aot1))
    aot1.drain()
    aot1.close()
    aot2 = AOTCache(root)
    c2 = ExecutorCache(aot=aot2)
    run_search(inst.p_times, c2)
    table = compile_report.render(c2.ledger_snapshot(), c2.snapshot(),
                                  aot2.snapshot())
    assert "source" in table and "deser_s" in table
    assert "disk" in table and "replayed from disk" in table
    assert "aot disk cache" in table
    # the CLI path renders a full status-snapshot dump with the new key
    snap_path = tmp_path / "status.json"
    snap_path.write_text(json.dumps(
        {"compile_ledger": c2.ledger_snapshot(),
         "executor_cache": c2.snapshot(),
         "aot_cache": aot2.snapshot()}))
    assert compile_report.main([str(snap_path)]) == 0
    aot2.close()


def test_fingerprint_contents():
    """The fields a wrong-runtime load is rejected on (the telemetry
    width is the subtle one: the static flag changes traced state
    SHAPES without appearing in the executor key)."""
    fp = runtime_fingerprint()
    assert {"jax", "jaxlib", "platform", "device_count",
            "device_kinds", "process_count",
            "telemetry_width"} <= set(fp)
    assert runtime_fingerprint({"x": 1})["x"] == 1
    assert runtime_fingerprint() == fp           # deterministic
