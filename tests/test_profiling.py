"""Deep profiling & resource observability (the PR-5 layer):
on-demand profiler capture, device-memory/host-RSS gauges, the
compile-cost ledger and the perf regression sentry.

Load-bearing assertions:

- the profiler session is strictly one-at-a-time (second start -> 409's
  exception, never a corrupted capture) and a CPU capture leaves an
  artifact directory that `obs/chrome_trace.load_xla_trace` /
  `tools/search_report.py` can attribute self-time from;
- a server publishes per-device `tts_device_bytes_*` gauges (and host
  RSS) on its registry and RETIRES the series on close;
- the executor cache's ledger holds exactly one entry per cache key
  with nonzero trace+compile seconds, mirrored into the
  `tts_compile_seconds` histogram;
- `POST /profile` answers 200 with an artifact, 409 while a capture is
  running, 503 on a closed server;
- `tools/perf_sentry.py` returns pass / regression / rc-failure
  verdicts from fixture rows and exits nonzero on the failing ones.
"""

import json
import os
import pathlib
import sys
import time
import urllib.error
import urllib.request

import pytest

from tpu_tree_search.obs import chrome_trace, metrics, profiler
from tpu_tree_search.obs import resource as obs_resource
from tpu_tree_search.obs import tracelog
from tpu_tree_search.obs.httpd import start_http_server
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.service import SearchRequest, SearchServer
from tpu_tree_search.service.executors import ExecutorCache

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "tools"))

import perf_sentry  # noqa: E402

KW = dict(chunk=8, capacity=1 << 12, min_seed=4)


@pytest.fixture
def fresh_obs(tmp_path):
    log = tracelog.TraceLog(capacity=1 << 16,
                            sink_path=tmp_path / "trace.jsonl")
    prev_log = tracelog.install(log)
    reg = metrics.Registry()
    prev_reg = metrics.install(reg)
    try:
        yield log, reg
    finally:
        tracelog.install(prev_log)
        metrics.install(prev_reg)


# ------------------------------------------------------- profiler session

def test_profiler_session_mutual_exclusion_and_artifact(fresh_obs,
                                                        tmp_path):
    """One capture at a time; the artifact parses back through the
    shared chrome_trace path (CPU backend traces included)."""
    import jax.numpy as jnp

    log, reg = fresh_obs
    sess = profiler.ProfilerSession()
    d1 = sess.fresh_dir(tmp_path / "profiles")
    sess.start(d1)
    assert sess.active
    with pytest.raises(profiler.ProfilerBusyError):
        sess.start(sess.fresh_dir(tmp_path / "profiles"))
    # real device work inside the capture window
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    out = sess.stop()
    assert out == d1 and not sess.active
    # a second capture works after the first released
    with sess.trace(sess.fresh_dir(tmp_path / "profiles")):
        jnp.arange(16).sum().block_until_ready()
    # artifact on disk, parseable, self-times attributable on CPU
    events = chrome_trace.load_xla_trace(d1)
    assert events, "no trace events written"
    self_us, counts = chrome_trace.self_times(events)
    assert sum(self_us.values()) > 0
    # flight-recorded + counted
    caps = [r for r in log.records() if r["name"] == "profiler.capture"]
    assert len(caps) == 2 and caps[0]["logdir"] == d1
    assert reg.counter("tts_profile_captures_total").value() == 2


def test_fresh_dir_unique_and_reserved(tmp_path):
    sess = profiler.ProfilerSession()
    a = sess.fresh_dir(tmp_path)
    assert os.path.isdir(a)          # reserved at naming time, so two
    b = sess.fresh_dir(tmp_path)     # racing callers can never collide
    assert os.path.isdir(b) and a != b


def test_search_report_attributes_selftime_from_artifact(fresh_obs,
                                                         tmp_path):
    """The acceptance path: an XLA artifact directory renders a
    self-time attribution table via tools/search_report.py."""
    import jax.numpy as jnp

    import search_report

    d = profiler.session().fresh_dir(tmp_path)
    with profiler.trace(d):
        jnp.sort(jnp.ones((128, 128)) @ jnp.ones((128, 128))
                 ).block_until_ready()
    table = search_report.render_selftime(d)
    assert table is not None
    assert "self-time attribution" in table
    assert "bucket" in table
    assert search_report.main([d]) == 0
    # a dir with no trace is a loud error, not an empty table
    empty = tmp_path / "empty"
    empty.mkdir()
    assert search_report.main([str(empty)]) == 1


# ------------------------------------------------------- resource sampler

def test_resource_sampler_gauges_and_trace_lanes(fresh_obs):
    log, _ = fresh_obs
    reg = metrics.Registry()
    sampler = obs_resource.ResourceSampler(registry=reg, period_s=0.0,
                                           autostart=False)
    sample = sampler.sample()
    assert sample["devices"], "no devices in snapshot"
    text = reg.to_prometheus()
    # per-device labels on the virtual 8-device CPU mesh
    import jax
    for d in jax.devices():
        assert f'tts_device_bytes_in_use{{device="{d.id}"' in text
    assert "tts_host_rss_bytes" in text
    assert reg.gauge("tts_host_rss_bytes").value() > 0
    # the sweep is a trace event that renders as Perfetto counter lanes
    recs = [r for r in log.records() if r["name"] == "resource.sample"]
    assert len(recs) == 1
    doc = chrome_trace.to_chrome(log.records())
    lanes = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert any(l.startswith("device0 bytes_in_use") for l in lanes)
    assert any(l.startswith("host_rss_bytes") for l in lanes)
    # retire drops every series
    sampler.retire()
    assert "tts_device_bytes_in_use{" not in reg.to_prometheus()


def test_server_resource_gauges_present_and_retired_on_close(fresh_obs,
                                                             tmp_path):
    srv = SearchServer(n_submeshes=2, workdir=tmp_path,
                       autostart=False, resource_sample_s=0.05)
    try:
        t0 = time.monotonic()
        while 'tts_device_bytes_in_use{device="0"' \
                not in srv.metrics.to_prometheus():
            assert time.monotonic() - t0 < 60, "sampler never published"
            time.sleep(0.02)
        text = srv.metrics.to_prometheus()
        assert 'platform=' in text
        assert "tts_device_bytes_peak" in text
    finally:
        srv.close()
    # the cardinality valve: a closed server's series are gone
    text = srv.metrics.to_prometheus()
    assert "tts_device_bytes_in_use{" not in text
    assert "tts_device_bytes_peak{" not in text


def test_segmented_run_emits_resource_samples(fresh_obs):
    """engine/distributed heartbeat hook: every segment leaves a
    resource.sample event (memory lane next to the pool/steal lanes)."""
    from tpu_tree_search.engine import distributed

    log, _ = fresh_obs
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=3)
    distributed.search(inst.p_times, lb_kind=1, init_ub=None,
                       n_devices=4, segment_iters=64, **KW)
    samples = [r for r in log.records()
               if r["name"] == "resource.sample"]
    segs = [r for r in log.records() if r["name"] == "segment"]
    assert segs, "run was not segmented"
    assert len(samples) >= len(segs)


# ------------------------------------------------------- compile ledger

def test_compile_ledger_one_entry_per_key(fresh_obs, tmp_path):
    """Two same-shape instances share one entry (nonzero compile
    seconds, measured once); a different lb_kind adds a second."""
    from tpu_tree_search.engine import distributed

    reg = metrics.Registry()
    cache = ExecutorCache(registry=reg)
    a = PFSPInstance.synthetic(jobs=7, machines=3, seed=0)
    b = PFSPInstance.synthetic(jobs=7, machines=3, seed=1)
    for p, lb in [(a.p_times, 1), (b.p_times, 1), (a.p_times, 2)]:
        distributed.search(p, lb_kind=lb, init_ub=None, n_devices=4,
                           loop_cache=cache, **KW)
    ledger = cache.ledger_snapshot()
    assert len(ledger) == 2                    # lb=1 shared, lb=2 new
    for e in ledger:
        assert e["compile_s"] is not None and e["compile_s"] > 0
        assert e["trace_s"] is not None
        assert e["method"] in ("aot", "first_call")
    h = reg.histogram("tts_compile_seconds").snapshot()
    assert h["count"] == 2 and h["sum"] > 0
    # the snapshot schema the service tests pin stays frozen
    assert set(cache.snapshot()) == {"entries", "hits", "misses"}
    # compile_report renders the ledger from a status-snapshot dump
    import compile_report
    snap_path = tmp_path / "status.json"
    snap_path.write_text(json.dumps(
        {"compile_ledger": ledger, "executor_cache": cache.snapshot()}))
    assert compile_report.main([str(snap_path)]) == 0
    table = compile_report.render(ledger, cache.snapshot())
    assert "compile-cost ledger" in table
    assert ("aot" in table) or ("first_call" in table)


def test_ledger_rides_server_status_snapshot(fresh_obs, tmp_path):
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=1)
    with SearchServer(n_submeshes=1, workdir=tmp_path,
                      resource_sample_s=0) as srv:
        rid = srv.submit(SearchRequest(p_times=inst.p_times, lb_kind=1,
                                       **KW))
        assert srv.result(rid, timeout=300).state == "DONE"
        snap = srv.status_snapshot()
    json.dumps(snap)                          # JSON-safe end to end
    assert len(snap["compile_ledger"]) == 1
    entry = snap["compile_ledger"][0]
    assert entry["compile_s"] > 0
    assert "pfsp" in entry["key"]


# ------------------------------------------------------- POST /profile

def test_http_profile_capture_409_and_503(fresh_obs, tmp_path):
    srv = SearchServer(n_submeshes=2, workdir=tmp_path,
                       autostart=False, resource_sample_s=0)
    httpd = start_http_server(srv, profile_dir=str(tmp_path / "prof"))
    try:
        # happy path: 200 with an artifact directory on disk that the
        # chrome_trace path can parse
        r = urllib.request.urlopen(urllib.request.Request(
            httpd.url + "/profile?duration_s=0.2", method="POST"),
            timeout=60)
        assert r.status == 200
        body = json.loads(r.read())
        assert os.path.isdir(body["artifact"])
        assert body["artifact"].startswith(str(tmp_path / "prof"))
        assert chrome_trace.load_xla_trace(body["artifact"]) is not None
        # 409 while a capture is running
        sess = profiler.session()
        sess.start(sess.fresh_dir(tmp_path / "prof"))
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    httpd.url + "/profile?duration_s=0.1",
                    method="POST"), timeout=30)
            assert ei.value.code == 409
        finally:
            sess.stop()
        # 400 on a nonsense duration
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                httpd.url + "/profile?duration_s=-3", method="POST"),
                timeout=30)
        assert ei.value.code == 400
        # 503 once the server is closing
        srv.close()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                httpd.url + "/profile?duration_s=0.1", method="POST"),
                timeout=30)
        assert ei.value.code == 503
    finally:
        httpd.close()
        srv.close()


# --------------------------------------------------------- perf sentry

def _wrapper(tmp_path, name, rc=0, rows=(), parsed=None, **extra):
    tail = "\n".join(json.dumps(r) for r in rows)
    path = tmp_path / name
    path.write_text(json.dumps(
        {"n": 1, "cmd": "python bench.py", "rc": rc, "tail": tail,
         "parsed": parsed, **extra}))
    return str(path)


def _row(metric="pfsp_ta021_lb1_node_evals_per_sec_per_chip",
         value=1e8, **kw):
    return {"metric": metric, "value": value,
            "unit": "node_evals_per_sec", "platform": "tpu", **kw}


def test_perf_sentry_rc_failure_fails_loudly(tmp_path):
    f = _wrapper(tmp_path, "BENCH_r07.json", rc=1)
    rc = perf_sentry.main([f, "--dir", str(tmp_path),
                           "--out", str(tmp_path / "s.md")])
    assert rc == 1
    md = (tmp_path / "s.md").read_text()
    assert "FAIL" in md and "rc=1" in md
    # report-only mode still says FAIL but exits 0 (the CI leg)
    assert perf_sentry.main([f, "--dir", str(tmp_path),
                             "--report-only"]) == 0


def test_perf_sentry_regression_and_pass(tmp_path, capsys):
    _wrapper(tmp_path, "BENCH_r01.json", rows=[_row(value=1.0e8)])
    # regression: 20% below the best prior value, default threshold 10%
    bad = _wrapper(tmp_path, "BENCH_r02.json", rows=[_row(value=0.8e8)])
    assert perf_sentry.main([bad, "--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and "-20.0%" in out
    # pass: within threshold
    ok = _wrapper(tmp_path, "BENCH_r03.json", rows=[_row(value=0.95e8)])
    assert perf_sentry.main([ok, "--dir", str(tmp_path)]) == 0
    # a looser explicit threshold un-fails the regression
    assert perf_sentry.main([bad, "--dir", str(tmp_path),
                             "--threshold", "0.3"]) == 0


def test_perf_sentry_lower_is_better_direction(tmp_path, capsys):
    """The segment-gap family regresses UPWARD: the reference is the
    MINIMUM prior value and a value above it by more than the threshold
    FAILs, while a further drop passes (and becomes the new best)."""
    gap = "pfsp_ta014_segment_gap_s"
    _wrapper(tmp_path, "BENCH_r01.json",
             rows=[_row(metric=gap, value=0.004,
                        unit="seconds_per_boundary")])
    # a LOWER later round must be the retained reference, not the max
    _wrapper(tmp_path, "BENCH_r02.json",
             rows=[_row(metric=gap, value=0.002,
                        unit="seconds_per_boundary")])
    # +100% above the 0.002 minimum prior: a first-class FAIL
    bad = _wrapper(tmp_path, "BENCH_r03.json",
                   rows=[_row(metric=gap, value=0.004,
                              unit="seconds_per_boundary")])
    assert perf_sentry.main([bad, "--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and "lowest prior" in out and "0.002" in out
    # dropping further than the reference passes (overlap doing its job)
    ok = _wrapper(tmp_path, "BENCH_r04.json",
                  rows=[_row(metric=gap, value=0.0)])
    assert perf_sentry.main([ok, "--dir", str(tmp_path)]) == 0
    # the machine-readable verdict carries the direction
    jp = tmp_path / "sentry.json"
    perf_sentry.main([bad, "--dir", str(tmp_path), "--report-only",
                      "--json", str(jp)])
    j = json.loads(jp.read_text())
    m = [v for v in j["metrics"] if v["metric"] == gap][0]
    assert m["direction"] == "lower" and m["verdict"] == "FAIL"


def test_perf_sentry_overlap_mode_not_cross_compared(tmp_path, capsys):
    """A gap row's TTS_OVERLAP mode travels with it: an overlap-off
    round judged against an overlap-on ~0.0 reference (or vice versa)
    is SKIP, not FAIL — a sync gap is not a pipelined-gap regression."""
    gap = "pfsp_ta014_segment_gap_s"
    _wrapper(tmp_path, "BENCH_r01.json",
             rows=[_row(metric=gap, value=0.0,
                        unit="seconds_per_boundary", overlap=1)])
    off = _wrapper(tmp_path, "BENCH_r02.json",
                   rows=[_row(metric=gap, value=0.0021,
                              unit="seconds_per_boundary", overlap=0)])
    assert perf_sentry.main([off, "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "SKIP" in out and "overlap mode" in out
    # same mode still compares (and FAILs on a real upward move)
    bad = _wrapper(tmp_path, "BENCH_r03.json",
                   rows=[_row(metric=gap, value=0.004,
                              unit="seconds_per_boundary", overlap=1)])
    assert perf_sentry.main([bad, "--dir", str(tmp_path)]) == 1


def test_perf_sentry_platform_mismatch_not_rate_compared(tmp_path,
                                                         capsys):
    """A CPU row (JAX_PLATFORMS=cpu, the CI leg) must not be judged
    against TPU history — and must not FAIL."""
    _wrapper(tmp_path, "BENCH_r01.json",
             rows=[_row(value=1.0e8, platform="tpu")])
    cpu = _wrapper(tmp_path, "BENCH_r02.json",
                   rows=[_row(value=2e5, platform="cpu")])
    assert perf_sentry.main([cpu, "--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "SKIP" in out and "rate not compared" in out
    # same platform still compares (and regresses)
    tpu = _wrapper(tmp_path, "BENCH_r03.json",
                   rows=[_row(value=0.5e8, platform="tpu")])
    assert perf_sentry.main([tpu, "--dir", str(tmp_path)]) == 1


def test_perf_sentry_latest_round_auto_discovery(tmp_path, capsys):
    _wrapper(tmp_path, "BENCH_r01.json", rows=[_row(value=1.0e8)])
    _wrapper(tmp_path, "BENCH_r02.json", rc=1)
    (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
         "tail": "dryrun ok"}))
    # no files given: judges ONLY the latest round (r02), r01 is history
    assert perf_sentry.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "BENCH_r02.json" in out and "BENCH_r01.json" not in out
    assert "MULTICHIP_r02.json" in out


def test_perf_sentry_reads_raw_bench_stdout(tmp_path):
    raw = tmp_path / "bench_row.jsonl"
    raw.write_text(json.dumps(_row(value=2e5, platform="cpu")) + "\n"
                   + "# lb=1 evals=...\n")
    assert perf_sentry.main([str(raw), "--dir", str(tmp_path)]) == 0
