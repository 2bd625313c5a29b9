"""The cell `flagship-4chip`: its manifest entries, a whole rehearsal
on a 4-device CPU mesh, and its five per-layer readers on fixtures (a
synthetic trace with collectives on four chips, recorder spans, and
per-chip counters)."""

import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark import trace_reduce as tr

CELL = "flagship-4chip"
ROOT = pathlib.Path(__file__).resolve().parents[2]
T0 = 100.0      # the recorder's clock origin on the host's monotonic clock


def test_the_cell_reports_nodes_per_second_and_setup():
    man = manifest.manifest()
    assert manifest.workload(man, CELL)["chips"] == 4
    e2e = [m["name"] for m in manifest.metrics_for(man, "end_to_end", CELL)]
    assert sorted(e2e) == ["setup_s", "tree_nodes_per_s"]
    layer = [m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)]
    assert sorted(layer) == sorted([
        "collective_share.dist", "tree_spread.dist", "chunk_fill.dist",
        "solve_host_ms.dist", "idle_share.dist"])


def test_rehearsal_on_four_cpu_devices_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 25), "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["device"] == {"platform": "cpu", "count": 4}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert line["notes"]["window_compiles"].get("lowered", 0) == 0
    # every solve of the window names its four chips' counters
    assert all(len(s[4]) == 4 for s in line["notes"]["solves"])


def ev(op, start_ms, dur_ms, plane="/device:TPU:0"):
    return tr.Event(plane, "XLA Ops" if plane.startswith("/device")
                    else "spans", op, start_ms * 1e6, dur_ms * 1e6)


def four_chip_trace():
    """Each chip busy 8 of a 10 ms window, 1 ms of it in collectives
    (chip 0: 2 ms)."""
    events = [ev("window", 0, 10, plane="/host:CPU")]
    for c in range(4):
        plane = f"/device:TPU:{c}"
        events += [ev("fusion.3 s32[20,65536]", 0, 4, plane),
                   ev("all-reduce.7 s32[]", 4, 0.5, plane),
                   ev("all-to-all.2 s16[20,819200]", 4.5, 0.5, plane),
                   ev("sort.1 u32[1310720]", 5, 3, plane)]
    # chip 0 spends one more ms of its busy time in an async gather
    events += [ev("all-gather-start.1 s32[4]", 8, 0.5),
               ev("all-gather-done.1 s32[4]", 8.5, 0.5)]
    return tr.summarize(events)


def trace_run(summary):
    return types.SimpleNamespace(trace=summary, notes={}, items=[],
                                 counters={})


def test_collective_share_reads_collective_ops_over_busy_time():
    r = trace_run(four_chip_trace())
    got = manifest.reader("collective_share.dist")(r)
    # 5 ms of collectives over 8+8+8+9 ms of busy time
    assert got == pytest.approx(100.0 * 5.0 / 33.0)
    assert set(r.notes["collective_ops_s"]) == {
        "all-reduce.7 s32[]", "all-to-all.2 s16[20,819200]",
        "all-gather-start.1 s32[4]", "all-gather-done.1 s32[4]"}


def test_idle_share_averages_the_chips():
    got = manifest.reader("idle_share.dist")(trace_run(four_chip_trace()))
    assert got == pytest.approx(100.0 * (1 - 33.0 / 4 / 10.0))


@pytest.mark.parametrize("name", ["collective_share.dist",
                                  "idle_share.dist"])
def test_trace_readers_give_nothing_untraced(name):
    assert manifest.reader(name)(trace_run(None)) is None


def solves_run(*chip_trees, iters=10, chunk=100):
    items = [{"tree": sum(t) + 7, "chip_tree": list(t),
              "chip_iters": [iters] * len(t)} for t in chip_trees]
    return types.SimpleNamespace(items=items, counters={"chunk": chunk},
                                 notes={}, trace=None)


def test_tree_spread_is_the_mean_largest_chip_over_mean():
    r = solves_run([100, 100, 100, 100], [400, 0, 0, 0], [160, 80, 80, 80])
    # 0 %, 300 %, 60 %
    assert manifest.reader("tree_spread.dist")(r) == pytest.approx(120.0)


def test_chunk_fill_counts_every_chip_iteration():
    r = solves_run([500, 500, 500, 500], [1000, 0, 0, 0])
    # (2007 + 1007) nodes over 2 solves x 4 chips x 10 iterations x 100
    assert manifest.reader("chunk_fill.dist")(r) == pytest.approx(
        100.0 * 3014 / 8000)


@pytest.mark.parametrize("name", ["tree_spread.dist", "chunk_fill.dist"])
def test_counter_readers_give_nothing_without_solves(name):
    assert manifest.reader(name)(solves_run()) is None


class Ring:
    def __init__(self, recs, dropped=0):
        self.t0, self.dropped = T0, dropped
        self._recs = [{"kind": "span", "pid": 1, **r} for r in recs]

    def records(self):
        return list(self._recs)


@pytest.fixture
def ring():
    from tpu_tree_search.obs import tracelog
    prev = tracelog.install(None)

    def install(recs, dropped=0):
        tracelog.install(Ring(recs, dropped))

    yield install
    tracelog.install(prev)


def solve(sid, ts):
    """One distributed solve's spans: prepare (warm-up, tables, seed and
    2 ms of its own), the run with its wait, the fetch."""
    prep = {"name": "request.prepare", "ts": ts, "dur": 0.030,
            "span_id": sid, "parent_id": None}
    kids = [("bfs_warmup", 0.010), ("dist.tables", 0.008),
            ("dist.seed", 0.010)]
    out, t = [prep], ts
    for k, (name, dur) in enumerate(kids, 1):
        out.append({"name": name, "ts": t, "dur": dur, "span_id": sid + k,
                    "parent_id": sid})
        t += dur
    run = {"name": "engine.run", "ts": ts + 0.030, "dur": 0.505,
           "span_id": sid + 5, "parent_id": None}
    wait = {"name": "segment.wait", "ts": ts + 0.034, "dur": 0.500,
            "span_id": sid + 6, "parent_id": sid + 5}
    fetch = {"name": "engine.fetch", "ts": ts + 0.535, "dur": 0.003,
             "span_id": sid + 7, "parent_id": None}
    return out + [run, wait, fetch]


def test_solve_host_ms_adds_prepare_dispatch_and_fetch(ring):
    ring(solve(10, 2.0) + solve(20, 3.0))
    r = types.SimpleNamespace(window=(T0 + 1, T0 + 10), notes={})
    got = manifest.reader("solve_host_ms.dist")(r)
    # 30 ms prepare + 5 ms dispatch + 3 ms fetch per solve
    assert got == pytest.approx(38.0)
    assert r.notes["solve_host_split_ms"] == pytest.approx({
        "bfs_warmup": 10.0, "tables": 8.0, "seed": 10.0,
        "prepare_rest": 2.0, "dispatch": 5.0, "fetch": 3.0})


def test_solve_host_ms_gives_nothing_without_a_fetch_span(ring):
    # what a program that records no `engine.fetch` leaves
    ring([s for s in solve(10, 2.0) if s["name"] != "engine.fetch"])
    r = types.SimpleNamespace(window=(T0 + 1, T0 + 10), notes={})
    assert manifest.reader("solve_host_ms.dist")(r) is None


def test_solve_host_ms_gives_nothing_when_the_ring_dropped_the_window(
        ring):
    ring([dict(s, ts=s["ts"] + 1.5) for s in solve(10, 2.0)], dropped=3)
    r = types.SimpleNamespace(window=(T0 + 1, T0 + 10), notes={})
    assert manifest.reader("solve_host_ms.dist")(r) is None
