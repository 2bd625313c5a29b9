"""The readers of the per-layer metrics that read the program's flight
recorder: their value from known spans, and None when the ring dropped
records of the stretch they read or holds no such span (as in a
program that records none)."""

import types

import pytest

from benchmark import manifest

T0 = 100.0      # the recorder's clock origin on the host's monotonic clock


class Ring:
    """What the readers use of the recorder: its records, clock origin
    and count of evicted records."""

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


def run(window=(T0 + 1, T0 + 10), setup_end=T0 + 50):
    return types.SimpleNamespace(window=window, setup_end=setup_end,
                                 notes={})


def solve(sid, ts, parts):
    """A `search` span at `ts` and its children, [(name, dur)] in
    order."""
    out, t = [], ts
    for name, dur in parts:
        out.append({"name": f"search.{name}", "ts": t, "dur": dur,
                    "span_id": sid + len(out) + 1, "parent_id": sid})
        t += dur
    return [{"name": "search", "ts": ts, "dur": t - ts + 0.02,
             "span_id": sid, "parent_id": None}] + out


SOLVES = (solve(10, 2.0, [("prepare", 0.05), ("dispatch", 0.02),
                          ("wait", 0.9), ("fetch", 0.01)])
          + solve(20, 4.0, [("prepare", 0.07), ("dispatch", 0.04),
                            ("wait", 1.5), ("fetch", 0.03)]))


def request(rid, thread, ts):
    parts = [("request.prepare", 0.1), ("segment.dispatch", 0.05),
             ("segment.wait", 0.3), ("segment.heartbeat", 0.01),
             ("checkpoint.save", 0.04), ("gap", 0.01),
             ("request.finish", 0.02)]
    out, t = [], ts
    for k, (name, dur) in enumerate(parts):
        if name != "gap":
            out.append({"name": name, "ts": t, "dur": dur, "span_id": k,
                        "thread": thread, "request_id": rid})
        t += dur
    # the synchronous driver's wait on the loop, inside its dispatch
    out.append({"name": "segment.wait", "ts": ts + 0.11, "dur": 0.03,
                "span_id": 99, "parent_id": 1, "thread": thread,
                "request_id": rid})
    # request.execute closes before request.finish opens
    return [{"name": "request.execute", "ts": ts, "dur": t - ts - 0.03,
             "thread": thread, "request_id": rid}] + out


REQUESTS = (request("req-1", "exec-0", 2.0) + request("req-2", "exec-0", 3.0)
            # the async checkpoint writer's save runs beside the
            # request: not the executor thread's time
            + [{"name": "checkpoint.save", "ts": 3.1, "dur": 0.2,
                "thread": "writer", "request_id": "req-2"}])

DISPATCHES = [
    {"name": "search.dispatch", "ts": 5.0, "dur": 30.0, "trace_s": 10.0,
     "lower_s": 5.0, "compile_s": 12.0, "cache_read_s": 2.0},
    {"name": "search.dispatch", "ts": 40.0, "dur": 1.0},
    # in the window, after set-up: not the first call
    {"name": "search.dispatch", "ts": 60.0, "dur": 90.0},
]


def test_solve_host_ms_is_each_solve_less_its_wait(ring):
    ring(SOLVES)
    r = run()
    got = manifest.reader("solve_host_ms.table")(r)
    # per solve, the span less its wait: 1.00 - 0.90 and 1.66 - 1.50
    assert got == pytest.approx(1e3 * (0.10 + 0.16) / 2)
    split = r.notes["solve_host_split_ms"]
    assert split["prepare"] == pytest.approx(60.0)
    assert split["dispatch"] == pytest.approx(30.0)
    assert split["fetch"] == pytest.approx(20.0)
    assert split["grow"] == 0.0
    assert split["unspanned"] == pytest.approx(20.0)
    assert sum(split.values()) == pytest.approx(got)


def test_request_host_ms_is_each_dispatch_less_its_waits(ring):
    ring(REQUESTS)
    r = run()
    got = manifest.reader("request_host_ms.serve")(r)
    # 0.53 s from execute's start to finish's end, less 0.33 s of waits
    assert got == pytest.approx(1e3 * (0.53 - 0.33))
    split = r.notes["request_host_split_ms"]
    assert split == pytest.approx({
        "prepare": 100.0, "segment.dispatch": 20.0, "heartbeat": 10.0,
        "checkpoint.save": 40.0, "finish": 20.0, "unspanned": 10.0})


def test_first_call_s_is_the_longest_dispatch_of_setup(ring):
    ring(DISPATCHES)
    r = run()
    assert manifest.reader("first_call_s.table")(r) == 30.0
    assert r.notes["first_call_split_s"] == pytest.approx({
        "trace_s": 10.0, "lower_s": 5.0, "cache_read_s": 2.0,
        "compile_s": 10.0, "rest": 3.0})


@pytest.mark.parametrize("name,recs", [
    ("solve_host_ms.table", SOLVES),
    ("request_host_ms.serve", REQUESTS),
    ("first_call_s.table", DISPATCHES),
])
def test_none_when_the_ring_dropped_records_of_the_stretch(ring, name,
                                                           recs):
    # the oldest record left is later than the stretch's start
    ring([dict(r, ts=r["ts"] + 1.5) for r in recs], dropped=3)
    assert manifest.reader(name)(run()) is None


@pytest.mark.parametrize("name", ["solve_host_ms.table",
                                  "request_host_ms.serve",
                                  "first_call_s.table"])
def test_none_when_the_program_records_no_such_span(ring, name):
    # what a program without these spans leaves: other spans, events
    ring([{"name": "segment", "ts": 2.0, "dur": 0.5},
          {"name": "request.dispatch", "ts": 2.5, "kind": "event"}])
    assert manifest.reader(name)(run()) is None
