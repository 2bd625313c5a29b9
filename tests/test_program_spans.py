"""The flight recorder's spans on the profiler's clock, and the spans
and named step phases that say where a solve's or a request's time
goes.

- inside an `obs.profiler` capture, a `device.search` leaves its
  `search.*` spans in the xplane's host plane (the device ops' clock);
- every span record carries its own id and its parent's, and the
  ambient request id;
- `search.prepare` holds `search.tables` (with a `tables.calibrate`
  only where the tables compute the strong-pair order) and
  `search.init_state`;
- the compile listener attributes a first call's trace, lowering and
  compile to the innermost open span;
- the compiled search loop names its phases (`pop`, `bound`, `prune`,
  `compact`, `push`) in its op metadata on every route;
- a served request leaves queued / prepare / dispatch / wait /
  heartbeat / finish spans under one request id, with either segment
  driver, and every wait on the chip is a `segment.wait`.
"""

import glob
import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tree_search.engine import device
from tpu_tree_search.obs import profiler, tracelog
from tpu_tree_search.ops import batched
from tpu_tree_search.problems import taillard
from tpu_tree_search.problems.pfsp import PFSPInstance
from tpu_tree_search.service import SearchRequest, SearchServer

PHASES = ("pop", "bound", "prune", "compact", "push")
ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def log():
    fresh = tracelog.TraceLog(capacity=1 << 16)
    prev = tracelog.install(fresh)
    try:
        yield fresh
    finally:
        tracelog.install(prev)


def _spans(log, name=None):
    return [r for r in log.records()
            if r["kind"] == "span" and name in (None, r["name"])]


def test_capture_holds_search_spans_on_the_host_plane(log, tmp_path):
    inst = PFSPInstance.synthetic(jobs=7, machines=3, seed=6)
    with profiler.trace(tmp_path / "capture"):
        res = device.search(inst.p_times, lb_kind=1, chunk=8,
                            capacity=1 << 12)
    assert res.complete
    paths = glob.glob(str(tmp_path / "capture" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert paths
    data = jax.profiler.ProfileData.from_file(paths[0])
    host = {e.name for plane in data.planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events}
    assert {"search", "search.prepare", "search.dispatch", "search.wait",
            "search.fetch"} <= host
    # the same spans, in the ring, nest under the one `search`
    top, = _spans(log, "search")
    kids = {r["name"] for r in _spans(log)
            if r["parent_id"] == top["span_id"]}
    assert kids == {"search.prepare", "search.dispatch", "search.wait",
                    "search.fetch"}
    assert top["jobs"] == 7 and top["lb_kind"] == 1


@pytest.mark.parametrize("inst,calibrates", [(21, True), (11, False)])
def test_prepare_spans_its_tables_and_state(log, inst, calibrates):
    # 20x20: P = 190 pairs, so the tables compute the strong-pair
    # order; 20x10: P = 45 <= 2 * PAIR_PREFILTER, so they never do
    res = device.search(taillard.processing_times(inst), lb_kind=1,
                        chunk=8, capacity=1 << 12, max_iters=2)
    assert res.iters == 2
    by_id = {r["span_id"]: r for r in _spans(log)}
    prep, = _spans(log, "search.prepare")
    kids = sorted(r["name"] for r in by_id.values()
                  if r["parent_id"] == prep["span_id"])
    assert kids == ["search.init_state", "search.tables"]
    cal = _spans(log, "tables.calibrate")
    if calibrates:
        only, = cal
        assert by_id[only["parent_id"]]["name"] == "search.tables"
        assert only["pairs"] == 190 and only["samples"] == 2048
    else:
        assert cal == []
    # solve_host_ms.table reads direct children of `search`: the new
    # spans are grandchildren and leave its split as it was
    top, = _spans(log, "search")
    assert {r["name"] for r in by_id.values()
            if r["parent_id"] == top["span_id"]} == {
        "search.prepare", "search.dispatch", "search.wait", "search.fetch"}
    spec = importlib.util.spec_from_file_location(
        "solve_host_ms", ROOT / "benchmark" / "metrics"
        / "solve_host_ms.table.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    start = log.t0 + top["ts"]
    run = types.SimpleNamespace(window=(start, start + top["dur"]),
                                notes={})
    assert reader.read(run) > 0
    split = run.notes["solve_host_split_ms"]
    assert set(split) == {"prepare", "dispatch", "fetch", "grow",
                          "unspanned"}
    assert split["prepare"] == pytest.approx(1e3 * prep["dur"])


def test_records_carry_span_parent_and_request_ids(log):
    with log.context(request_id="req-7"):
        with log.span("outer") as outer:
            with log.span("inner"):
                log.event("inside")
            log.span_at("after", 0.0, 0.0)
        log.event("outside")
    recs = {r["name"]: r for r in log.records()}
    assert recs["outer"]["parent_id"] is None
    assert recs["inner"]["parent_id"] == outer.span_id \
        == recs["outer"]["span_id"]
    assert recs["inside"]["parent_id"] == recs["inner"]["span_id"]
    assert recs["after"]["parent_id"] == outer.span_id
    assert recs["after"]["span_id"] not in (
        recs["outer"]["span_id"], recs["inner"]["span_id"])
    assert recs["outside"]["parent_id"] is None
    assert all(r["request_id"] == "req-7" for r in recs.values())


def test_compile_listener_fills_the_innermost_span(log):
    x = jnp.arange(11)
    with log.span("outer"):
        with log.span("first_call"):
            # a function this process has never compiled
            jax.jit(lambda v: v * 7 + 3)(x).block_until_ready()
    first, = _spans(log, "first_call")
    outer, = _spans(log, "outer")
    assert first["compile_s"] > 0 and first["lower_s"] > 0
    assert first["trace_s"] > 0
    # nested traces count once: the phases fit inside the span
    assert first["trace_s"] + first["lower_s"] + first["compile_s"] \
        <= first["dur"] + 1e-3
    assert not any(k in outer for k in tracelog.COMPILE_EVENTS.values())


@pytest.mark.parametrize("inst,lb_kind", [
    (21, 1),         # LB1: bound, prune, compact, push
    (21, 2),         # LB2 two-phase: prefilter and pair sweeps
    (1, 2),          # LB2 dense route (few pairs)
])
def test_lowered_loop_names_the_step_phases(inst, lb_kind):
    p = taillard.processing_times(inst)
    tables = batched.make_tables(p)
    state = device.init_state(p.shape[1], 1 << 12, None, p_times=p)
    text = device._run.lower(
        tables, state, lb_kind, 8, np.int32(4),
        np.int32(1)).as_text(debug_info=True)
    missing = [ph for ph in PHASES if f"/{ph}/" not in text]
    assert not missing, missing


@pytest.mark.parametrize("overlap", [False, True])
def test_served_request_leaves_its_spans_under_one_id(log, tmp_path,
                                                      overlap):
    inst = PFSPInstance.synthetic(jobs=8, machines=3, seed=5)
    with SearchServer(n_submeshes=1, devices=jax.devices()[:1],
                      workdir=tmp_path / "wd", overlap=overlap) as srv:
        rid = srv.submit(SearchRequest(
            p_times=inst.p_times, lb_kind=1, chunk=8, capacity=1 << 12,
            min_seed=4, segment_iters=8, checkpoint_every=2))
        rec = srv.result(rid, timeout=300)
    assert rec.state == "DONE", rec.error
    mine = [r for r in _spans(log) if r.get("request_id") == rid]
    names = {r["name"] for r in mine}
    assert {"request.queued", "request.execute", "request.prepare",
            "segment.dispatch", "segment.wait", "segment.heartbeat",
            "request.finish"} <= names
    by_id = {r["span_id"]: r for r in mine}

    def under_execute(r):
        while r["parent_id"] is not None:
            r = by_id[r["parent_id"]]
        return r["name"] == "request.execute"

    for r in mine:
        if r["name"] in ("request.prepare", "segment.dispatch",
                         "segment.wait", "segment.heartbeat"):
            assert under_execute(r), r
    waits = [r for r in mine if r["name"] == "segment.wait"]
    assert len(waits) >= 2
    if overlap:
        # the checkpoint segment's state fetch is a wait too
        assert any(r.get("checkpoint") for r in waits)
    else:
        # the synchronous driver blocks on the loop inside its dispatch
        assert any(by_id[r["parent_id"]]["name"] == "segment.dispatch"
                   for r in waits)
