"""Open loop of requests into an in-process `SearchServer`.

Mix keys: `lb`, `rows` (Taillard ids), `rate` (requests/s offered),
`arrivals` (`regular` or `poisson`; `stats.open_loop_schedule` draws
the schedule from `--seed`: every seed offers each row equally often,
in its own order), `trace_seconds` (a traced run profiles the window's
last this-many seconds: the profiler's stop stalls the process, and at
the end it delays only the requests still outstanding),
`wait_after_s` (how long past the window's close the run waits for the
last answers). Configuration key: `submeshes`.

Requests are what a `serve` user writes, `{"p_times", "lb", "ub"}`,
turned into requests by `spool.request_from_payload` at submission, so
the server's defaults decide every knob. Set-up serves every row of
the mix once: that compiles the loop of each shape class and every
small program a row's solve touches (the checkpoint's live-row slices
take their shapes from the pool). A request's latency runs from its
scheduled arrival to its result reaching the client. Every request of
the window must end DONE with the oracle's explored tree and optimum.
"""

from __future__ import annotations

import queue
import tempfile
import threading
import time

from benchmark import stats

GAP_METRIC = "tts_segment_gap_seconds"


def _gap_snapshot():
    from tpu_tree_search.obs import metrics as obs_metrics
    snap = obs_metrics.default().histogram(GAP_METRIC).snapshot_matching()
    return int(snap["count"]), float(snap["sum"])


def payload(i: int, lb: int) -> dict:
    from tpu_tree_search.problems import taillard
    return {"p_times": taillard.processing_times(i).tolist(), "lb": lb,
            "ub": taillard.optimal_makespan(i)}


def serve_once(srv, pl: dict):
    from tpu_tree_search.service import spool
    return srv.result(srv.submit(spool.request_from_payload(pl)),
                      timeout=900)


def run(r) -> None:
    from tpu_tree_search.service.server import SearchServer

    cfg, tr = r.config, r.traffic
    lb = int(tr["lb"])
    rows = list(tr["rows"])
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as workdir:
        srv = SearchServer(n_submeshes=int(cfg["submeshes"]),
                           devices=r.devices, workdir=workdir)
        try:
            for i in rows:
                rec = serve_once(srv, payload(i, lb))
                if rec.state != "DONE":
                    raise RuntimeError(f"warm-up ta{i:03d} ended {rec.state}")
            r.setup_done()
            window(r, srv, float(tr["rate"]))
        finally:
            srv.close()
    judge(r)


def window(r, srv, rate: float) -> None:
    """Offer the mix's schedule at `rate` for `r.seconds`, wait for
    every answer, and record them in `r.items`."""
    from tpu_tree_search.service import spool

    tr = r.traffic
    lb = int(tr["lb"])
    offsets, rows = stats.open_loop_schedule(
        rate, r.seconds, list(tr["rows"]), r.seed,
        tr.get("arrivals", "regular"))
    payloads = [payload(i, lb) for i in rows]
    trace_from = r.seconds - float(tr.get("trace_seconds", r.seconds))
    wait_after = float(tr.get("wait_after_s", 60))
    pending: queue.Queue = queue.Queue()
    items: list[dict] = []

    gap0 = _gap_snapshot()
    start = time.monotonic()
    give_up = start + r.seconds + wait_after

    def collect():
        while True:
            got = pending.get()
            if got is None:
                return
            it, rid = got
            if rid is None:
                continue
            try:
                rec = srv.result(rid, timeout=max(0.0,
                                                  give_up - time.monotonic()))
            except TimeoutError:
                it["state"] = "TIMEOUT"
                continue
            it["done"] = time.monotonic()
            it["state"] = rec.state
            if rec.result is not None:
                it["tree"] = int(rec.result.explored_tree)
                it["best"] = int(rec.result.best)
            if rec.finished_t is not None:
                # time in the server less execution time
                it["queue_wait"] = (rec.finished_t - rec.submitted_t
                                    - rec.spent_prev_s)

    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    traced = False

    def sleep_until(t):
        delay = t - time.monotonic()
        if delay > 0:
            time.sleep(delay)

    def trace_from_here(upto):
        nonlocal traced
        if r.tracing and not traced and upto >= trace_from:
            sleep_until(start + trace_from)
            r.start_trace()
            traced = True

    try:
        for off, i, pl in zip(offsets, rows, payloads):
            due = start + off
            trace_from_here(off)
            sleep_until(due)
            now = time.monotonic()
            it = {"inst": i, "sched": due, "submit": now, "lag": now - due,
                  "done": None, "state": "REFUSED", "tree": None,
                  "best": None, "queue_wait": None}
            items.append(it)
            try:
                with r.span("submit"):
                    rid = srv.submit(spool.request_from_payload(pl))
            except (ValueError, RuntimeError) as e:
                it["error"] = repr(e)
                rid = None
            pending.put((it, rid))
        # the traced stretch is the window's last `trace_seconds`; the
        # profiler's stop then delays only the requests still out
        trace_from_here(r.seconds)
        sleep_until(start + r.seconds)
        r.end_trace()
    finally:
        pending.put(None)
        collector.join(timeout=max(1.0, give_up - time.monotonic() + 5))
    gap1 = _gap_snapshot()
    r.items = items
    last = max((it["done"] for it in items if it["done"] is not None),
               default=start)
    r.window = (start, max(start + r.seconds, last))
    # a miss counts with the latency it had when the run stopped waiting
    r.counters["give_up"] = time.monotonic()
    r.counters["segment_gaps"] = gap1[0] - gap0[0]
    r.counters["segment_gap_s"] = gap1[1] - gap0[1]
    r.counters["queue_waits"] = [it["queue_wait"] for it in items
                                 if it["queue_wait"] is not None]
    r.counters["generator_lag_max_s"] = max(it["lag"] for it in items)
    r.counters["offered"] = len(items)


def judge(r) -> None:
    """Compare every request of the window with the oracle, and derive
    the end-to-end metrics."""
    lb = int(r.traffic["lb"])
    want = {i: r.oracle[(i, lb)] for i in r.traffic["rows"]}
    items = r.items
    ok = [it["state"] == "DONE"
          and it["tree"] == want[it["inst"]]["tree"]
          and it["best"] == want[it["inst"]]["best"] for it in items]
    done = [it["done"] if good else None for it, good in zip(items, ok)]
    lat = stats.open_loop_latencies([it["sched"] for it in items], done,
                                    r.counters["give_up"])
    r.end_to_end["latency_p50_s"] = stats.percentile(lat, 50)
    r.end_to_end["latency_p90_s"] = stats.percentile(lat, 90)
    r.end_to_end["served_per_s"] = stats.served_per_s(sum(ok), *r.window)
    r.attempted, r.failed = len(items), len(items) - sum(ok)
    tree_gap = max((abs(it["tree"] - want[it["inst"]]["tree"])
                    / max(want[it["inst"]]["tree"], 1)
                    for it in items if it["tree"] is not None), default=0.0)
    best_gap = max((abs(it["best"] - want[it["inst"]]["best"])
                    for it in items if it["best"] is not None), default=0)
    r.checks = {
        "tree_gap_max": (tree_gap, 0.0),
        "makespan_gap_max": (best_gap, 0),
        "requests_not_done": (sum(it["state"] != "DONE" for it in items), 0),
    }

    def label_at(t):
        if any(it["submit"] <= t <= (it["done"] or t) for it in items):
            return "request outstanding"
        return "no request outstanding"
    r.label_at = label_at
