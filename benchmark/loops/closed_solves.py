"""Closed loop of whole solves through `engine/device.search`.

Mix keys: `lb` (bound), `rows` (Taillard ids), `warmup_iters` (the
bounded run that warms the loop), `trace_seconds` (how much of the
window a traced run profiles: the solves that start in it).
Configuration keys: `chunk`, `capacity`.

Each cycle solves every row once, in the order the seed gives it; the
window runs whole cycles until it has lasted `--seconds`. Every solve
of the window is compared with the oracle's explored tree and optimum.
"""

from __future__ import annotations

import time

from benchmark import stats


def run(r) -> None:
    from tpu_tree_search.engine import device
    from tpu_tree_search.problems import taillard

    cfg, tr = r.config, r.traffic
    lb, rows = int(tr["lb"]), list(tr["rows"])
    chunk, capacity = int(cfg["chunk"]), int(cfg["capacity"])
    want = {i: r.oracle[(i, lb)] for i in rows}
    inst = {i: (taillard.processing_times(i), taillard.optimal_makespan(i))
            for i in rows}

    def solve(i, max_iters=None):
        p, ub = inst[i]
        return device.search(p, lb_kind=lb, init_ub=ub, chunk=chunk,
                             capacity=capacity, max_iters=max_iters)

    # one loop program serves every row of a shape: warm one row each
    warmed = set()
    for i in rows:
        shape = inst[i][0].shape
        if shape not in warmed:
            solve(i, max_iters=int(tr["warmup_iters"]))
            warmed.add(shape)
    r.setup_done()

    trace_s = float(tr.get("trace_seconds", r.seconds))
    r.start_trace()
    start = time.monotonic()
    cycle = 0
    traced = None
    while time.monotonic() - start < r.seconds:
        for i in stats.closed_cycle(rows, r.seed, cycle):
            with r.span("solve"):
                t0, c0 = time.monotonic(), time.process_time()
                res = solve(i)
                t1, c1 = time.monotonic(), time.process_time()
            r.items.append({
                "inst": i, "start": t0, "end": t1, "cpu": c1 - c0,
                "tree": int(res.explored_tree), "best": int(res.best),
                "iters": int(res.iters), "evals": int(res.evals),
                "complete": bool(res.complete),
                "overflow": bool(res.overflow)})
            if traced is None and r.tracing and t1 - start >= trace_s:
                traced = len(r.items)
                r.end_trace()
        cycle += 1
    r.end_trace()
    items = r.items
    r.window = (items[0]["start"], items[-1]["end"])
    r.counters["traced_solves"] = items[:traced or len(items)]
    r.counters["chunk"] = chunk
    # each solve's row, start in the window, seconds, loop iterations
    # and the process's CPU seconds, in window order
    r.notes["solves"] = [[it["inst"], it["start"] - items[0]["start"],
                          it["end"] - it["start"], it["iters"], it["cpu"]]
                         for it in items]

    def label_at(t):
        for it in items:
            if it["start"] <= t <= it["end"]:
                return f"inside a solve (ta{it['inst']:03d})"
        return "between solves"
    r.label_at = label_at

    tree_gap = best_gap = 0.0
    bad = 0
    for it in items:
        w = want[it["inst"]]
        gap = abs(it["tree"] - w["tree"]) / max(w["tree"], 1)
        bgap = abs(it["best"] - w["best"])
        unfinished = (not it["complete"]) or it["overflow"]
        tree_gap, best_gap = max(tree_gap, gap), max(best_gap, bgap)
        bad += int(gap > 0 or bgap > 0 or unfinished)
    r.attempted, r.failed = len(items), bad
    r.checks = {
        "tree_gap_max": (tree_gap, 0.0),
        "makespan_gap_max": (best_gap, 0.0),
        "solves_unfinished": (sum((not it["complete"]) or it["overflow"]
                                  for it in items), 0),
    }
    r.end_to_end["tree_nodes_per_s"] = stats.tree_nodes_per_s(
        [want[it["inst"]]["tree"] for it in items], *r.window)
