"""Closed loop of whole solves through `engine/distributed.search` over
the cell's chips, as `python -m tpu_tree_search pfsp -D <chips>` runs
each one: a host warm-up to `min_seed` nodes per chip, round-robin
stripes, one SPMD loop with a balance round every `balance_period`
steps, until every pool is empty.

Mix keys: `lb` (bound), `rows` (Taillard ids), `warmup_iters` (loop
iterations of the bounded run that warms each row, rounded up to whole
balance rounds), `trace_seconds` (a traced run profiles the whole
cycles that start before this much of the window has passed).
Configuration keys: `chips`, `chunk` and `capacity` (per chip),
`balance_period`, `min_seed` (warm-up nodes per chip).

Each cycle solves every row once, in the order the seed gives it; the
window runs whole cycles until it has lasted `--seconds`. Every solve
of the window is compared with the oracle's explored tree and optimum.
"""

from __future__ import annotations

import time

from benchmark import stats


def run(r) -> None:
    from tpu_tree_search.engine import distributed
    from tpu_tree_search.problems import taillard

    cfg, tr = r.config, r.traffic
    lb, rows = int(tr["lb"]), list(tr["rows"])
    chips, chunk = int(cfg["chips"]), int(cfg["chunk"])
    period = int(cfg["balance_period"])
    want = {i: r.oracle[(i, lb)] for i in rows}
    inst = {i: (taillard.processing_times(i), taillard.optimal_makespan(i))
            for i in rows}

    def solve(i, max_rounds=None):
        p, ub = inst[i]
        return distributed.search(
            p, lb_kind=lb, init_ub=ub, n_devices=chips, chunk=chunk,
            capacity=int(cfg["capacity"]), balance_period=period,
            min_seed=int(cfg["min_seed"]), max_rounds=max_rounds)

    # every row: one loop program serves them all, and each row's
    # warm-up frontier sets the shape of its seed program
    rounds = max(1, -(-int(tr["warmup_iters"]) // period))
    for i in rows:
        solve(i, max_rounds=rounds)
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
            dev = res.per_device
            r.items.append({
                "inst": i, "start": t0, "end": t1, "cpu": c1 - c0,
                "tree": int(res.explored_tree), "best": int(res.best),
                "complete": bool(res.complete),
                "chip_tree": [int(x) for x in dev["tree"]],
                "chip_iters": [int(x) for x in dev["iters"]],
                "chip_sent": [int(x) for x in dev["sent"]],
                "chip_steals": [int(x) for x in dev["steals"]]})
        cycle += 1
        if traced is None and r.tracing and (
                time.monotonic() - start >= trace_s):
            traced = len(r.items)
            r.end_trace()
    r.end_trace()
    items = r.items
    r.window = (items[0]["start"], items[-1]["end"])
    r.counters["traced_solves"] = items[:traced or len(items)]
    r.counters["chunk"] = chunk
    # each solve's row, start in the window, seconds, the process's CPU
    # seconds, and per chip: explored tree, loop iterations, nodes sent
    # and balance rounds that received nodes
    r.notes["solves"] = [
        [it["inst"], it["start"] - items[0]["start"],
         it["end"] - it["start"], it["cpu"], it["chip_tree"],
         it["chip_iters"], it["chip_sent"], it["chip_steals"]]
        for it in items]

    def label_at(t):
        for it in items:
            if it["start"] <= t <= it["end"]:
                return f"inside a solve (ta{it['inst']:03d})"
        return "between solves"
    r.label_at = label_at

    tree_gap = best_gap = 0.0
    bad = unfinished = 0
    for it in items:
        w = want[it["inst"]]
        gap = abs(it["tree"] - w["tree"]) / max(w["tree"], 1)
        bgap = abs(it["best"] - w["best"])
        tree_gap, best_gap = max(tree_gap, gap), max(best_gap, bgap)
        unfinished += int(not it["complete"])
        bad += int(gap > 0 or bgap > 0 or not it["complete"])
    r.attempted, r.failed = len(items), bad
    r.checks = {
        "tree_gap_max": (tree_gap, 0.0),
        "makespan_gap_max": (best_gap, 0.0),
        "solves_unfinished": (unfinished, 0),
    }
    r.end_to_end["tree_nodes_per_s"] = stats.tree_nodes_per_s(
        [want[it["inst"]]["tree"] for it in items], *r.window)
