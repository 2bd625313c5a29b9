"""Device busy time per search-loop iteration of the traced solves, in
microseconds: the trace's busy time over the `iters` counters that
`SearchResult` returns for those solves."""


def read(run):
    solves = run.counters.get("traced_solves")
    if run.trace is None or not solves:
        return None
    iters = sum(s["iters"] for s in solves)
    return 1e6 * run.trace.busy_s / iters if iters else None
