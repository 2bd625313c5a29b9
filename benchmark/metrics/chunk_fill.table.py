"""Share of the popped parent slots that held a node, in %: explored
tree nodes of the window's solves over (loop iterations x chunk), from
the `tree` and `iters` counters `SearchResult` returns. Over whole
solves every pushed node is popped once."""


def read(run):
    solves = run.items
    slots = sum(s["iters"] for s in solves) * run.counters.get("chunk", 0)
    if not slots:
        return None
    return 100.0 * sum(s["tree"] for s in solves) / slots
