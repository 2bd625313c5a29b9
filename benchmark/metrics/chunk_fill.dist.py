"""Share of the popped parent slots that held a node over all chips, in
%: explored tree nodes of the window's solves over (the chips' summed
loop iterations x chunk), from the `DistResult` counters the loop
records per solve. A chip whose pool ran dry while others worked
iterates with an empty chunk, so uneven balance shows here too."""


def read(run):
    solves = run.items
    slots = sum(sum(s.get("chip_iters", ())) for s in solves) * (
        run.counters.get("chunk", 0))
    if not slots:
        return None
    return 100.0 * sum(s["tree"] for s in solves) / slots
