"""Host time per whole solve of the window, in milliseconds: the mean,
over the window's `search` spans of the program's flight recorder
(`engine/device.search`), of the span's duration less its
`search.wait` child, the one wait on the chip. The split by child span
goes to the notes as `solve_host_split_ms`: prepare, dispatch, fetch,
grow, and what no child covers (unspanned). None when the program
records no such span, or when the recorder's ring dropped records of
the window."""

PARTS = ("prepare", "dispatch", "fetch", "grow")


def window_spans(lo: float, hi: float):
    """The recorder's spans that start inside [lo, hi] (host monotonic
    seconds), each with its start `t` on that clock; None when records
    from the stretch were evicted."""
    from tpu_tree_search.obs import tracelog
    log = tracelog.get()
    recs = log.records()
    if not recs or (log.dropped and log.t0 + recs[0]["ts"] > lo):
        return None
    return [{**r, "t": log.t0 + r["ts"]} for r in recs
            if r.get("kind") == "span"
            and lo - 2e-6 <= log.t0 + r["ts"] <= hi]


def read(run):
    window = getattr(run, "window", None)
    spans = (window_spans(*window) if window else None) or []
    solves = [s for s in spans if s["name"] == "search"]
    if not solves:
        return None
    children: dict = {}
    for s in spans:
        children.setdefault(s.get("parent_id"), []).append(s)
    split = dict.fromkeys(PARTS + ("unspanned",), 0.0)
    host = 0.0
    for s in solves:
        took = {}
        for c in children.get(s.get("span_id"), ()):
            part = c["name"].removeprefix("search.")
            took[part] = took.get(part, 0.0) + c["dur"]
        mine = s["dur"] - took.get("wait", 0.0)
        host += mine
        for part in PARTS:
            split[part] += took.get(part, 0.0)
        split["unspanned"] += mine - sum(took.get(p, 0.0) for p in PARTS)
    n = len(solves)
    run.notes["solve_host_split_ms"] = {k: 1e3 * v / n
                                        for k, v in split.items()}
    return 1e3 * host / n
