"""Host time per whole distributed solve of the window, in
milliseconds, from the program's flight recorder
(`engine/distributed.search`): the mean over the window's solves of
`request.prepare` + `engine.run` less its `segment.wait` children (the
host's waits on the chips) + `engine.fetch`. The split goes to the
notes as `solve_host_split_ms`: bfs_warmup, tables (`dist.tables`),
seed (`dist.seed`), the rest of prepare, dispatch (`engine.run` less
its waits) and fetch. None when the program records no `engine.fetch`
span, or when the recorder's ring dropped records of the window."""

PREPARE_PARTS = {"bfs_warmup": "bfs_warmup", "dist.tables": "tables",
                 "dist.seed": "seed"}


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
    by_name: dict = {}
    children: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
        children.setdefault(s.get("parent_id"), []).append(s)
    prepares = by_name.get("request.prepare", [])
    fetches = by_name.get("engine.fetch", [])
    if not prepares or not fetches:
        return None
    split = dict.fromkeys([*PREPARE_PARTS.values(), "prepare_rest",
                           "dispatch", "fetch"], 0.0)
    for p in prepares:
        rest = p["dur"]
        for c in children.get(p.get("span_id"), ()):
            part = PREPARE_PARTS.get(c["name"])
            if part is not None:
                split[part] += c["dur"]
                rest -= c["dur"]
        split["prepare_rest"] += rest
    for r in by_name.get("engine.run", []):
        split["dispatch"] += r["dur"] - sum(
            c["dur"] for c in children.get(r.get("span_id"), ())
            if c["name"] == "segment.wait")
    split["fetch"] = sum(f["dur"] for f in fetches)
    n = len(prepares)
    run.notes["solve_host_split_ms"] = {k: 1e3 * v / n
                                        for k, v in split.items()}
    return 1e3 * sum(split.values()) / n
