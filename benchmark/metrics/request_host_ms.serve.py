"""Host time per served request of the window, in milliseconds: the
mean, over the window's dispatches, of the stretch from the start of
the program's `request.execute` span to the end of the
`request.finish` span that follows it on the same executor thread and
request, less the `segment.wait` spans inside it (the host blocked on
the chip; the synchronous segment driver's wait sits inside its
`segment.dispatch`, and comes off that part). The split goes to the
notes as `request_host_split_ms`:
prepare (`request.prepare`), segment.dispatch, heartbeat
(`segment.heartbeat`), checkpoint.save, finish (`request.finish`), and
what none of them covers (unspanned). None when the program records no
such spans, or when the recorder's ring dropped records of the
window."""

PARTS = {"request.prepare": "prepare",
         "segment.dispatch": "segment.dispatch",
         "segment.heartbeat": "heartbeat",
         "checkpoint.save": "checkpoint.save",
         "request.finish": "finish"}


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
    lanes: dict = {}       # (thread, request) -> spans, in start order
    for s in sorted(spans, key=lambda s: s["t"]):
        lanes.setdefault((s.get("thread"), s.get("request_id")),
                         []).append(s)
    split = dict.fromkeys([*PARTS.values(), "unspanned"], 0.0)
    hosts = []
    for lane in lanes.values():
        for ex in lane:
            if ex["name"] != "request.execute":
                continue
            after = [s for s in lane if s["t"] >= ex["t"] and s is not ex]
            fin = next((s for s in after
                        if s["name"] == "request.finish"), None)
            if fin is None:
                continue
            end = fin["t"] + fin["dur"]
            inside = [s for s in after if s["t"] + s["dur"] <= end + 2e-6]
            took = dict.fromkeys(split, 0.0)
            wait = 0.0
            waited: dict = {}      # span id -> its segment.wait children
            for s in inside:
                if s["name"] == "segment.wait":
                    wait += s["dur"]
                    waited[s.get("parent_id")] = (
                        waited.get(s.get("parent_id"), 0.0) + s["dur"])
            for s in inside:
                if s["name"] in PARTS:
                    took[PARTS[s["name"]]] += (
                        s["dur"] - waited.get(s.get("span_id"), 0.0))
            host = end - ex["t"] - wait
            took["unspanned"] = host - sum(took.values())
            for k, v in took.items():
                split[k] += v
            hosts.append(host)
    if not hosts:
        return None
    run.notes["request_host_split_ms"] = {k: 1e3 * v / len(hosts)
                                          for k, v in split.items()}
    return 1e3 * sum(hosts) / len(hosts)
