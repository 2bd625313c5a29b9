"""The first call of the cell's search loop, in seconds: the longest
`search.dispatch` span of set-up in the program's flight recorder
(`engine/device.search`), which holds the loop's trace, lowering and
compile or persistent-cache read. The split goes to the notes as
`first_call_split_s`, from the span's attributes that the recorder's
compile listener fills: trace_s, lower_s, cache_read_s, compile_s (the
backend compile less the cache read inside it), and the rest. None
when the program records no such span, or when the recorder's ring
dropped records of set-up."""


def read(run):
    from tpu_tree_search.obs import tracelog
    log = tracelog.get()
    recs = log.records()
    setup_end = getattr(run, "setup_end", None)
    # set-up runs from the recorder's creation to the window
    if not recs or setup_end is None or (
            log.dropped and recs[0]["ts"] > 0):
        return None
    calls = [r for r in recs
             if r.get("kind") == "span" and r["name"] == "search.dispatch"
             and log.t0 + r["ts"] + r["dur"] <= setup_end + 2e-6]
    if not calls:
        return None
    first = max(calls, key=lambda r: r["dur"])
    trace, lower, compiled, cache = (
        float(first.get(k, 0.0))
        for k in ("trace_s", "lower_s", "compile_s", "cache_read_s"))
    run.notes["first_call_split_s"] = {
        "trace_s": trace, "lower_s": lower, "cache_read_s": cache,
        "compile_s": compiled - cache,
        "rest": first["dur"] - trace - lower - compiled}
    return first["dur"]
