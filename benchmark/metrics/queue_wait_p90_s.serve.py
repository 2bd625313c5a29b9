"""90th percentile of the time the window's requests waited in the
server before they ran, in seconds: from the request records' host
stamps, `finished_t - submitted_t - spent_prev_s` (time in the server
less execution time; one dispatch per request here)."""

from benchmark import stats


def read(run):
    waits = run.counters.get("queue_waits")
    return stats.percentile(waits, 90) if waits else None
