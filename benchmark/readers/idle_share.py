"""Share of the traced window in which no operation ran on the chip,
in %: 1 minus the union of the device's op intervals over the window
(benchmark/trace_reduce.py). A metric file names it: `{"reader":
"idle_share"}`."""


def read(run, metric):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
