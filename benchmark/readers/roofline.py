"""Share of its roofline a Pallas kernel reaches in the traced window,
in %: operations and bytes of each launch from its recorded shapes
(benchmark/kernel_work.py), against the vector peak and the published
HBM bandwidth, over the launches' device time. Which bound binds goes
to the result line's notes as `<metric>_bound`.

A metric file names the kernel: `{"reader": "roofline", "kernel":
"lb2_bounds_tpu"}`."""

from benchmark import kernel_work


def read(run, metric, kernel):
    if run.trace is None or not run.peaks.get("vector_ops_per_s"):
        return None
    got = kernel_work.roofline_share(run.trace.launches, kernel, run.peaks)
    if got is None:
        return None
    run.notes[f"{metric}_bound"] = got[1]
    return got[0]
