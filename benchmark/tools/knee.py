#!/usr/bin/env python3
"""Find the highest rate a served cell sustains: its knee.

    python benchmark/tools/knee.py --workload serve-steady \
        --seconds 40 --seed 1 1.5 2.0 2.5 3.0

One process, one server, set up as the cell's run sets it up; then one
open-loop window per rate, in the order given, each with the cell's
own schedule at that rate. Prints one JSON line per rate: requests
offered and answered, latency p50/p90, and the growth of the backlog
(requests outstanding at each arrival, least-squares slope in
requests/s and its mean over the window's last quarter against its
first). A rate is sustained when the backlog does not grow through the
window. The knee is measured once, on the chip, and written into the
mix's `rate` as a number; runs of the cell never search for it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest, stats  # noqa: E402
from benchmark.run import Run  # noqa: E402


def backlog(items) -> tuple[float, float, float]:
    """Requests outstanding at each arrival: slope (per s), mean over
    the first and over the last quarter of the arrivals."""
    t = np.array([it["sched"] for it in items])
    ends = np.array([it["done"] if it["done"] is not None else np.inf
                     for it in items])
    out = np.array([int(((t[:k + 1] <= t[k]) & (ends[:k + 1] > t[k])).sum())
                    for k in range(len(items))])
    slope = float(np.polyfit(t - t[0], out, 1)[0]) if len(t) > 2 else 0.0
    q = max(1, len(out) // 4)
    return slope, float(out[:q].mean()), float(out[-q:].mean())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args(argv)

    import jax

    from tpu_tree_search.service.server import SearchServer
    from tpu_tree_search.utils import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    config = manifest.config(man, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    oracle = manifest.oracle(cell["config"])
    drv = manifest.loop(traffic["kind"])
    devices = jax.devices()[:cell["chips"]]
    with tempfile.TemporaryDirectory(prefix="bench_knee_") as workdir:
        srv = SearchServer(n_submeshes=int(config["submeshes"]),
                           devices=devices, workdir=workdir)
        try:
            for i in traffic["rows"]:
                drv.serve_once(srv, drv.payload(i, int(traffic["lb"])))
            for rate in args.rates:
                r = Run(cell, config, traffic, oracle, args.seed,
                        args.seconds, False, devices)
                drv.window(r, srv, rate)
                drv.judge(r)
                slope, first, last = backlog(r.items)
                print(json.dumps({
                    "rate": rate, "offered": len(r.items),
                    "failed": r.failed, **r.end_to_end,
                    "backlog_slope_per_s": slope,
                    "backlog_first_quarter": first,
                    "backlog_last_quarter": last,
                    "generator_lag_max_s": r.counters[
                        "generator_lag_max_s"],
                    "queue_wait_p90_s": stats.percentile(
                        r.counters["queue_waits"], 90)}), flush=True)
        finally:
            srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
