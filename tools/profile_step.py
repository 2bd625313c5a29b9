"""Attribute steady-state step time to ops from a jax.profiler trace.

Usage:
    python tools/profile_step.py [--lb 2] [--inst 21] [--chunk 32768]
        [--warm 400] [--iters 30]

Warms the single-device engine past its ramp (underfilled chunks), traces
a short window of the compiled loop through the shared profiler session
(tpu_tree_search/obs/profiler.py — the SAME one-at-a-time session behind
``POST /profile`` and the `profile` CLI subcommand; no direct
``jax.profiler`` calls live in the tools any more), then aggregates
per-op SELF times (exclusive of nested control-flow spans —
tpu_tree_search/obs/chrome_trace.py owns the trace parsing AND the phase
buckets, shared with tools/trace_selftime.py, tools/search_report.py and
tools/validate_attribution.py). The tool's own wall-clock phases
(warm-up, traced window) are flight-recorded as obs/tracelog spans, so a
`TTS_TRACE_FILE=...` run leaves a timeline of the measurement itself.
It measures what the two-phase
LB2 step (resp. the LB1 step) actually spends its time on.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpu_tree_search.engine import device  # noqa: E402
from tpu_tree_search.obs import profiler, tracelog  # noqa: E402
from tpu_tree_search.obs.chrome_trace import (bucket_of,  # noqa: E402
                                              bucketed_self_times,
                                              load_xla_trace, self_times)
from tpu_tree_search.ops import batched  # noqa: E402
from tpu_tree_search.problems import taillard  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lb", type=int, default=2)
    ap.add_argument("--inst", type=int, default=21)
    ap.add_argument("--chunk", type=int, default=32768)
    ap.add_argument("--warm", type=int, default=400)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--logdir", default=None,
                    help="keep the trace here instead of a tempdir")
    args = ap.parse_args()

    p = taillard.processing_times(args.inst)
    ub = taillard.optimal_makespan(args.inst)
    tables = batched.make_tables(p)
    jobs = p.shape[1]
    state = device.init_state(jobs, 1 << 22, ub, p_times=p)
    with tracelog.span("profile_step.warmup", inst=args.inst, lb=args.lb,
                       chunk=args.chunk) as warm_sp:
        state = device.run(tables, state, args.lb, args.chunk,
                           max_iters=args.warm)
        state.size.block_until_ready()
        warm_sp.set(iters=int(state.iters), pool=int(state.size))
    print(f"# warmed: iters={int(state.iters)} pool={int(state.size)} "
          f"evals={int(state.evals)} ({warm_sp.dur:.2f}s)",
          file=sys.stderr)

    log_dir = args.logdir or tempfile.mkdtemp(prefix="tts_trace_")
    with tracelog.span("profile_step.traced_window", logdir=log_dir):
        with profiler.trace(log_dir):
            out = device.run(tables, state, args.lb, args.chunk,
                             max_iters=args.warm + args.iters)
            out.size.block_until_ready()
    n_iters = int(out.iters) - int(state.iters)
    evals = int(out.evals) - int(state.evals)
    print(f"# traced {n_iters} iters, {evals} evals; trace in {log_dir}",
          file=sys.stderr)

    self_us, counts = self_times(load_xla_trace(log_dir))
    total = sum(self_us.values())
    if total == 0:
        raise SystemExit("no device op self-times found in trace "
                         "(thread-name heuristic missed; inspect "
                         f"{log_dir} manually)")

    by_bucket = bucketed_self_times(self_us)

    print(json.dumps({
        "lb": args.lb, "inst": args.inst, "chunk": args.chunk,
        "iters": n_iters, "evals": evals,
        "device_self_ms": round(total / 1e3, 2),
        "per_iter_ms": round(total / 1e3 / max(n_iters, 1), 3),
        "evals_per_sec": round(evals / (total / 1e6), 1) if total else 0,
        "buckets_ms": {k: round(v / 1e3, 2)
                       for k, v in by_bucket.most_common()},
    }))
    print("\n# top ops by device self-time:")
    for name, d in self_us.most_common(args.top):
        print(f"{d/1e3:10.2f} ms  x{counts[name]:<6} "
              f"[{bucket_of(name):>15}]  {name[:100]}")


if __name__ == "__main__":
    main()
