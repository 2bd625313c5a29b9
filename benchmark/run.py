#!/usr/bin/env python3
"""Benchmark of tpu-tree-search: one run of one cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`. Its configuration
file, traffic mix, oracle, traffic loop and per-layer readers are all
found by name under this directory. The run builds its inputs from
`--seed`, warms up the cell's own programs (set-up), measures for
`--seconds`, checks every answer of the window against the recorded
plain reference, and prints one JSON line last on stdout:
`correct`, `attempted`, `failed`, `metrics`, `device`, with `--trace 1`
also `breakdown`, and last `checks`, each compared number beside its
limit (also the last lines on stderr).

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window and from the program's counters.

The run needs a TPU with as many chips as the cell asks for, and
otherwise exits 2 with no result. `--rehearse`, on a host whose JAX
runs on the CPU (`JAX_PLATFORMS=cpu`), runs the cell at the small
sizes of the configuration's `rehearsal` block instead and prints a
line with no metrics of the device.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest  # noqa: E402


class Run:
    """One run of one cell: what the loop fills in, what the readers
    and the result line read."""

    def __init__(self, cell: dict, config: dict, traffic: dict,
                 oracle: dict, seed: int, seconds: float, trace: bool,
                 devices: list):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.oracle, self.seed, self.seconds = oracle, seed, seconds
        self.tracing = trace
        self.devices = devices
        self.items: list[dict] = []       # solves or requests of the window
        self.window = (0.0, 0.0)          # host monotonic start, end
        self.setup_end = None
        self.end_to_end: dict[str, float] = {}
        self.checks: dict[str, tuple[float, float]] = {}
        self.attempted = self.failed = 0
        self.counters: dict = {}          # program counters for readers
        self.notes: dict = {}             # readers' remarks for the line
        self.trace = None                 # trace_reduce.Summary
        self.peaks: dict = {}
        self.label_at = lambda t: "none"  # host activity at monotonic t
        self._trace_dir = None
        self._window_span = None
        self.trace_t0 = None              # host monotonic, traced start

    # -- set-up and tracing hooks the loops call -------------------
    def setup_done(self) -> None:
        self.setup_end = time.monotonic()

    def span(self, name: str):
        """A host span in the profiler's trace (nothing when untraced)."""
        if not self.tracing or self._trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_trace(self) -> None:
        """Start the profiler and open the traced window."""
        if not self.tracing:
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._window_span = jax.profiler.TraceAnnotation("window")
        self._window_span.__enter__()
        self.trace_t0 = time.monotonic()

    def end_trace(self) -> None:
        """Close the traced window, run the peak probe under the
        profiler, and stop it. Idempotent."""
        if self._window_span is None:
            return
        import jax

        from benchmark import probe
        self._window_span.__exit__(None, None, None)
        self._window_span = None
        with jax.profiler.TraceAnnotation("probe"):
            probe.run(self.devices[0])
        jax.profiler.stop_trace()

    # -- after the window ---------------------------------------------
    def reduce_trace(self) -> None:
        from benchmark import probe, trace_reduce
        if self._trace_dir is None:
            return
        try:
            paths = glob.glob(os.path.join(self._trace_dir, "**",
                                           "*.xplane.pb"), recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            events = trace_reduce.load_xplane(paths[0])
            self.trace = trace_reduce.summarize(
                events, label_at=self.label_at,
                host_t0=self.trace_t0)
            self.peaks = dict(self.peaks)
            peak, remark = probe.checked_peak(
                probe.peak_from_trace(self.trace),
                self.peaks["vector_ops_per_s_recorded"])
            self.peaks["vector_ops_per_s"] = peak
            if remark:
                self.notes["vector_ops_per_s_remark"] = remark
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


class CompileWatch:
    """What JAX lowered, compiled or read from its persistent cache, with
    the host time of each, so a run can report what happened inside its
    window: the window should hold none of it."""

    COUNTED = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
        "/jax/core/compile/backend_compile_duration": "compiled",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_reads",
    }

    def __init__(self):
        import jax
        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **_) -> None:
        name = self.COUNTED.get(event)
        if name is not None:
            self.events.append((time.monotonic(), name, float(duration)))

    def between(self, lo: float, hi: float) -> dict:
        out = {"seconds": 0.0}
        for t, name, dur in self.events:
            if lo <= t <= hi:
                out[name] = out.get(name, 0) + 1
                out["seconds"] += dur
        return out


def device_block(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def per_layer(run: Run, man: dict, cell_name: str) -> dict:
    out = {}
    for m in manifest.metrics_for(man, "per_layer", cell_name):
        value = manifest.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(run: Run, man: dict, cell_name: str) -> dict:
    out = {}
    for m in manifest.metrics_for(man, "end_to_end", cell_name):
        if m["name"] == "setup_s":
            value = run.setup_end - T0
        else:
            value = run.end_to_end.get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def checks_block(run: Run) -> dict:
    return {k: {"value": v, "limit": lim}
            for k, (v, lim) in run.checks.items()}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's small "
                         "sizes; prints no device metrics")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    man = manifest.manifest()
    cell = manifest.workload(man, args.workload)
    config = manifest.config(man, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    oracle = manifest.oracle(cell["config"])

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            print("run: --rehearse is for a CPU host", file=sys.stderr)
            return 2
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    elif platform != "tpu":
        print(f"run: no TPU (JAX platform {platform!r})", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run: the cell needs {cell['chips']} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from tpu_tree_search.utils import compile_cache
    compile_cache.enable()
    # every program of the cell, however quick to compile, comes from
    # the persistent cache after the first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    run = Run(cell, config, traffic, oracle, args.seed, args.seconds,
              bool(args.trace) and not args.rehearse,
              devices[:cell["chips"]])
    run.peaks = manifest.load_json(manifest.HERE / "peaks.json").get(
        devices[0].device_kind, {}) if not args.rehearse else {}
    if not args.rehearse and not run.peaks:
        print(f"run: no peaks for device kind {devices[0].device_kind!r}"
              " in benchmark/peaks.json", file=sys.stderr)
        return 2
    watch = CompileWatch()
    manifest.loop(traffic["kind"]).run(run)
    dev = device_block(devices, cell["chips"])
    run.notes["window_compiles"] = watch.between(run.setup_end,
                                                 run.window[1])

    result = {"correct": all(v <= lim for v, lim in run.checks.values())
              and bool(run.checks),
              "attempted": run.attempted, "failed": run.failed}
    if args.rehearse:
        result["metrics"] = {}
        result["rehearsal"] = {
            "end_to_end": run.end_to_end, "counters": {
                k: v for k, v in run.counters.items()
                if isinstance(v, (int, float))}}
        result["device"] = {"platform": dev["platform"],
                            "count": dev["count"]}
    elif args.trace:
        run.reduce_trace()
        result["metrics"] = per_layer(run, man, args.workload)
        tr = run.trace
        dev["busy_s"] = tr.busy_s
        dev["window_s"] = tr.window_s
        result["device"] = dev
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.top_gaps(10)}
        run.notes["vector_ops_per_s"] = run.peaks.get("vector_ops_per_s")
    else:
        result["metrics"] = end_to_end(run, man, args.workload)
        result["device"] = dev
    result["notes"] = run.notes
    result["checks"] = checks_block(run)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
