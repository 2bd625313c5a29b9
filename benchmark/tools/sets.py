#!/usr/bin/env python3
"""Run sets of runs of one cell and read their spreads.

    python benchmark/tools/sets.py run --workload <cell> --seconds 51 \
        --out bench_out/<dir> --tag A --seeds s1 s2 .. [--trace 1] \
        [--script benchmark/control.py]
    python benchmark/tools/sets.py spreads bench_out/<dir>/runs.jsonl

`run` starts each run as a process of its own, one after the other,
keeps its whole output in `<out>/<cell>_<seed>_<trace>_<tag>.log` and
appends one summary line per run to `<out>/runs.jsonl`. `spreads`
prints, for each metric of the two sets tagged A and B (the same seeds
in both), each set's median and spread: the inter-quartile distance
over the median, Python's `statistics.quantiles(n=4)`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import stats  # noqa: E402


def summary(log: str, **tags) -> dict:
    try:
        with open(log) as f:
            d = json.loads(f.read().strip().splitlines()[-1])
        return {**tags, "correct": d["correct"], "attempted": d["attempted"],
                "failed": d["failed"],
                "m": {k: v["value"] for k, v in d["metrics"].items()},
                "device": d["device"],
                "checks": {k: v["value"] for k, v in d["checks"].items()}}
    except (OSError, ValueError, KeyError, IndexError) as e:
        return {**tags, "error": repr(e)[:300]}


def run_set(a) -> int:
    os.makedirs(a.out, exist_ok=True)
    for seed in a.seeds:
        log = os.path.join(a.out, f"{a.workload}_{seed}_{a.trace}_{a.tag}.log")
        t0 = time.monotonic()
        with open(log, "w") as f:
            rc = subprocess.call(
                [sys.executable, a.script, "--workload", a.workload,
                 "--seed", str(seed), "--seconds", str(a.seconds),
                 "--trace", str(a.trace)], stdout=f, stderr=subprocess.STDOUT)
        line = summary(log, tag=a.tag, seed=seed, trace=a.trace, rc=rc,
                       wall=round(time.monotonic() - t0, 1))
        with open(os.path.join(a.out, "runs.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


def spreads(paths) -> int:
    runs = []
    for p in paths:
        with open(p) as f:
            runs += [json.loads(x) for x in f if x.startswith("{")]
    sets = {t: [r for r in runs if r.get("tag") == t and r.get("m")]
            for t in ("A", "B")}
    for name in sorted({k for r in sets["A"] for k in r["m"]}):
        row = {"metric": name}
        for t, rs in sets.items():
            vals = [r["m"][name] for r in rs if name in r["m"]]
            if len(vals) >= 2:
                row[t] = {"n": len(vals), "median": statistics.median(vals),
                          "spread": stats.spread(vals), "values": vals}
        print(json.dumps(row))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--tag", required=True)
    r.add_argument("--seeds", type=int, nargs="+", required=True)
    r.add_argument("--trace", type=int, default=0)
    r.add_argument("--script", default="benchmark/run.py")
    s = sub.add_parser("spreads")
    s.add_argument("paths", nargs="+")
    a = ap.parse_args(argv)
    return run_set(a) if a.cmd == "run" else spreads(a.paths)


if __name__ == "__main__":
    sys.exit(main())
