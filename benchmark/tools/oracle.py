#!/usr/bin/env python3
"""Count oracle rows with the plain reference in C, on a CPU.

    python benchmark/tools/oracle.py --build-dir <dir> [--jobs 4] \
        <inst>:<lb> ...

Builds `benchmark/reference.c` into `<dir>` with the system's C
compiler, runs one process per row (at most `--jobs` at a time) at
Taillard's optimum as the incumbent, and prints one line per row in
the form of `benchmark/oracle/<config>.jsonl`. The 20x20 LB2 rows take
some minutes each.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BY = "benchmark/reference.c (cc -O2), `reference_c {inst} {lb}`, on a CPU"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--build-dir", required=True)
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("rows", nargs="+")
    a = ap.parse_args(argv)
    os.makedirs(a.build_dir, exist_ok=True)
    exe = os.path.join(a.build_dir, "reference_c")
    subprocess.check_call(["cc", "-O2", "-o", exe,
                           os.path.join(HERE, "reference.c")])

    def count(row):
        inst, lb = (int(x) for x in row.split(":"))
        out = json.loads(subprocess.check_output([exe, str(inst), str(lb)]))
        machines = 5 if inst <= 10 else 10 if inst <= 20 else 20
        return {"inst": inst, "jobs": 20, "machines": machines, "lb": lb,
                "ub": "opt", "tree": out["tree"], "best": out["best"],
                "by": BY.format(inst=inst, lb=lb)}

    with ThreadPoolExecutor(a.jobs) as ex:
        for line in ex.map(count, a.rows):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
