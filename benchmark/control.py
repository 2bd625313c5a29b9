#!/usr/bin/env python3
"""The control: a run of a cell whose timed path breaks one guarantee
the configuration states, and which the comparison has to find.

    python benchmark/control.py --workload <cell> --seed <n> \
        --seconds <s> [--rehearse]

The configurations state no numeric precision; they state exactness:
every node whose bound is below the incumbent is branched, and every
request ends DONE with the whole tree. Each control switches on a path
the program has of its own that gives up part of that work, the step a
later change would be tempted to take for speed:

- closed solves: the device loop's `drain_min` exit (the hybrid
  search's hand-off point, `engine/device.run(drain_min=...)`) at one
  chunk, with the residue left unexplored: the drain phase's
  underfilled steps are the slow tail of every solve;
- the served mix: a per-request `deadline_s` budget of a tenth of a
  second, under which long requests stop at a segment boundary in
  DEADLINE with partial counters.

Prints the run's result line, as `benchmark/run.py` does. The
benchmark's own runs never run this; `benchmark/tests/test_control.py`
keeps it as a test at rehearsal sizes.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402

DEADLINE_S = 0.1


def drain_early():
    """Replace `device.search` with a search whose loop stops once the
    pool holds less than one chunk, leaving the rest unexplored."""
    from tpu_tree_search.engine import device
    from tpu_tree_search.ops import batched

    def search(p_times, lb_kind=1, init_ub=None, chunk=64,
               capacity=1 << 18, max_iters=None, tables=None, tile=1024):
        tables = batched.make_tables(p_times) if tables is None else tables
        jobs = p_times.shape[1]
        state = device.init_state(jobs, capacity, init_ub, p_times=p_times)
        # ramp up as the search does (one level per step), then stop
        # at the first step that cannot fill a chunk
        out = device.run(tables, state, lb_kind, chunk, jobs, tile=tile)
        out = device.run(tables, out, lb_kind, chunk, max_iters,
                         tile=tile, drain_min=chunk)
        return device.SearchResult(
            explored_tree=int(out.tree), explored_sol=int(out.sol),
            best=int(out.best), iters=int(out.iters),
            evals=int(out.evals), overflow=bool(out.overflow),
            complete=int(out.size) == 0)

    device.search = search


def with_deadline():
    """Give every served request a `deadline_s` budget."""
    from tpu_tree_search.service import spool
    plain = spool.request_from_payload

    def request_from_payload(payload):
        return plain({**payload, "deadline_s": DEADLINE_S})

    spool.request_from_payload = request_from_payload


CONTROLS = {"closed_solves": drain_early, "open_loop_service": with_deadline}


def main(argv=None) -> int:
    args = bench_run.parse(argv)
    from benchmark import manifest
    man = manifest.manifest()
    kind = manifest.traffic(manifest.workload(man, args.workload)
                            ["traffic"])["kind"]
    setup_done = bench_run.Run.setup_done

    def switch_at_window(self):
        # set-up runs as in a sound run; the window runs the control
        CONTROLS[kind]()
        setup_done(self)

    bench_run.Run.setup_done = switch_at_window
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
