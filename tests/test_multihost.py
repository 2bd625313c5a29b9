"""Multihost (DCN) tier: 2-process jax.distributed run of the SAME SPMD
program, counters matching the single-controller run exactly.

This is the capability the reference needs a whole separate MPI
executable for (pfsp_dist_multigpu_cuda.c:910, launched one rank per
node, README.md:109-116). Round 1 shipped the --multihost code paths
(_fetch/_to_mesh) with zero coverage; this test executes them end to
end on two real processes.
"""

import json
import os
import pathlib
import socket
import subprocess
import sys

import pytest

from tpu_tree_search.engine import distributed, sequential as seq
from tpu_tree_search.problems.pfsp import PFSPInstance

WORKER = pathlib.Path(__file__).parent / "_multihost_worker.py"

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch_pair(*extra_args):
    """Run the 2-process worker pair; returns both RESULT dicts."""
    port = _free_port()
    repo_root = WORKER.parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(repo_root)] + env.get("PYTHONPATH", "").split(os.pathsep))
    procs = [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(port), str(i), "2",
             *map(str, extra_args)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(repo_root))
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    results = []
    for rc, out, err in outs:
        assert rc == 0, f"worker failed (rc={rc}):\n{err[-3000:]}"
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert lines, f"no RESULT line in worker output:\n{out[-2000:]}"
        results.append(json.loads(lines[-1][len("RESULT "):]))
    return results


def test_two_process_multihost_matches_single_controller():
    results = _launch_pair()

    # every process reports identical global totals
    assert results[0]["tree"] == results[1]["tree"]
    assert results[0]["sol"] == results[1]["sol"]
    assert results[0]["best"] == results[1]["best"]
    assert results[0]["complete"] and results[1]["complete"]

    # and they match the single-controller 8-worker run + the oracle
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=0)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    got = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                             chunk=8, capacity=1 << 12, min_seed=4)
    assert (got.explored_tree, got.explored_sol, got.best) == \
           (want.explored_tree, want.explored_sol, want.best)
    assert results[0]["tree"] == want.explored_tree
    assert results[0]["sol"] == want.explored_sol
    assert results[0]["best"] == want.best


def test_two_process_multihost_kill_resume(tmp_path):
    """Multihost DURABILITY (the tier the reference's MPI flagship has no
    answer to): a 2-process segmented run truncated mid-search writes a
    rank-0-gated checkpoint (checkpoint.save: every rank joins the
    collective fetch, only process 0 writes the shared file); a SECOND
    2-process launch resumes it and the final totals match the
    uninterrupted single-controller oracle exactly."""
    ck = str(tmp_path / "mh.npz")
    trunc = _launch_pair("trunc", ck, 1)
    assert not trunc[0]["complete"], \
        "truncated run drained the pool; lower MAX_ROUNDS"
    assert os.path.exists(ck), "rank 0 wrote no checkpoint"
    assert not os.path.exists(str(tmp_path / "mh.tmp.npz")), \
        "stray tmp file left"

    resumed = _launch_pair("resume", ck)
    for k in ("tree", "sol", "best", "complete"):
        assert resumed[0][k] == resumed[1][k]
    assert resumed[0]["complete"]

    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=0)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    assert resumed[0]["tree"] == want.explored_tree
    assert resumed[0]["sol"] == want.explored_sol
    assert resumed[0]["best"] == want.best
