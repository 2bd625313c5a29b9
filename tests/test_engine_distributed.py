"""Distributed engine on the virtual 8-device CPU mesh vs the oracle.

The collective logic (pmin incumbent, psum termination, all_to_all
steal-half balancing) runs on host-platform virtual devices — the
single-machine multi-node simulation facility the reference lacks
(SURVEY.md §4: "multi-node testing = real clusters").
"""

import numpy as np
import pytest

from tpu_tree_search.engine import distributed, sequential as seq
from tpu_tree_search.problems.pfsp import PFSPInstance


@pytest.mark.parametrize("lb_kind", [0, 1, 2])
def test_dist_matches_oracle_ub_opt(lb_kind):
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=0)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=lb_kind, init_ub=opt)
    got = distributed.search(inst.p_times, lb_kind=lb_kind, init_ub=opt,
                             chunk=8, capacity=1 << 12, min_seed=4)
    assert (got.explored_tree, got.explored_sol, got.best) == \
           (want.explored_tree, want.explored_sol, want.best)


def test_dist_finds_optimum_ub_inf():
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=1)
    opt = inst.brute_force_optimum()
    got = distributed.search(inst.p_times, lb_kind=1, init_ub=None,
                             chunk=8, capacity=1 << 12, min_seed=4)
    assert got.best == opt


@pytest.mark.parametrize("n_devices", [1, 2, 8])
def test_device_count_invariance(n_devices):
    """Counts with ub=opt must not depend on the mesh size."""
    inst = PFSPInstance.synthetic(jobs=8, machines=4, seed=2)
    opt = inst.brute_force_optimum()
    want = seq.pfsp_search(inst, lb=1, init_ub=opt)
    got = distributed.search(inst.p_times, lb_kind=1, init_ub=opt,
                             n_devices=n_devices, chunk=4,
                             capacity=1 << 12, min_seed=4)
    assert (got.explored_tree, got.explored_sol) == \
           (want.explored_tree, want.explored_sol)


def test_device_count_invariance_d32():
    """ub=opt count invariance at POD width: a 32-worker
    mesh — four times the suite's 8-device conftest split, so it runs in
    a subprocess with its own platform config — must reproduce ta003's
    exact reference tree, with the water-filling balance plan running
    real multi-receiver rounds (sent > 0 across 32 pools seeded from one
    root stripe). The donor threshold is set to 2 * chunk: the default,
    2 * min_seed = 512 nodes above the mean, is more than these small
    pools ever hold, so no round would move nodes."""
    import os
    import subprocess
    import sys

    code = (
        "import jax\n"
        "jax.config.update('jax_num_cpu_devices', 32)\n"
        "assert jax.device_count() == 32, jax.devices()\n"
        "from tpu_tree_search.engine import distributed\n"
        "from tpu_tree_search.problems import taillard\n"
        "out = distributed.search(taillard.processing_times(3),\n"
        "    lb_kind=2, init_ub=taillard.optimal_makespan(3),\n"
        "    n_devices=32, chunk=32, capacity=4096,\n"
        "    balance_period=2, min_seed=256, min_transfer=64)\n"
        "assert out.complete\n"
        "assert out.explored_tree == 80062, out.explored_tree\n"
        "assert out.best == 1081, out.best\n"
        "sent = int(out.per_device['sent'].sum())\n"
        "assert sent > 0, 'balance never moved nodes at D=32'\n"
        "print('D32-OK sent=', sent)\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=32"}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "D32-OK" in r.stdout


def test_balance_spreads_work():
    """With aggressive balancing most workers should explore something."""
    inst = PFSPInstance.synthetic(jobs=9, machines=4, seed=3)
    got = distributed.search(inst.p_times, lb_kind=1, init_ub=None,
                             chunk=4, capacity=1 << 12, min_seed=16,
                             balance_period=2, min_transfer=2)
    want = seq.pfsp_search(
        PFSPInstance.synthetic(jobs=9, machines=4, seed=3), lb=1,
        init_ub=got.best)
    # correctness anchor: optimum matches a fresh oracle run seeded with it
    assert got.best == want.best
    assert (got.per_device["tree"] > 0).sum() >= 4
