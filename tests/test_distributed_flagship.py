"""The distributed search as the reference's multi-GPU flagship runs it,
on a 4-device mesh of the virtual CPU devices:

- exact trees and optima against the plain reference
  (`benchmark/reference.py`), on seeded random instances and Taillard
  rows, at LB1 and LB2;
- the pools are seeded on the mesh, leaf for leaf the state the host
  used to build at full capacity, with no host array of pool size;
- same-shape searches in one process lower their loop once;
- the search's spans: `dist.tables` and `dist.seed` inside
  `request.prepare`, `engine.fetch` after the run, and the
  `engine.complete` event's balance counters, `recv_per_steal` (rows per
  receiving round) beside `transfer_cap` among them.
"""

import functools
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from tpu_tree_search.engine import distributed
from tpu_tree_search.obs import tracelog
from tpu_tree_search.problems import get as get_problem
from tpu_tree_search.problems import taillard
from tpu_tree_search.problems.pfsp import PFSPInstance

ROOT = pathlib.Path(__file__).resolve().parents[1]
N_DEV = 4


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference", ROOT / "benchmark" / "reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REFERENCE = _reference()


@functools.lru_cache(maxsize=None)
def _instance(name):
    if name.startswith("ta"):
        i = int(name[2:])
        return taillard.processing_times(i), taillard.optimal_makespan(i)
    jobs, machines, seed = (int(x) for x in name.split("-")[1:])
    p = PFSPInstance.synthetic(jobs=jobs, machines=machines,
                               seed=seed).p_times
    # the optimum from a search with no incumbent, then the exact tree
    # under it (the tree at UB=opt does not depend on the search order)
    return p, REFERENCE.search(np.asarray(p), 1, 2**31 - 1)[2]


@functools.lru_cache(maxsize=None)
def _want(name, lb_kind):
    p, opt = _instance(name)
    return REFERENCE.search(np.asarray(p), lb_kind, opt)


# ta004 at LB2 only: its LB1 tree is 1,163,892 nodes, 84 s in the
# reference alone
CASES = [(name, lb) for name in ("rand-8-4-11", "rand-10-5-12", "ta002",
                                 "ta019") for lb in (1, 2)] + [("ta004", 2)]


@pytest.mark.parametrize("n_devices", [N_DEV])
@pytest.mark.parametrize("name,lb_kind", CASES)
def test_four_devices_match_the_reference(name, lb_kind, n_devices):
    p, opt = _instance(name)
    want = _want(name, lb_kind)
    got = distributed.search(p, lb_kind=lb_kind, init_ub=opt,
                             n_devices=n_devices, chunk=64,
                             capacity=1 << 14, min_seed=4)
    assert got.complete
    assert (got.explored_tree, got.explored_sol, got.best) == want


@pytest.mark.parametrize("n_devices", [N_DEV])
def test_cli_four_devices_solve_the_row_exactly(n_devices, capsys):
    """`pfsp -D 4` with the CLI's other defaults, as the launcher runs
    each row: the explored tree and optimum are the reference's."""
    from tpu_tree_search import cli
    rc = cli.main(["pfsp", "-i", "4", "-l", "2", "-u", "1",
                   "-D", str(n_devices), "--chunk", "64",
                   "--capacity", str(1 << 14)])
    out = capsys.readouterr().out
    assert rc == 0
    tree, sol, best = _want("ta004", 2)
    assert f"Size of the explored tree: {tree}" in out
    assert f"Optimal makespan: {best}" in out
    assert f"TPU B&B ({n_devices} device(s)" in out


def _driver(n_dev, capacity_chunk=8):
    p = taillard.processing_times(19)
    prob = get_problem("pfsp")
    table = np.asarray(p)
    adt = prob.aux_dtype(table)
    driver = distributed._problem_driver(
        prob, distributed.worker_mesh(n_dev), prob.make_tables(table),
        table, 2, capacity_chunk, 4, 64, 2 * capacity_chunk, adt, None)
    return driver, table, adt


def _frontier(n, jobs, machines, adt, seed=0):
    rng = np.random.default_rng(seed)
    prmu = np.stack([rng.permutation(jobs) for _ in range(n)]).astype(
        np.int16) if n else np.zeros((0, jobs), np.int16)
    return distributed.Frontier(
        prmu=prmu, depth=rng.integers(0, jobs, n).astype(np.int16),
        tree=0, sol=0, best=1600,
        aux=rng.integers(0, 3000, (n, machines)).astype(adt))


@pytest.mark.parametrize("n_devices", [N_DEV])
@pytest.mark.parametrize("case", ["chips_left_empty", "one_node",
                                  "stripe_at_the_limit", "uneven"])
def test_seed_on_the_mesh_equals_the_host_built_state(case, n_devices,
                                                      monkeypatch):
    driver, table, adt = _driver(n_devices)
    jobs, machines = table.shape[1], table.shape[0]
    capacity = 1 << 12
    limit = driver.limit(capacity)
    n = {"chips_left_empty": 2, "one_node": 1,
         "stripe_at_the_limit": limit * n_devices,
         "uneven": 4 * n_devices + 3}[case]
    fr = _frontier(n, jobs, machines, adt)
    widths = []
    stripes = distributed._stripes

    def spy(fr, n_dev, width, limit):
        widths.append(width)
        return stripes(fr, n_dev, width, limit)

    monkeypatch.setattr(distributed, "_stripes", spy)
    got = driver.seed(fr, capacity, jobs, 1600)
    monkeypatch.undo()
    want = distributed._shard_frontier(fr, n_devices, capacity, jobs, 1600,
                                       limit=limit)
    assert got.prmu.shape[-1] == capacity
    # what crossed from the host was the stripes, not the pools
    if case == "stripe_at_the_limit":
        assert widths == [min(distributed._seed_rows(limit), capacity)]
    else:
        assert widths and max(widths) <= 64 < capacity
    for field, g, w in zip(distributed.SearchState._fields, got, want):
        g_host, w_host = np.asarray(g), np.asarray(w)
        assert g_host.dtype == w_host.dtype, field
        assert np.array_equal(g_host, w_host), field
        assert g.sharding.spec == distributed.P(distributed.AX), field
        assert len(g.sharding.device_set) == n_devices, field


def test_same_shape_searches_lower_their_loop_once(monkeypatch):
    lowered = []

    def seen(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(duration)

    built = []
    build = distributed.build_dist_loop

    def counted_build(*a, **k):
        built.append(1)
        return build(*a, **k)

    monkeypatch.setattr(distributed, "build_dist_loop", counted_build)
    jax.monitoring.register_event_duration_secs_listener(seen)
    per_call = []
    try:
        # a capacity no other test uses, so the first call builds afresh
        for inst in (2, 4, 7):
            before = len(lowered)
            res = distributed.search(
                taillard.processing_times(inst), lb_kind=2,
                init_ub=taillard.optimal_makespan(inst),
                n_devices=N_DEV, chunk=32, capacity=3 << 12, min_seed=4,
                max_rounds=2)
            per_call.append(len(lowered) - before)
            assert res.best > 0
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    assert len(built) == 1
    assert per_call[0] >= 1
    assert per_call[1:] == [0, 0]


@pytest.fixture
def log():
    fresh = tracelog.TraceLog(capacity=1 << 16)
    prev = tracelog.install(fresh)
    try:
        yield fresh
    finally:
        tracelog.install(prev)


def _by_name(log):
    out = {}
    for r in log.records():
        out.setdefault(r["name"], []).append(r)
    return out


def test_one_shot_search_spans_its_seed_and_fetch(log):
    p = taillard.processing_times(4)
    res = distributed.search(p, lb_kind=2,
                             init_ub=taillard.optimal_makespan(4),
                             n_devices=N_DEV, chunk=64, capacity=1 << 14,
                             min_seed=4)
    recs = _by_name(log)
    (prep,) = recs["request.prepare"]
    (run,) = recs["engine.run"]
    (fetch,) = recs["engine.fetch"]
    for child in ("bfs_warmup", "dist.tables", "dist.seed"):
        (rec,) = recs[child]
        assert rec["parent_id"] == prep["span_id"], child
    assert recs["dist.seed"][0]["frontier"] >= 4 * N_DEV
    waits = recs["segment.wait"]
    assert waits and all(w["parent_id"] == run["span_id"] for w in waits)
    # prepare, the run and the fetch follow one another at the top
    assert prep["parent_id"] is None and run["parent_id"] is None
    assert fetch["parent_id"] is None
    assert prep["ts"] + prep["dur"] <= run["ts"]
    assert run["ts"] + run["dur"] <= fetch["ts"]
    (done,) = recs["engine.complete"]
    assert done["kind"] == "event" and done["parent_id"] is None
    trees = res.per_device["tree"]
    assert done["moved"] == int(res.per_device["sent"].sum())
    assert done["tree_max_over_mean"] == pytest.approx(
        trees.max() / trees.mean())
    assert done["tree"] == res.explored_tree
    recv, steals = res.per_device["recv"], res.per_device["steals"]
    assert steals.sum() > 0
    assert done["recv_per_steal"] == pytest.approx(recv.sum() / steals.sum())
    assert done["transfer_cap"] == 64      # one chunk per pair


def test_segmented_search_keeps_its_segment_spans(log):
    p = taillard.processing_times(4)
    res = distributed.search(p, lb_kind=2,
                             init_ub=taillard.optimal_makespan(4),
                             n_devices=N_DEV, chunk=64, capacity=1 << 14,
                             min_seed=4, segment_iters=8, overlap=False)
    assert res.explored_tree == 33283
    recs = _by_name(log)
    by_id = {r["span_id"]: r for r in log.records() if r["kind"] == "span"}
    (prep,) = recs["request.prepare"]
    assert recs["dist.seed"][0]["parent_id"] == prep["span_id"]
    assert "engine.run" not in recs
    assert len(recs["segment.dispatch"]) >= 2
    # the synchronous driver's overflow read sits in its dispatch
    assert any(by_id.get(w["parent_id"], {}).get("name")
               == "segment.dispatch" for w in recs["segment.wait"])
    (fetch,) = recs["engine.fetch"]
    last = max(r["ts"] + r["dur"] for r in recs["segment.dispatch"])
    assert fetch["ts"] >= last
    (done,) = recs["engine.complete"]
    assert done["moved"] == int(res.per_device["sent"].sum())
