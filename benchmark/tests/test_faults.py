"""A run with its timed path broken underneath comes out not correct.

Each test drives the whole harness (`benchmark/run.py --rehearse`: the
cell's loop, its window and its comparison with the oracle, at the
configuration's rehearsal sizes on the CPU) with one fault planted in
the program, and reads `correct` from the result line. The faults are
those a cell on one chip can have: a step that returns its state
unchanged, half of the popped parents left out, and an answer altered
where it is produced. (No cell spans chips, so none leaves out an
exchange between them.)
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import run

CLOSED = "table20-lb2"
SERVED = "serve-steady"


def result(capsys, workload, seconds=3, seed=2**31 + 11):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def fresh_programs():
    """A fault changes what a program traces to: start and end with no
    compiled program in the process."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def state_unchanged(monkeypatch):
    from tpu_tree_search.engine import device
    real = device.step

    def step(*args, state, **kw):
        # count the iteration, change nothing else
        return state._replace(iters=state.iters + 1)

    monkeypatch.setattr(device, "step", step)
    monkeypatch.setattr(device, "run",
                        lambda tables, state, *a, **k: state)
    return real


def half_batch(monkeypatch):
    from tpu_tree_search.engine import device
    real = device.pop_chunk

    def pop_chunk(state, B, M):
        p_prmu, p_depth, p_aux, n, start, valid = real(state, B, M)
        keep = jnp.arange(B) < (n + 1) // 2
        return (p_prmu, jnp.where(keep[None, :], p_depth, 0), p_aux, n,
                start, valid & keep)

    monkeypatch.setattr(device, "pop_chunk", pop_chunk)


def answer_altered(monkeypatch):
    from tpu_tree_search.engine import device, distributed
    search, dsearch = device.search, distributed.search

    def one_more(*a, **k):
        res = search(*a, **k)
        return res._replace(explored_tree=res.explored_tree + 1)

    def one_more_dist(*a, **k):
        res = dsearch(*a, **k)
        res.explored_tree += 1
        return res

    monkeypatch.setattr(device, "search", one_more)
    monkeypatch.setattr(distributed, "search", one_more_dist)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}


@pytest.mark.parametrize("workload", [CLOSED, SERVED])
def test_sound_run_is_correct(capsys, workload):
    out = result(capsys, workload)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", [CLOSED, SERVED])
def test_fault_is_not_correct(capsys, monkeypatch, workload, fault):
    from tpu_tree_search.service.executors import ExecutorCache
    setup_done = run.Run.setup_done
    caches = []
    init = ExecutorCache.__init__

    def track(self, *a, **k):
        init(self, *a, **k)
        caches.append(self)

    def plant(self):
        # set-up runs sound; the window runs the fault, compiled anew
        # (the server keeps the loops it compiled in set-up)
        FAULTS[fault](monkeypatch)
        jax.clear_caches()
        for cache in caches:
            with cache._lock:
                cache._fns.clear()
        setup_done(self)

    monkeypatch.setattr(ExecutorCache, "__init__", track)

    monkeypatch.setattr(run.Run, "setup_done", plant)
    out = result(capsys, workload)
    assert out["correct"] is False
    assert out["failed"] > 0
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
