"""Warmed probe runner: one harness for every dispatch-knob sweep.

The methodology is the one tools/bench_balance_period.py validated
on-chip (and the two earlier methodologies it documents as garbage):
warm a REAL pool past the ramp once, then time the full SPMD program
(engine/distributed.build_dist_loop) for each candidate configuration
on IDENTICAL warmed state and identical iteration windows — same
state, same window, best-of-N wall time. The chunk sweep and the
balance-period sweep (previously two bespoke tools) are both thin
loops over :meth:`ProbeHarness.measure`; the Autotuner drives the same
entry points, so the offline tuner and the hand-run sweep tools can
never measure different things.

The score is node-evals/s (bound evaluations per wall second): the
north-star unit, and the one that stays comparable across chunk
candidates — different chunks do different amounts of work per
iteration, so ms/iter only ranks candidates at a FIXED chunk
(balance-period sweeps report it too, for continuity with the old
tool's output).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

__all__ = ["ProbeHarness", "ProbeResult", "ProbeError",
           "measure_balance_periods"]


class ProbeError(RuntimeError):
    """The harness could not produce a steady measurement state (the
    instance exhausted or overflowed inside the warm-up). Callers fall
    back to the defaults tier — a failed probe must never fail a boot."""


@dataclasses.dataclass
class ProbeResult:
    """One candidate's measurement."""

    chunk: int
    balance_period: int
    transfer_cap: int
    evals_per_s: float
    ms_per_iter: float
    window_iters: int
    evals: int
    seconds: float          # best-of-repeats wall time of the window
    pool_start: int         # live rows when the window began
    underfilled: bool       # pool < chunk at window start: the rate is
    #                         a ramp rate, not a steady-state one —
    #                         the tuner deprioritizes these

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class ProbeHarness:
    """Warm ONCE per (instance, bound), measure MANY candidates on the
    identical state. Single-device mesh by construction (the same-state
    method needs one canonical pool; the per-worker program cost is
    what the knobs move — spread effects are not measured here).

    `problem` (registry name or plugin object, default "pfsp")
    generalizes the harness to every registered workload: the pool is
    seeded from the plugin's root/seed_aux, the warm-up and every
    measured candidate run the plugin's own step pipeline
    (Problem.make_step — the fast-path hook for PFSP, generic_step for
    the rest), so TSP/knapsack shapes get MEASURED chunk optima
    instead of the serving fallback row (ROADMAP item 2c). `table` is
    the problem's 2-D instance table; the historical ``p_times`` name
    is kept for the PFSP callers."""

    def __init__(self, p_times: np.ndarray, lb_kind: int = 1,
                 init_ub: int | None = None, capacity: int = 1 << 18,
                 warm_chunk: int | None = None, warm_iters: int = 200,
                 window_iters: int = 24, repeats: int = 2,
                 problem="pfsp"):
        from ..engine import device

        if isinstance(problem, str):
            from .. import problems as problems_pkg
            problem = problems_pkg.get(problem)
        self.problem = problem
        self.p_times = np.asarray(p_times)
        self.jobs = int(problem.slots(self.p_times))
        self.machines = int(problem.aux_rows(self.p_times))
        self.lb_kind = int(lb_kind)
        self.capacity = int(capacity)
        self.window_iters = int(window_iters)
        self.repeats = max(1, int(repeats))
        self.tables = problem.make_tables(self.p_times)
        self._adt = np.dtype(problem.aux_dtype(self.p_times))

        warm_chunk = int(warm_chunk or 64)
        prmu0, depth0 = problem.root(self.p_times)
        state = device.init_state(
            self.jobs, self.capacity, init_ub, prmu0=prmu0,
            depth0=depth0,
            aux0=problem.seed_aux(self.p_times, prmu0, depth0))
        state = device.run_problem(problem, self.tables, state,
                                   self.lb_kind, warm_chunk,
                                   max_iters=warm_iters)
        state.size.block_until_ready()
        if bool(state.overflow) or int(state.size) == 0:
            raise ProbeError(
                f"warm-up left no steady state to measure "
                f"(pool={int(state.size)}, "
                f"overflow={bool(state.overflow)}) — instance "
                "exhausts or overflows within the warm-up window")
        self.pool = int(state.size)
        self.iters0 = int(state.iters)
        self._evals0 = int(state.evals)
        # DEVICE-resident, exactly like the validated tool this
        # harness replaces: a host-numpy pool would re-upload tens of
        # MB inside every timed window and rank candidates by
        # transfer noise instead of program cost
        self._stacked = tuple(x[None] for x in state)

    def measure(self, chunk: int, balance_period: int,
                transfer_cap: int | None = None,
                min_transfer: int | None = None) -> ProbeResult:
        """Time one candidate configuration on the warmed state."""
        import jax
        import jax.numpy as jnp

        from ..engine import distributed
        from ..parallel.mesh import worker_mesh

        chunk = int(chunk)
        balance_period = int(balance_period)
        tc, mt = distributed.balance_defaults(
            chunk, self.jobs, self.machines, 1, distributed.MIN_SEED,
            aux_itemsize=self._adt.itemsize)
        transfer_cap = tc if transfer_cap is None else int(transfer_cap)
        min_transfer = mt if min_transfer is None else int(min_transfer)
        limit = min(self.problem.usable_rows(self.capacity, chunk,
                                             self.jobs),
                    self.capacity - transfer_cap)
        if limit < 1:
            raise ProbeError(
                f"chunk {chunk} leaves no usable rows at capacity "
                f"{self.capacity} (limit={limit}); raise the harness "
                "capacity or drop the candidate")

        def mls(t, lim):
            return self.problem.make_step(t, self.lb_kind, chunk, 1024,
                                          lim)

        loop = distributed.build_dist_loop(
            worker_mesh(1), self.tables, mls, balance_period,
            transfer_cap, min_transfer, limit)
        target = jnp.asarray(self.iters0 + self.window_iters, jnp.int64)
        cap = jnp.asarray(distributed.I32_MAX, jnp.int32)

        def call():
            out = loop(self.tables, target, cap, *self._stacked)
            jax.block_until_ready(out)
            return out

        out = call()                 # compile + warm at the final sig
        best = float("inf")
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            out = call()
            best = min(best, time.perf_counter() - t0)
        from ..engine.device import SearchState
        res = SearchState(*out)
        evals = int(np.asarray(res.evals).sum()) - self._evals0
        iters = int(np.asarray(res.iters).max()) - self.iters0
        return ProbeResult(
            chunk=chunk, balance_period=balance_period,
            transfer_cap=int(transfer_cap),
            evals_per_s=round(evals / best, 1) if best > 0 else 0.0,
            ms_per_iter=round(best / max(iters, 1) * 1e3, 4),
            window_iters=iters, evals=evals, seconds=round(best, 6),
            pool_start=self.pool,
            underfilled=self.pool < chunk)


def measure_balance_periods(p_times: np.ndarray, lb_kind: int,
                            chunk: int, periods, capacity: int = 1 << 22,
                            warm_iters: int = 500,
                            window_iters: int = 256,
                            repeats: int = 3,
                            init_ub: int | None = None) -> list[dict]:
    """The balance-period sweep (the old tools/bench_balance_period.py
    body, now a loop over the shared harness — its CLI is a thin
    wrapper around this). Returns one dict per period with the legacy
    ``ms_per_iter`` field plus the harness's evals/s."""
    h = ProbeHarness(p_times, lb_kind=lb_kind, init_ub=init_ub,
                     capacity=capacity, warm_chunk=chunk,
                     warm_iters=warm_iters, window_iters=window_iters,
                     repeats=repeats)
    rows = []
    for period in periods:
        r = h.measure(chunk, period)
        rows.append({"balance_period": int(period),
                     "ms_per_iter": r.ms_per_iter,
                     "evals_per_s": r.evals_per_s})
    return rows
