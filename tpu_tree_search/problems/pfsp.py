"""PFSP problem definition: node layout and branching scheme.

A B&B node for the Permutation Flowshop Scheduling Problem is a partial
permutation: jobs at positions `0..depth-1` of `prmu` are the fixed prefix
(already scheduled), the rest are unscheduled. The reference stores
`(int16 depth, int16 limit1, int16 prmu[MAX_JOBS])`
(reference: pfsp/lib/PFSP_node.h:15-20); with the forward-only branching
rule every engine uses (`child.limit1 = parent.limit1 + 1`,
PFSP_lib.c:13-16), `limit1 == depth - 1` is an invariant, so the TPU node
is just `(depth, prmu)` and `limit1` is derived.

Branching ("decompose", reference: PFSP_lib.c:7-42): the children of a node
at depth `d` are obtained by swapping `prmu[d] <-> prmu[i]` for each
`i in d..jobs-1`, fixing one more job at the front. A child with
`depth == jobs` is a complete schedule (leaf).

Device layout is struct-of-arrays: a pool of N nodes is
`prmu: int16[N, jobs]`, `depth: int16[N]` resident in HBM.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from . import base, taillard


@dataclasses.dataclass(frozen=True)
class PFSPInstance:
    """A PFSP instance plus the static shape info engines specialize on.

    The reference hardcodes MAX_JOBS/MAX_MACHINES at compile time
    (pfsp/lib/macro.h:9-11); here the concrete (jobs, machines) are static
    arguments baked into `jit`, chosen per instance.
    """

    inst_id: int            # Taillard instance id (1..120), 0 for synthetic
    jobs: int
    machines: int
    p_times: np.ndarray     # (machines, jobs) int32

    @staticmethod
    def from_taillard(inst: int) -> "PFSPInstance":
        p, n, m = taillard.instance(inst)
        return PFSPInstance(inst_id=inst, jobs=n, machines=m, p_times=p)

    @staticmethod
    def synthetic(jobs: int, machines: int, seed: int = 0,
                  low: int = 1, high: int = 99) -> "PFSPInstance":
        """Random instance for tests (brute-forceable at small `jobs`)."""
        rng = np.random.default_rng(seed)
        p = rng.integers(low, high + 1, size=(machines, jobs), dtype=np.int32)
        return PFSPInstance(inst_id=0, jobs=jobs, machines=machines, p_times=p)

    @property
    def optimum(self) -> int | None:
        return taillard.optimal_makespan(self.inst_id) if self.inst_id else None

    def makespan(self, permutation: np.ndarray) -> int:
        """Cmax of a complete permutation (reference: c_bound_simple.c:92-106)."""
        perm = np.asarray(permutation)
        completion = np.zeros(self.machines, dtype=np.int64)
        for job in perm:
            completion[0] += self.p_times[0, job]
            for mach in range(1, self.machines):
                completion[mach] = max(completion[mach - 1], completion[mach]) \
                    + self.p_times[mach, job]
        return int(completion[-1])

    def brute_force_optimum(self) -> int:
        """Exhaustive optimum for tiny instances (test oracle only)."""
        import itertools

        assert self.jobs <= 9, "brute force only for tiny instances"
        best = np.inf
        for perm in itertools.permutations(range(self.jobs)):
            best = min(best, self.makespan(np.array(perm)))
        return int(best)


def root_node(jobs: int) -> tuple[np.ndarray, int]:
    """Root = identity permutation at depth 0 (reference: PFSP_node.c:7-14)."""
    return np.arange(jobs, dtype=np.int16), 0


ROOT_DEPTH = 0


class PFSPProblem(base.Problem):
    """PFSP as a plugin of the generic engine.

    The flagship workload keeps its specialized pipeline: `make_step`
    is the Pallas fast-path hook onto engine/device.step (the two-phase
    LB2 prefilter, tiered compaction, feature-major kernels) — the
    protocol's `branch`/`bound` decomposition is deliberately NOT used
    on the hot path, which is exactly what the hook exists for. Host
    seeding (root/seed_aux/warmup) and the static spec route through
    the same single functions the engine always used, so a search
    driven through the plugin is op-identical to the pre-refactor one.
    """

    name = "pfsp"
    leaf_in_evals = True
    supports_host_tier = True
    lb_kinds = (0, 1, 2)
    default_lb = 1
    telemetry_labels = {"objective": "makespan"}

    def validate(self, table: np.ndarray) -> str | None:
        p = np.asarray(table)
        if p.ndim != 2 or p.shape[0] < 1 or p.shape[1] < 2:
            return (f"p_times must be a (machines, jobs>=2) table, "
                    f"got shape {p.shape}")
        return None

    def slots(self, table: np.ndarray) -> int:
        return int(np.asarray(table).shape[1])

    def aux_rows(self, table: np.ndarray) -> int:
        return int(np.asarray(table).shape[0])

    def aux_dtype(self, table: np.ndarray) -> np.dtype:
        from ..engine.device import aux_dtype
        return aux_dtype(np.asarray(table))

    def default_capacity(self, table: np.ndarray) -> int:
        from ..engine.device import default_capacity
        t = np.asarray(table)
        return default_capacity(t.shape[1], t.shape[0])

    def make_tables(self, table: np.ndarray):
        from ..ops import batched
        return batched.make_tables(np.asarray(table))

    def root(self, table: np.ndarray):
        n = self.slots(table)
        return (np.arange(n, dtype=np.int16)[None, :],
                np.zeros(1, np.int16))

    def seed_aux(self, table: np.ndarray, prmu: np.ndarray,
                 depth: np.ndarray) -> np.ndarray:
        from ..ops import reference as ref
        t = np.asarray(table)
        m = t.shape[0]
        adt = self.aux_dtype(t)
        if len(depth) == 0:
            return np.zeros((0, m), adt)
        return ref.prefix_front_remain(t, prmu, depth)[:, :m].astype(adt)

    def warmup(self, table: np.ndarray, lb_kind: int,
               init_ub: int | None, target: int):
        from ..engine import distributed
        return distributed.bfs_warmup(np.asarray(table), lb_kind,
                                      init_ub, target)

    def host_children(self, table: np.ndarray, node: np.ndarray,
                      depth: int, best: int, *, lb_kind: int = 1):
        # the host oracle stays on lb1 regardless of lb_kind — PFSP's
        # native -C tier (engine/hybrid.HostSession) owns lb2 hosting
        from ..ops import reference as ref
        p = np.asarray(table)
        jobs = p.shape[1]
        lb1 = ref.make_lb1_data(p)
        for i in range(depth, jobs):
            child = node.copy()
            child[depth], child[i] = child[i], child[depth]
            bound = ref.lb1_bound(lb1, child, depth, jobs)
            yield child, depth + 1, int(bound), depth + 1 == jobs

    def make_step(self, tables, lb_kind: int, chunk: int, tile: int,
                  limit: int | None):
        from ..engine.device import step
        return functools.partial(step, tables, lb_kind, chunk,
                                 tile=tile, limit=limit)


PROBLEM = base.register(PFSPProblem())
