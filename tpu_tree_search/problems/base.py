"""Problem-plugin protocol and registry: one generic B&B engine, many
workloads.

The reference cleanly separates problem definition (L1) and bounding
(L2) from the search engine — `Node`/branching and the LB functions are
swappable per problem while the multi-pool DFS core is shared (PAPER.md
layer map). This module is that separation for the TPU engine: a
:class:`Problem` is a *singleton plugin* that tells the problem-blind
pipeline (engine/device.generic_step, engine/distributed.search)
everything problem-specific:

- **static shape spec** from one 2-D instance table (`slots` — the pool
  node width, `aux_rows`/`aux_dtype` — the per-node side tables,
  `shape_class` — the tuning-table key);
- **jittable callables**: `branch` (the dense child grid + evaluated
  mask), `bound` (child bound values; at leaf children the bound must
  equal the exact objective, the PFSP convention), `is_leaf_cols`,
  `make_step` (the optional Pallas fast-path hook — PFSP overrides it
  with the specialized engine/device.step pipeline; the default builds
  engine/device.generic_step from branch/bound);
- **host-side seeding**: `root` / `seed_aux` / `warmup` (the BFS
  frontier generator the distributed seeding consumes);
- **accounting semantics**: `leaf_in_evals` picks between the two
  counting conventions the reference ships — PFSP-style (every
  evaluated leaf child counts as a solution; solutions are never
  pushed) and N-Queens-style (all safe children are pushed, a POPPED
  complete node counts as a solution) — and the node-conservation
  auditor (obs/audit) keys its invariant off it;
- **telemetry labels** for the per-problem observability surface.

THE UNIVERSAL INSTANCE FORMAT is one 2-D integer table (the thing every
transport — spool payloads, the request ledger, checkpoints, HTTP
bodies — already knows how to carry as `p_times`):

=========  ======================  =====================================
problem    table shape             meaning
=========  ======================  =====================================
pfsp       (machines, jobs)        processing times
nqueens    (g, n)                  board size n; g safety-check repeats
                                   ride the SHAPE (static, like every
                                   trace-specializing knob)
tsp        (n, n)                  city distance matrix
knapsack   (3, n)                  rows: weights, values,
                                   [capacity, 0, ...]
=========  ======================  =====================================

Registration is import-time (`problems/__init__` registers the four
built-ins); `get(name)` is the single resolution point the engine,
service and CLI share.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

I32_MAX = 2**31 - 1


class HostTierUnsupported(ValueError):
    """Typed refusal for `-C host_fraction > 0` on a problem whose
    plugin has no host tier (`Problem.supports_host_tier` is False).
    Subclasses ValueError so pre-existing callers that caught the old
    untyped refusal keep working; service admission and the CLI match
    on the type to reject the request instead of crashing the worker."""

    def __init__(self, problem: str):
        self.problem = problem
        super().__init__(
            f"the -C host tier is not supported for problem "
            f"{problem!r} (no host_children/host-session support; "
            f"set supports_host_tier on the plugin to enable it)")


class BranchOut(NamedTuple):
    """One step's dense child grid, feature-major like the pool.

    `children` is (J, C) int16 with C = chunk * branching-factor;
    `evaluated` marks the real child columns (invalid parents and
    below-depth slots are masked off). `extras` is an opaque pytree
    `branch` hands to `bound` so shared intermediates (edge costs,
    feasibility masks) are computed once.
    """

    children: Any        # (J, C) int16
    child_depth: Any     # (C,) int16
    child_aux: Any       # (A, C) int32 (cast to the pool dtype at write)
    evaluated: Any       # (C,) bool
    extras: Any = ()


class Problem:
    """Base plugin. Subclasses are stateless singletons — every
    per-instance quantity must derive from the instance table (values
    at trace time are runtime arguments; anything static must ride the
    table's SHAPE, exactly like jit static args)."""

    name: str = ""
    # PFSP-style accounting (True): every evaluated leaf child counts
    # toward `sol` and leaves are never pushed; the audit identity is
    # branched + pruned + sol == evals. N-Queens-style (False): all
    # surviving children are pushed (complete nodes included), a popped
    # complete node counts as a solution; branched + pruned == evals.
    leaf_in_evals: bool = True
    # the -C heterogeneous host tier (engine/hybrid): PFSP runs the
    # native C++ runtime, other opted-in plugins get the generic
    # Python session over host_children (hybrid.PyHostSession). The
    # engine raises HostTierUnsupported for host_fraction > 0 on a
    # plugin that has not opted in.
    supports_host_tier: bool = False
    lb_kinds: tuple = (1,)
    default_lb: int = 1
    # children per popped parent; None = slots (permutation problems'
    # dense (chunk, J) child grid). The engine sizes the pool's
    # scratch margin off this, so a low-branching problem (knapsack:
    # 2) does not reserve chunk*J rows it can never write.
    branch_factor: int | None = None
    # identity labels merged into the per-request telemetry gauges
    # (engine/telemetry.publish) so /metrics rows are self-describing
    telemetry_labels: dict = {"objective": "bound"}

    # ------------------------------------------------------ static spec

    def validate(self, table: np.ndarray) -> str | None:
        """Admission-side table validation; a rejection reason or None."""
        raise NotImplementedError

    def slots(self, table: np.ndarray) -> int:
        """Pool node width J (the prmu row length)."""
        raise NotImplementedError

    def aux_rows(self, table: np.ndarray) -> int:
        return 0

    def aux_dtype(self, table: np.ndarray) -> np.dtype:
        return np.dtype(np.int32)

    def branching(self, table: np.ndarray) -> int:
        """Children per parent (the child-grid width per popped node)."""
        return self.branch_factor or self.slots(table)

    def usable_rows(self, capacity: int, chunk: int, slots: int) -> int:
        """Usable pool rows: capacity minus the chunk*branching scratch
        margin (the generalization of engine/device.row_limit — an
        overflowing step routes its full-width block write there, so
        every commit point must keep size <= this)."""
        return max(capacity - chunk * (self.branch_factor or slots), 0)

    def default_capacity(self, table: np.ndarray) -> int:
        return 1 << 18

    def make_tables(self, table: np.ndarray):
        """Replicated jittable pytree of problem tables. Only shapes and
        dtypes specialize the trace — the values are runtime arguments,
        so same-shape instances share one compiled loop."""
        raise NotImplementedError

    # -------------------------------------------------- host-side seed

    def root(self, table: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Seed rows: ((n0, J) int16 nodes, (n0,) int16 depths)."""
        raise NotImplementedError

    def seed_aux(self, table: np.ndarray, prmu: np.ndarray,
                 depth: np.ndarray) -> np.ndarray | None:
        """(n, A) per-node aux rows for host-built nodes (None when
        A == 0). Must agree exactly with what `branch` maintains."""
        return None

    def warmup(self, table: np.ndarray, lb_kind: int,
               init_ub: int | None, target: int):
        """Host BFS frontier of >= `target` nodes (or the exhausted
        tree) with warm-up counters — the distributed seeding input
        (engine/distributed.Frontier). Default: generic pop-front BFS
        over :meth:`host_children`."""
        from ..engine.distributed import Frontier
        from collections import deque

        best = I32_MAX if init_ub is None else int(init_ub)
        tree = sol = 0
        prmu0, depth0 = self.root(table)
        frontier: deque = deque(
            (np.asarray(p, np.int16), int(d))
            for p, d in zip(prmu0, depth0))
        while frontier and len(frontier) < target:
            node, depth = frontier.popleft()
            if not self.leaf_in_evals and depth == self.slots(table):
                sol += 1
                continue
            for child, cdepth, bound, is_leaf in self.host_children(
                    table, node, depth, best, lb_kind=lb_kind):
                if self.leaf_in_evals and is_leaf:
                    sol += 1
                    if bound < best:
                        best = bound
                elif bound < best:
                    frontier.append((child, cdepth))
                    tree += 1
        J = self.slots(table)
        if frontier:
            prmu = np.stack([f[0] for f in frontier]).astype(np.int16)
            depth = np.array([f[1] for f in frontier], np.int16)
        else:
            prmu = np.zeros((0, J), np.int16)
            depth = np.zeros(0, np.int16)
        return Frontier(prmu=prmu, depth=depth, tree=tree, sol=sol,
                        best=best)

    def host_children(self, table: np.ndarray, node: np.ndarray,
                      depth: int, best: int, *, lb_kind: int = 1):
        """Host-side oracle branching: yield (child, child_depth,
        bound, is_leaf) for every evaluated child of one node —
        the warm-up generator, the `-C` host tier's generic session
        (engine/hybrid.PyHostSession) and the conformance tests'
        reference semantics. Must match `branch`+`bound` exactly for
        the same `lb_kind` (plugins with one bound tier may ignore
        the keyword)."""
        raise NotImplementedError

    # ------------------------------------------------- jittable engine

    def branch(self, tables, p_prmu, p_depth, p_aux, valid) -> BranchOut:
        """Dense child grid of a popped block. Feature-major popped
        inputs: p_prmu (J, B) int16, p_depth (B,) int32 (invalid
        columns zeroed), p_aux (A, B) int32, valid (B,) bool."""
        raise NotImplementedError

    def bound(self, tables, lb_kind: int, br: BranchOut, best):
        """(C,) int32 child bounds. Convention: for `leaf_in_evals`
        problems a LEAF child's bound is its exact objective (the PFSP
        complete-schedule-LB==makespan identity the incumbent update
        relies on); unbounded problems return 0 (survive) / I32_MAX
        (infeasible)."""
        raise NotImplementedError

    def is_leaf_cols(self, tables, br: BranchOut):
        """(C,) bool: which child columns are complete solutions."""
        import jax.numpy as jnp
        J = br.children.shape[0]
        return br.child_depth.astype(jnp.int32) == J

    def make_step(self, tables, lb_kind: int, chunk: int, tile: int,
                  limit: int | None):
        """SearchState -> SearchState step callable. The default wires
        the generic pop/bound/prune/branch/compact pipeline
        (engine/device.generic_step); plugins with a specialized
        (Pallas) pipeline override this — the fast-path hook."""
        import functools

        from ..engine.device import generic_step
        return functools.partial(generic_step, self, tables, lb_kind,
                                 chunk, tile=tile, limit=limit)

    # ------------------------------------------------------- reporting

    def display_objective(self, best: int) -> int:
        """Human-facing objective from the engine's minimized `best`
        (knapsack negates: the engine minimizes -value)."""
        return int(best)

    def engine_objective(self, value: int) -> int:
        """The inverse of :meth:`display_objective`: a human-facing
        objective value (e.g. a CLI --ub seed) converted into the
        engine's minimized domain. Every caller that accepts an
        objective from a user must route it through here — seeding a
        knapsack incumbent with a raw positive value would silently
        disable pruning instead of tightening it."""
        return int(value)

    def __repr__(self) -> str:  # pragma: no cover - debug nicety
        return f"<Problem {self.name!r}>"


# --------------------------------------------------------------- registry

_REGISTRY: dict[str, Problem] = {}


def register(problem: Problem) -> Problem:
    """Register a plugin singleton under `problem.name` (idempotent for
    the same object; a name collision with a DIFFERENT object raises —
    two definitions of one problem would silently fork semantics)."""
    if not problem.name:
        raise ValueError("problem plugins must set a non-empty .name")
    prior = _REGISTRY.get(problem.name)
    if prior is not None and prior is not problem:
        raise ValueError(f"problem {problem.name!r} is already "
                         f"registered by {prior!r}")
    _REGISTRY[problem.name] = problem
    return problem


def get(name: str) -> Problem:
    """The single resolution point: engine, service, spool and CLI all
    resolve problem names here."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown problem {name!r} (registered: {sorted(_REGISTRY)})"
        ) from None


def names() -> list[str]:
    return sorted(_REGISTRY)
