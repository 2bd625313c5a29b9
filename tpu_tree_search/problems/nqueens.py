"""N-Queens problem definition (permutation-based backtracking).

The reference's proof-of-concept workload (reference: nqueens/lib/
NQueens_node.h:11-17, nqueens/nqueens_c.c:80-117). A node is a permutation
`board` of column->row assignments plus a `depth`: queens `0..depth-1` are
placed (one per column, rows given by `board`), the rest are candidate rows.
Branching swaps `board[depth] <-> board[j]` for each `j in depth..N-1`
whose row is diagonal-safe against the placed prefix; the permutation
scheme makes row-conflicts impossible by construction so only diagonals
are checked. A node at depth N is a solution.

`g` replicates the safety check g times to scale arithmetic intensity for
benchmarking (reference: nqueens_c.c:80-96); it does not change results.

Known solution counts (OEIS A000170) are the correctness oracle.
"""

from __future__ import annotations

import numpy as np

from . import base

# Total solutions of N-Queens for N = 0..17 (OEIS A000170).
SOLUTION_COUNTS = (
    1, 1, 0, 0, 2, 10, 4, 40, 92, 352, 724, 2680, 14200, 73712,
    365596, 2279184, 14772512, 95815104,
)


def root_node(n: int) -> tuple[np.ndarray, int]:
    """Root = identity board at depth 0 (reference: NQueens_node.c:7-13)."""
    return np.arange(n, dtype=np.int16), 0


def is_safe(board: np.ndarray, depth: int, row: int) -> bool:
    """Diagonal-safety of placing `row` in column `depth` against the prefix
    (reference: nqueens_c.c:80-96)."""
    placed = np.asarray(board[:depth], dtype=np.int64)
    dist = depth - np.arange(depth, dtype=np.int64)
    return bool(np.all((placed != row - dist) & (placed != row + dist)))


def table(n: int, g: int = 1) -> np.ndarray:
    """The N-Queens instance table: shape (g, n) — both knobs ride the
    SHAPE (they specialize the trace, like every static engine knob);
    the values are unused."""
    return np.zeros((max(int(g), 1), int(n)), np.int32)


class NQueensProblem(base.Problem):
    """N-Queens as a plugin of the generic engine.

    The jittable callables are op-for-op the pipeline the deleted
    `engine/nqueens_device.nq_step` ran (same safety kernel, same child
    grid, same masks), driven through device.generic_step — node/sol/
    evals counts are bit-identical to the pre-refactor fork (parity
    tests pin them against the sequential oracle, which the fork also
    matched exactly).
    """

    name = "nqueens"
    leaf_in_evals = False      # sols are POPPED complete boards; all
    #                            safe children (complete ones included)
    #                            are pushed — reference nqueens_c.c
    supports_host_tier = False
    lb_kinds = (0,)            # no bound function exists
    default_lb = 0
    telemetry_labels = {"objective": "none"}

    def validate(self, table: np.ndarray) -> str | None:
        t = np.asarray(table)
        if t.ndim != 2 or t.shape[0] < 1 or not 4 <= t.shape[1] <= 32:
            return (f"nqueens table must be (g>=1, 4<=n<=32), got "
                    f"shape {t.shape}")
        return None

    def slots(self, table: np.ndarray) -> int:
        return int(np.asarray(table).shape[1])

    def make_tables(self, table: np.ndarray):
        import jax.numpy as jnp
        return jnp.asarray(np.asarray(table), jnp.int32)

    def root(self, table: np.ndarray):
        n = self.slots(table)
        return (np.arange(n, dtype=np.int16)[None, :],
                np.zeros(1, np.int16))

    def host_children(self, table: np.ndarray, node: np.ndarray,
                      depth: int, best: int, *, lb_kind: int = 1):
        n = self.slots(table)
        for j in range(depth, n):
            ok = is_safe(node, depth, int(node[j]))
            child = node.copy()
            child[depth], child[j] = child[j], child[depth]
            yield child, depth + 1, (0 if ok else base.I32_MAX), \
                depth + 1 == n

    # ------------------------------------------------ jittable engine

    def branch(self, tables, p_prmu, p_depth, p_aux, valid):
        import jax.numpy as jnp

        from ..engine.device import make_children
        from ..ops import nqueens_ops
        g, n = tables.shape                 # STATIC: knobs ride the shape
        board = p_prmu.T                    # (B, n) row-major, as nq_step
        B = board.shape[0]
        safe = nqueens_ops.safe_children(board, p_depth, valid, g=g)
        children = make_children(board, p_depth).reshape(B * n, n).T
        child_depth = jnp.broadcast_to((p_depth + 1)[:, None], (B, n)) \
            .reshape(-1).astype(jnp.int16)
        evaluated = ((jnp.arange(n)[None, :] >= p_depth[:, None])
                     & valid[:, None]).reshape(-1)
        return base.BranchOut(
            children=children, child_depth=child_depth,
            child_aux=jnp.zeros((0, B * n), jnp.int32),
            evaluated=evaluated, extras=safe.reshape(-1))

    def bound(self, tables, lb_kind: int, br, best):
        import jax.numpy as jnp
        # no bound function: 0 = safe (always survives the I32_MAX
        # incumbent), I32_MAX = unsafe (never does)
        return jnp.where(br.extras, 0, 2**31 - 1).astype(jnp.int32)


PROBLEM = base.register(NQueensProblem())


def search(n: int, g: int = 1, chunk: int = 64, capacity: int = 1 << 18,
           max_iters: int | None = None):
    """Single-device N-Queens through the generic engine (the drop-in
    for the deleted nqueens_device.search)."""
    from ..engine import device
    return device.solve(PROBLEM, table(n, g), lb_kind=0, chunk=chunk,
                        capacity=capacity, max_iters=max_iters)


def search_distributed(n: int, g: int = 1, n_devices: int | None = None,
                       chunk: int = 64, capacity: int = 1 << 17,
                       balance_period: int = 4, min_seed: int = 32,
                       transfer_cap: int | None = None,
                       min_transfer: int | None = None, mesh=None):
    """Distributed N-Queens through the generic SPMD engine (the
    drop-in for the deleted nqueens_device.search_distributed, with
    its exact 4*chunk / 2*chunk transfer defaults, which the tiny-chunk
    tests were sized for; distributed.balance_defaults would re-size
    them)."""
    from ..engine import distributed
    return distributed.search(
        table(n, g), problem="nqueens", lb_kind=0, n_devices=n_devices,
        chunk=chunk, capacity=capacity, balance_period=balance_period,
        min_seed=min_seed, transfer_cap=transfer_cap or 4 * chunk,
        min_transfer=min_transfer or 2 * chunk, mesh=mesh)
