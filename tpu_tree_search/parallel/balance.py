"""Collective load balancing: work stealing as an all_to_all exchange.

The reference has two dynamic load-balancing tiers: intra-node randomized
steal-half work stealing with CAS spin-locks (WS0/WS1 loops,
pfsp_multigpu_cuda.c:347-431) and inter-node collective redistribution
driven by a dedicated communicator thread (Allgather of needs + donor pops
+ Allgatherv scatter, pfsp_dist_multigpu_cuda.c:380-465). On a TPU mesh
both collapse into one synchronous exchange executed by every worker
inside the compiled loop:

1. `all_gather` the pool sizes (every worker sees the global picture —
   the analogue of the Allgather of `local_need`).
2. Compute a deterministic exchange plan, identically on every worker:
   workers above the mean donate their whole surplus, workers below
   fill their deficit, matched by interval overlap so one donor can feed
   many receivers, capped by the static transfer-buffer size. One round
   water-fills every worker to the mean. The reference's pairwise
   steal-half (popBackBulk, Pool_atom.c:154-178, ratio 2) splits one
   victim's pool with one thief, who sees no other size; the plan sees
   every size, so its fixed point is the mean, not half the gap.
3. Donors pop from the top of their stack (deepest nodes — preserving the
   DFS locality the reference's popBack stealing keeps): the rows for
   each receiver are one contiguous slice, D slices of `cap` rows form
   the send buffer, `all_to_all` moves it, and receivers append each
   sender's rows in sender order (engine/distributed._balance_round).

No locks, no victim retries, no communicator thread: the plan is a pure
function of the gathered sizes, so every worker agrees on it by
construction. Empty-handed workers keep looping (their local steps are
masked no-ops) until the exchange refills them or global termination —
the reference's idle-spin + reawaken protocol (dist:652-686) with the
spin replaced by the loop's own cadence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def waterfill_counts(total: int, m: int) -> np.ndarray:
    """(m,) per-worker pool sizes for an m-way water-filled split of
    `total` nodes: the fixed point exchange_plan's surplus/deficit
    flow fills to (max-min difference <= 1, lower worker ids carry the
    remainder — exactly the counts a round-robin stripe `d::m`
    produces, matching the warm-up seeding's roundRobin_distribution
    idiom).

    Host-side numpy on purpose: this is the elastic-resume half of the
    water-filling machinery (engine/checkpoint.reshard_state re-splits
    an N-worker snapshot across M workers with it), which runs on the
    host between segments, not inside the compiled loop."""
    if m < 1:
        raise ValueError(f"m must be >= 1, got {m}")
    if total < 0:
        raise ValueError(f"total must be >= 0, got {total}")
    return (total // m
            + (np.arange(m) < total % m).astype(np.int64))


def exchange_plan(sizes: jax.Array, cap: int, min_transfer: int) -> jax.Array:
    """(D, D) flow matrix: plan[d, e] nodes move d -> e this round.

    Pure function of the globally-known sizes vector, so every worker
    computes the same plan. Water-filling to the mean: a worker at least
    `min_transfer` nodes above the mean (the reference's `size >= 2m`
    steal threshold, popBackBulk, Pool_atom.c:154-178; by default
    2 * min_seed, engine/distributed.balance_defaults) donates its whole
    surplus, and workers below the mean fill their deficit. Not the
    reference's half: its thief sees one victim's pool, this plan sees
    every size, so one round moves every worker to the mean instead of
    halving the gap. A receiver ends at most at the mean and a donor at
    least at it, so no pool grows past the largest size before the
    round. Donor surpluses and receiver deficits are laid out as
    consecutive intervals on one shared flow axis; plan[d, e] is the
    overlap of donor d's and receiver e's intervals — so one hot worker
    feeds MANY starving workers in a single round (the
    r-th-fullest/r-th-emptiest pairing it replaces moved work to exactly
    one receiver per donor per round, which converges D× slower on wide
    meshes). Per-pair flow is capped at `cap`, the static width of the
    all_to_all transfer buffer.
    """
    D = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    mean = sizes.sum() // D
    surplus = jnp.where(sizes - mean >= min_transfer,
                        sizes - mean, 0)                     # donors
    deficit = jnp.clip(mean - sizes, 0, None)                # receivers
    d_lo = (jnp.cumsum(surplus) - surplus)[:, None]          # (D, 1)
    d_hi = d_lo + surplus[:, None]
    r_lo = (jnp.cumsum(deficit) - deficit)[None, :]          # (1, D)
    r_hi = r_lo + deficit[None, :]
    overlap = jnp.minimum(d_hi, r_hi) - jnp.maximum(d_lo, r_lo)
    return jnp.clip(overlap, 0, cap)
