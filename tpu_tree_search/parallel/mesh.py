"""Device-mesh construction.

The reference binds parallel workers explicitly — OpenMP thread ids to
CUDA devices intra-node (pfsp_multigpu_cuda.c:159-160) and MPI ranks to
nodes inter-node (pfsp_dist_multigpu_cuda.c:910). The TPU equivalent is a
`jax.sharding.Mesh` with a single `"workers"` axis laid over all chips:
ICI inside a slice, DCN across hosts, with no code distinction between
the two tiers — growing the mesh is the only change for multi-host
(`jax.distributed.initialize` + the same program).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

WORKER_AXIS = "workers"


def worker_mesh(n_devices: int | None = None,
                devices: list | None = None) -> Mesh:
    """1-D mesh over all (or the first n) addressable devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        assert len(devices) >= n_devices, (
            f"need {n_devices} devices, have {len(devices)}"
        )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (WORKER_AXIS,))


def partition_submeshes(n_submeshes: int,
                        devices: list | None = None) -> list[Mesh]:
    """Partition the device set into `n_submeshes` equal, disjoint 1-D
    worker meshes (8 devices -> 2 submeshes of 4, 4 of 2, ...).

    The search service schedules one request per submesh, so a
    submesh is exactly the worker_mesh() shape the engines already
    compile against — a request served on a submesh runs the same SPMD
    program a standalone `n_devices=len(submesh)` run would, with
    bit-identical node counts (device identity never enters the search;
    only the worker count does).

    Devices are split contiguously so each submesh keeps the locality
    of the underlying topology (on real hardware, neighbouring chips on
    the ICI torus; the platform's device order is already
    locality-sorted). The device count must divide evenly: silently
    dropping a remainder would strand capacity the operator believes is
    serving.
    """
    if devices is None:
        devices = jax.devices()
    if n_submeshes < 1:
        raise ValueError(f"n_submeshes must be >= 1, got {n_submeshes}")
    if len(devices) % n_submeshes:
        raise ValueError(
            f"{len(devices)} devices do not split into {n_submeshes} "
            f"equal submeshes; pick a divisor of the device count")
    per = len(devices) // n_submeshes
    return [worker_mesh(devices=list(devices[i * per:(i + 1) * per]))
            for i in range(n_submeshes)]


def shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with the engine's settings.

    check_vma is disabled: the engine's scan/while carries are seeded from
    unvarying constants but updated from worker-varying pool data, which
    the varying-manual-axes checker rejects even though the program is a
    correct SPMD computation (collectives appear only at the balance and
    termination points, by construction).
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
