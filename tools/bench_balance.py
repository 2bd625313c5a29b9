"""Measure the balance exchange at PRODUCTION shapes.

Times `_balance_round` on the 8-worker virtual CPU mesh with
20x20-class pools at chunk 32768 and a sweep of transfer_cap values
(including the default of distributed.balance_defaults, whose donor
threshold every round uses), reporting ms/round and the
all_to_all buffer footprint. Multi-chip hardware is not reachable from
this environment, so absolute times are CPU-mesh numbers — the useful
outputs are the RELATIVE cost vs transfer_cap and the buffer sizes,
which are backend-independent.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bench_balance.py
"""

import functools
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tpu_tree_search.engine import device, distributed  # noqa: E402
from tpu_tree_search.ops import batched, reference as ref  # noqa: E402
from tpu_tree_search.parallel.mesh import shard_map, worker_mesh  # noqa: E402
from tpu_tree_search.problems import taillard  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def main():
    from tpu_tree_search.utils import config as _cfg
    chunk = _cfg.env_int("TTS_BAL_CHUNK")
    capacity = _cfg.env_int("TTS_BAL_CAP")
    rounds = _cfg.env_int("TTS_BAL_ROUNDS")
    p = taillard.processing_times(21)
    jobs, machines = p.shape[1], p.shape[0]
    mesh = worker_mesh(8)
    D = mesh.devices.size

    # unbalanced production-like pools: worker 0 loaded, rest light —
    # every round has real flow
    rng = np.random.default_rng(0)
    sizes = [int(0.5 * capacity)] + [chunk // 2] * (D - 1)
    prmu = np.zeros((D, jobs, capacity), np.int16)
    depth = np.zeros((D, capacity), np.int16)
    aux = np.zeros((D, machines, capacity), device.aux_dtype(p))
    for d in range(D):
        n = sizes[d]
        pm = np.argsort(rng.random((n, jobs)), axis=1).astype(np.int16)
        dp = rng.integers(4, 12, n).astype(np.int16)
        prmu[d, :, :n] = pm.T
        depth[d, :n] = dp
        aux[d, :, :n] = ref.prefix_front_remain(p, pm, dp)[:, :machines].T

    base = device.init_state(jobs, capacity, 3000, p_times=p)
    leaves = []
    for f in base._fields:
        x = getattr(base, f)
        if f in ("prmu",):
            leaves.append(jnp.asarray(prmu))
        elif f == "depth":
            leaves.append(jnp.asarray(depth))
        elif f == "aux":
            leaves.append(jnp.asarray(aux))
        elif f == "size":
            leaves.append(jnp.asarray(np.asarray(sizes, np.int32)))
        else:
            leaves.append(jnp.broadcast_to(x, (D,) + x.shape).copy())
    specs = device.SearchState(*(P("workers") for _ in base._fields))

    itemsize = device.aux_dtype(p).itemsize
    bytes_per_col = 2 * jobs + itemsize * machines + 2
    default_cap, min_transfer = distributed.balance_defaults(
        chunk, jobs, machines, D, distributed.MIN_SEED,
        aux_itemsize=itemsize)
    caps = sorted({chunk // 2, chunk, 2 * chunk, 4 * chunk, default_cap})
    for cap in caps:
        limit = device.row_limit(capacity, chunk, jobs) - D * cap

        @functools.partial(jax.jit)
        def run(leaves_):
            def body(*ls):
                s = device.SearchState(*(x[0] for x in ls))
                for _ in range(1):
                    s = distributed._balance_round(s, cap, min_transfer,
                                                   limit)
                return tuple(x[None] for x in s)
            return shard_map(body, mesh,
                             in_specs=tuple(specs),
                             out_specs=tuple(specs))(*leaves_)

        out = run(tuple(leaves))
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(rounds):
            out = run(tuple(out))
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / rounds * 1e3
        buf_mb = bytes_per_col * D * cap / 2**20
        print(f"transfer_cap={cap:7d}: {dt:8.2f} ms/round  "
              f"buffer {buf_mb:7.1f} MB/worker/way  "
              f"moved<= {D * cap} nodes/worker")


if __name__ == "__main__":
    main()
