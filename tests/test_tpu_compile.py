"""The main path's Pallas kernels compile for a described TPU v5e chip.

No chip is attached: `topologies.get_topology_desc` describes one, and
each kernel is lowered from shapes placed on its first device and
compiled by the TPU compiler installed here. A compile that passes is
not a chip run; it catches what the Pallas interpreter cannot (tile
alignment, VMEM limits, primitives Mosaic cannot lower) at no chip
time. Shapes are the Taillard classes the engine serves: ta021 (20x20),
ta031 (50x5) and ta101 (200x20).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every xdist worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tree_search.ops import batched, pallas_expand
from tpu_tree_search.problems import taillard
from tpu_tree_search.utils import compile_cache


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the persistent cache would store these compiles and fail to read
    # them back without a chip: keep it off for this module
    with compile_cache.disabled():
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _tables(sharding, inst):
    t = batched.make_tables(taillard.processing_times(inst))
    return jax.tree.map(
        lambda x: _sds(sharding, np.shape(x), np.asarray(x).dtype), t)


def _parents(sharding, jobs, machines, batch):
    return (_sds(sharding, (jobs, batch), jnp.int16),
            _sds(sharding, (1, batch), jnp.int32),
            _sds(sharding, (machines, batch), jnp.int32))


def _compiled_text(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()


@pytest.mark.parametrize("lb_kind", [0, 1])
@pytest.mark.parametrize("kernel", ["expand_tpu", "expand_bounds_tpu"])
def test_expand_kernels_compile_at_ta021(one_chip, kernel, lb_kind):
    fn = getattr(pallas_expand, kernel)
    text = _compiled_text(fn, _tables(one_chip, 21),
                          *_parents(one_chip, 20, 20, 2048),
                          lb_kind=lb_kind, tile=1024)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("inst", [21, 31, 101])
def test_lb2_pair_sweep_compiles(one_chip, inst):
    # the same tile rule and kernel choice as pallas_expand.lb2_bounds:
    # the resident-table kernel up to J=64, the streaming one beyond
    machines, jobs = taillard.processing_times(inst).shape
    tables = _tables(one_chip, inst)
    pairs = int(tables.ma0.shape[0])
    width = jobs * 2048 if jobs <= 64 else 4096
    tile = pallas_expand.lb2_sweep_tile(jobs, pairs, machines, width)
    assert tile > 0
    fits = pallas_expand.lb2_kernel_fits(jobs, pairs)
    assert fits == (jobs <= 64)
    fn = (pallas_expand.lb2_bounds_tpu if fits
          else pallas_expand.lb2_bounds_bigj_tpu)
    text = _compiled_text(fn, tables,
                          _sds(one_chip, (machines, width), jnp.int32),
                          _sds(one_chip, (jobs, width), jnp.bfloat16),
                          tile=tile)
    assert "tpu_custom_call" in text


def test_tile64_expand_is_refused_and_not_admitted(one_chip):
    # the 200x20 class's TB=64 expand tile: the installed Mosaic cannot
    # lower its (J, 64) -> (1, J*64) reshape, so kernel_shape_ok must
    # send it to the XLA fallback. When the compile passes, TB=64 can be
    # admitted again after a run on the chip.
    assert pallas_expand.effective_tile(200, 1024, 1024, 1,
                                        machines=20) == 64
    assert not pallas_expand.kernel_shape_ok(200, 64, 1, machines=20)
    rng = np.random.default_rng(17)
    tables = jax.tree.map(
        lambda x: _sds(one_chip, np.shape(x), np.asarray(x).dtype),
        batched.make_tables(rng.integers(1, 100, (20, 200))
                            .astype(np.int32)))
    # jax raises a private MosaicError (a plain Exception subclass)
    with pytest.raises(Exception, match="unsupported shape cast"):
        _compiled_text(pallas_expand.expand_bounds_tpu, tables,
                       *_parents(one_chip, 200, 20, 1024), lb_kind=1,
                       tile=64)


@pytest.mark.parametrize("inst", [21, 101])
def test_pair_order_program_compiles(one_chip, inst):
    # make_tables' strong-pair order, at the 20x20 class it serves
    # and at J=200
    machines, jobs = taillard.processing_times(inst).shape
    pairs = machines * (machines - 1) // 2
    n = len(batched._calibration_samples(jobs)[2])
    shapes = [(jobs, machines), (machines,), (n, jobs), (n, jobs), (n,),
              (pairs,), (pairs,)] + [(pairs, jobs)] * 4
    args = [_sds(one_chip, s, jnp.int32) for s in shapes]
    text = batched._strongest_first.lower(*args).compile().as_text()
    assert "sort" in text


def test_balance_round_compiles_for_four_chips(topo):
    # the flagship's round at its shapes (2^22-row pools of ta022, the
    # defaults of a 4-chip 20x20 run): the exchange is slices, one
    # all-to-all per pool array and in-place block writes, with no sort
    # and no gather
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_tree_search.engine import device, distributed
    from tpu_tree_search.parallel.mesh import WORKER_AXIS, shard_map

    mesh = Mesh(np.asarray(topo.devices), (WORKER_AXIS,))
    p = taillard.processing_times(22)
    machines, jobs = p.shape
    adt = device.aux_dtype(p)
    cap, min_transfer = distributed.balance_defaults(
        65536, jobs, machines, 4, 25, aux_itemsize=adt.itemsize)
    capacity = 1 << 22
    limit = min(device.row_limit(capacity, 65536, jobs),
                capacity - 4 * cap)
    shard = NamedSharding(mesh, P(WORKER_AXIS))
    i64 = jnp.zeros((), jnp.int64).dtype
    state = device.SearchState(
        prmu=_sds(shard, (4, jobs, capacity), jnp.int16),
        depth=_sds(shard, (4, capacity), jnp.int16),
        aux=_sds(shard, (4, machines, capacity), adt),
        size=_sds(shard, (4,), jnp.int32),
        best=_sds(shard, (4,), jnp.int32),
        tree=_sds(shard, (4,), i64), sol=_sds(shard, (4,), i64),
        iters=_sds(shard, (4,), i64), evals=_sds(shard, (4,), i64),
        sent=_sds(shard, (4,), i64), recv=_sds(shard, (4,), i64),
        steals=_sds(shard, (4,), i64),
        overflow=_sds(shard, (4,), jnp.bool_),
        telemetry=_sds(shard, (4, 0), i64))
    spec = tuple(P(WORKER_AXIS) for _ in device.SearchState._fields)

    def body(*leaves):
        s = distributed._local_state(*leaves)
        return distributed._expand(distributed._balance_round(
            s, cap, min_transfer, limit))

    text = jax.jit(shard_map(body, mesh, in_specs=spec, out_specs=spec)
                   ).lower(*state).compile().as_text()
    assert " gather(" not in text and " sort(" not in text
    assert text.count("all-to-all(") == 3
