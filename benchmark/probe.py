"""The vector-unit peak, measured in the traced run itself.

No published figure gives a TPU's throughput for the integer and f32
elementwise work the bound kernels do (the published peaks are the
matrix unit's). So each traced run launches this fixed kernel under
the profiler, after its window: 8 vector registers of each of two
chains, a loop of adds, subtracts and max/min on them with no memory
traffic, in int32 and in f32. Its operations over its device time in
the trace is the peak: the median launch of each dtype, and of the two
dtypes the faster, so the roofline shares against it can only come out
lower. A reading more than a factor of two from the peak recorded in
peaks.json is set aside for the recorded one (`checked_peak`).
"""

from __future__ import annotations

import functools

ROWS = 64              # (64, 128) per chain: 8 vector registers
ITERS = 2_000_000      # ~20 ms per launch on a v5e
OPS_PER_ITER = 6       # per element: add, sub, max; add, add, min
LAUNCHES = 3
NAME = "bench_vpu_probe"   # the kernel's op name in the trace


def bench_vpu_probe(x_ref, o_ref, *, iters, f32):
    import jax
    import jax.numpy as jnp
    a = x_ref[...]
    b = a * 0.5 if f32 else a ^ 0x55

    def body(_, ab):
        a, b = ab
        for _ in range(8):
            a = jnp.maximum(a + b, b - 1)
            b = jnp.minimum(b + a, a + 5)
        return a, b

    a, b = jax.lax.fori_loop(0, iters // 8, body, (a, b))
    o_ref[...] = a + b


@functools.cache
def _kernels():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    out = []
    for dt in (jnp.int32, jnp.float32):
        out.append((jax.jit(pl.pallas_call(
            functools.partial(bench_vpu_probe, iters=ITERS,
                              f32=dt == jnp.float32),
            out_shape=jax.ShapeDtypeStruct((ROWS, 128), dt),
            name=NAME)), dt))
    return out


def run(device) -> None:
    """Launch the probe kernels on `device` (inside the profiler)."""
    import jax
    import jax.numpy as jnp
    for fn, dt in _kernels():
        x = jax.device_put(jnp.ones((ROWS, 128), dt), device)
        for _ in range(LAUNCHES):
            fn(x).block_until_ready()


def peak_from_trace(summary) -> float | None:
    """Operations per second of the probe: the median launch of each
    dtype, and the faster dtype. None when the trace holds no launch."""
    import statistics

    from benchmark import kernel_work
    rates: dict[str, list[float]] = {}
    for dur, shapes in summary.probe:
        if dur > 0:
            rates.setdefault(shapes[0][0], []).append(
                kernel_work.probe(shapes, ITERS, OPS_PER_ITER) / dur)
    if not rates:
        return None
    return max(statistics.median(v) for v in rates.values())


def checked_peak(measured: float | None, recorded: float):
    """The peak a run uses, and a remark when it departs from the one
    recorded in peaks.json: a reading more than twice or less than half
    the recorded peak (a launch misread in the trace) is set aside for
    the recorded one."""
    if measured is None:
        return recorded, "no probe launch in the trace"
    if not recorded / 2 <= measured <= recorded * 2:
        return recorded, f"probe read {measured!r}, set aside"
    return measured, None
