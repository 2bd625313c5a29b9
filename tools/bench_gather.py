"""Micro-benchmark: TPU gather formulations at the LB2 step's exact
compaction shapes (ta021, chunk 32768: N = 655,360 child slots).

The round-3 chip step profile pinned 2.56 ms of the 6.83 ms
LB2 step in six column gathers over feature-major (rows, N) blocks —
~17 GB/s effective, 2% of v5e HBM bandwidth, because gathering along
the minor (lane) axis is element/latency-bound on TPU. This tool
measures the alternatives before the engine commits to one:

  fm   jnp.take(src (rows, N) i32, idx, axis=1)   [current engine path]
  rm   jnp.take(src (N, rows) i32, idx, axis=0)   row-major: each
       gathered row is a contiguous rows*4B run (DMA-friendly)
  rmT  rm + transpose of the (t, rows) result back to feature-major
       (what the engine would actually pay, since the sweeps and the
       pool are feature-major)
  fmT  transpose src to (N, rows) on the fly, rm gather, transpose back
       (no engine refactor needed — pays 2 transposes per gather)

Timing: each variant runs inside ONE compiled fori_loop (the host
dispatch floor would swamp per-call timing); the gathered
block is reduced into the carry so XLA cannot hoist the gather, and the
index vector is rolled by the loop counter so iterations are not CSE'd.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 200


def _time_loop(fn, *args, iters=ITERS):
    @jax.jit
    def loop(args):
        def body(i, carry):
            acc, args = carry
            out = fn(i, *args)
            return acc + out, args
        acc0 = jnp.zeros((), jnp.int32)
        acc, _ = jax.lax.fori_loop(0, iters, body, (acc0, args))
        return acc

    out = loop(args).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    out = loop(args).block_until_ready()
    dt = time.perf_counter() - t0
    return dt / iters * 1e3, int(out)  # ms per iteration


def bench_shape(rows, srcN, t, label):
    rng = np.random.default_rng(0)
    src_fm = jnp.asarray(rng.integers(0, 1000, (rows, srcN), np.int32))
    src_rm = jnp.asarray(np.ascontiguousarray(np.asarray(src_fm).T))
    # replace=True: round-1 regathers index chunk-wide parents from
    # N/4 child slots, so indices repeat (children share parents)
    idx = jnp.asarray(np.sort(rng.choice(srcN, t, replace=True))
                      .astype(np.int32))

    def vary(i, ix):
        # cheap per-iteration perturbation (defeats CSE/hoisting);
        # stays in-range, preserves sortedness shape-wise
        return jax.lax.optimization_barrier((ix + i) % srcN)

    def g_fm(i, src, ix):
        out = jnp.take(src, vary(i, ix), axis=1)
        return jax.lax.optimization_barrier(out).sum(dtype=jnp.int32)

    def g_rm(i, src, ix):
        out = jnp.take(src, vary(i, ix), axis=0)
        return jax.lax.optimization_barrier(out).sum(dtype=jnp.int32)

    def g_rmT(i, src, ix):
        out = jnp.take(src, vary(i, ix), axis=0)
        out = jax.lax.optimization_barrier(out).T
        return jax.lax.optimization_barrier(out).sum(dtype=jnp.int32)

    def g_fmT(i, src, ix):
        srcT = jax.lax.optimization_barrier(src.T)
        out = jnp.take(srcT, vary(i, ix), axis=0)
        out = jax.lax.optimization_barrier(out).T
        return jax.lax.optimization_barrier(out).sum(dtype=jnp.int32)

    res = {}
    for name, fn, args in (("fm", g_fm, (src_fm, idx)),
                           ("rm", g_rm, (src_rm, idx)),
                           ("rmT", g_rmT, (src_rm, idx)),
                           ("fmT", g_fmT, (src_fm, idx))):
        ms, _ = _time_loop(fn, *args)
        res[name] = ms
    gb = rows * t * 4 / 1e9
    print(f"{label:34s} rows={rows:3d} srcN={srcN:7d} t={t:7d}  "
          + "  ".join(f"{k}={v:7.3f}ms ({gb / (v / 1e3):5.1f}GB/s)"
                      for k, v in res.items()))
    return res


def bench_src_width(rows, srcN, t, label, dtype=jnp.int32):
    """Direct fm gather cost vs allocated source width (cliff hunt)."""
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.integers(0, 1000, (rows, srcN))
                      .astype(np.int32)).astype(dtype)
    idx = jnp.asarray(np.sort(rng.choice(srcN, t, replace=True))
                      .astype(np.int32))

    def g(i, src, ix):
        ix = jax.lax.optimization_barrier((ix + i) % srcN)
        out = jnp.take(src, ix, axis=1)
        return jax.lax.optimization_barrier(out).sum(dtype=jnp.int32)

    ms, _ = _time_loop(g, src, idx)
    mb = rows * srcN * src.dtype.itemsize / 1e6
    print(f"{label:34s} rows={rows:3d} srcN={srcN:7d} ({mb:6.1f}MB) "
          f"t={t:7d}  {ms:7.3f}ms  {ms / t * 1e6:6.1f}ns/idx")
    return ms


def main():
    J, M, B = 20, 20, 32768
    N = B * J
    print(f"devices: {jax.devices()}")
    # round-1 regather sources are chunk-wide (parents)
    bench_shape(J + M + 1, B, N // 4, "round1 regather (parents)")
    # round-2 mid-compaction: children+aux_plus over N-wide blocks
    bench_shape(J + M + 3, N, 3 * N // 32, "round2 mid-compaction")
    # round-3 final compaction
    bench_shape(J + M + 1, N, N // 16, "round3 final compaction")
    # sensitivity: single wide gather at round-1 width over N-wide source
    bench_shape(J + M + 1, N, N // 4, "N-wide source at N/4")

    print("\n--- source-width cliff (fm gather, fixed t=61440) ---")
    for s in (32768, 65536, 98304, 131072, 163840, 327680, 655360):
        bench_src_width(41, s, 61440, f"src width {s}")

    print("\n--- row scaling (srcN=655360, t=61440) ---")
    for r in (1, 2, 8, 21, 41):
        bench_src_width(r, N, 61440, f"rows {r}")

    print("\n--- 1-row (N,)-source composition takes ---")
    for t in (40960, 61440, 163840, 655360):
        bench_src_width(1, N, t, f"compose t={t}")

    print("\n--- dtype effect (rows=20, srcN=655360, t=61440) ---")
    bench_src_width(20, N, 61440, "i32", jnp.int32)
    bench_src_width(20, N, 61440, "i16", jnp.int16)

    print("\n--- chunk-wide source, t scaling (rows=41, srcN=32768) ---")
    for t in (40960, 61440, 163840):
        bench_src_width(41, B, t, f"parents t={t}")

    print("\n--- slice-then-gather from N-wide source (the engine fix) ---")
    rng = np.random.default_rng(1)
    for rows, s, t in ((43, N // 4, 3 * N // 32), (41, 3 * N // 32, N // 16),
                       (43, N // 4, N // 4), (41, N // 16, N // 16)):
        src = jnp.asarray(rng.integers(0, 1000, (rows, N), np.int32))
        idx = jnp.asarray(np.sort(rng.choice(s, t, replace=True))
                          .astype(np.int32))

        def g(i, src, ix, s=s):
            ix = jax.lax.optimization_barrier((ix + i) % s)
            sub = jax.lax.optimization_barrier(
                jax.lax.slice(src, (0, 0), (src.shape[0], s)))
            out = jnp.take(sub, ix, axis=1)
            return jax.lax.optimization_barrier(out).sum(dtype=jnp.int32)

        ms, _ = _time_loop(g, src, idx)
        print(f"slice N->{s:7d} t={t:7d} rows={rows}   {ms:7.3f}ms")


if __name__ == "__main__":
    main()
