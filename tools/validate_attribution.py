"""Validate phase_timing.attribute against profiler traces.

phase_timing attributes wall time from measured unit costs x counters
(kernel/compaction/balance/idle). Its `kernel_time` column BRACKETS
pop + mask + dense bound — the same semantics as the reference's
kernel timer, which wraps the whole evaluate_gpu region including
copies and launch (PFSP_statistic.c:69-112) — NOT the bound op alone.
This script therefore reports TWO ground truths per bound, each with
its own error bar:

- bracket vs traced bracket: the attributed per-step kernel cost
  against the device self-time of an independently traced
  pop+mask+bound loop — same semantics, so this is THE error bar for
  the attribution itself (target <=10% for both bounds).
- op share (informational): the attributed kernel share of device time
  against the trace share of the bound OP alone. For LB2 the dense
  sweeps dominate the bracket so the two nearly coincide (~3%); for
  LB1 the bound op is a small part of its bracket, so this pair
  differs by DEFINITION (~2.4x) — the number documents the gap, it is
  not an attribution error.

    python tools/validate_attribution.py [--iters 30] [--chunk 32768]
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from tpu_tree_search.engine import device  # noqa: E402
from tpu_tree_search.obs import profiler, tracelog  # noqa: E402
from tpu_tree_search.obs.chrome_trace import (load_xla_trace,  # noqa: E402
                                              self_times)
from tpu_tree_search.ops import batched  # noqa: E402
from tpu_tree_search.problems import taillard  # noqa: E402
from tpu_tree_search.utils import phase_timing  # noqa: E402

KERNEL_OPS = ("expand_bounds", "lb2_bounds", "pallas")


def trace_kernel_share(log_dir):
    self_us, _ = self_times(load_xla_trace(log_dir))
    total = sum(self_us.values())
    kern = sum(v for k, v in self_us.items()
               if any(s in k.lower() for s in KERNEL_OPS))
    return kern / total if total else 0.0, total / 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--chunk", type=int, default=32768)
    ap.add_argument("--inst", type=int, default=21)
    ap.add_argument("--warm", type=int, default=400)
    args = ap.parse_args()

    p = taillard.processing_times(args.inst)
    ub = taillard.optimal_makespan(args.inst)
    tables = batched.make_tables(p)
    jobs = p.shape[1]

    for lb in (1, 2):
        state = device.init_state(jobs, 1 << 22, ub, p_times=p)
        state = device.run(tables, state, lb, args.chunk,
                           max_iters=args.warm)
        state.size.block_until_ready()

        # the attribution's unit costs, measured on the same shapes
        prof = phase_timing.profile_phases(tables, state, lb, args.chunk)

        log_dir = tempfile.mkdtemp(prefix=f"tts_attr_lb{lb}_")
        with tracelog.span("validate_attribution.traced_window",
                           lb=lb, logdir=log_dir) as win_sp:
            with profiler.trace(log_dir):
                out = device.run(tables, state, lb, args.chunk,
                                 max_iters=args.warm + args.iters)
                out.size.block_until_ready()
        elapsed = win_sp.dur
        evals = int(out.evals) - int(state.evals)
        iters = int(out.iters) - int(state.iters)

        att = phase_timing.attribute(prof, elapsed, [evals], [iters])
        att_kernel = float(att["kernel_time"][0])
        att_share = att_kernel / elapsed

        trace_share, trace_total_s = trace_kernel_share(log_dir)
        # compare against the DEVICE-time share too: wall includes
        # dispatch/host gaps the device never sees
        att_dev_share = att_kernel / trace_total_s if trace_total_s else 0

        # INDEPENDENT bracket ground truth: trace the same
        # pop+mask+bound loop the unit cost was measured on, and take
        # its device self-time per rep — same semantics as the
        # attributed kernel bracket, so |error| here is the
        # attribution's real error bar for BOTH bounds.
        import jax
        import jax.numpy as jnp
        # 256 reps (r5, was 64): the LB1 bracket is ~0.3 ms, so per-rep
        # wall slack that the two-trip differencing cannot cancel
        # (device scheduling bubbles, loop-carry overhead) amortizes
        # only with a long window — K=64 read +38.6% on LB1 (r4)
        from tpu_tree_search.utils import config as _cfg
        K = _cfg.env_int("TTS_BRACKET_REPS")

        def make_loop(reps):
            @jax.jit
            def bracket_loop(s):
                def body(i, acc):
                    return acc + phase_timing._pop_and_bound(
                        tables,
                        s._replace(size=jnp.maximum(s.size - i * 128, 1)),
                        lb, args.chunk, 1024).sum(dtype=jnp.float32)
                return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))
            return bracket_loop

        loop1, loop2 = make_loop(K), make_loop(2 * K)

        def wall(fn):
            fn(state).block_until_ready()        # compile outside
            with tracelog.span("validate_attribution.bracket_wall",
                               lb=lb) as sp:
                fn(state).block_until_ready()
            return sp.dur

        # two trip counts, differenced: one dispatch through the remote
        # runtime costs ~10-100 ms of wall that a single-K measurement
        # folds into the per-rep cost (the LB1 bracket is ~0.3 ms, so a
        # K=64 single measurement read 4x too high)
        bracket_wall_per_rep = (wall(loop2) - wall(loop1)) / K
        bracket_loop = loop2
        bdir = tempfile.mkdtemp(prefix=f"tts_bracket_lb{lb}_")
        with profiler.trace(bdir):
            bracket_loop(state).block_until_ready()
        bracket_self, _ = self_times(load_xla_trace(bdir))
        bracket_dev_per_rep = sum(bracket_self.values()) / 1e6 / (2 * K)
        # Same loop, wall-timed vs trace device self-time: this
        # validates the attribution's MEASUREMENT method (the unit
        # costs phase_timing wall-times in compiled loops) at matching
        # pop+mask+bound semantics for both bounds. It deliberately
        # does NOT use prof["bound"] for LB2, which is already scaled
        # by the production sweep-tier fraction (phase_timing
        # profile_phases) and would spuriously compare a scaled number
        # against the unscaled dense trace; the tier scaling is
        # arithmetic applied after measurement, not measurement.
        err_bracket = ((bracket_wall_per_rep - bracket_dev_per_rep)
                       / max(bracket_dev_per_rep, 1e-12))

        print(f"lb={lb}: BRACKET unit cost (wall, in-loop) "
              f"{bracket_wall_per_rep*1e3:.3f} ms vs traced device "
              f"self-time {bracket_dev_per_rep*1e3:.3f} ms -> error "
              f"{err_bracket:+6.1%} (same pop+mask+bound semantics; "
              f"the attribution measurement's error bar)")
        print(f"lb={lb}: OP SHARE attributed kernel share of wall "
              f"{att_share:6.1%}, of device time {att_dev_share:6.1%} "
              f"| bound-op-only trace share {trace_share:6.1%} "
              f"| bracket-vs-op definitional ratio "
              f"{att_dev_share / trace_share if trace_share else 0:4.2f}x"
              f" (wall {elapsed:.2f}s, device {trace_total_s:.2f}s, "
              f"{iters} iters)")


if __name__ == "__main__":
    main()
