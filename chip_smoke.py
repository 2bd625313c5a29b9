#!/usr/bin/env python3
"""Bring-up smoke of the PFSP search and its solver service on the chip.

Drives the system's main paths once, in one process, at the size its
users run: the reference's single-GPU 20x20 Taillard table (ta021-ta030)
at UB=opt with the tuned 20x20 chunk (65536) over an HBM-resident pool.

1 chip (no arguments):

- CLI path: ``python -m tpu_tree_search pfsp`` solves ta022 (LB2) and
  ta030 (LB1) to exhaustion;
- service path: an in-process ``SearchServer`` with one submesh answers
  ta014 LB1, ta022 LB2 and ta029 LB2, each ending DONE;
- one-chip distributed path: ``distributed.search(n_devices=1)`` on
  ta022 LB2;
- kernels: after each solve, the program that phase ran must contain a
  Pallas ``tpu_custom_call`` (a step that took the XLA path would still
  count the right tree). For the CLI that is the single-device search
  loop lowered at the solve's shapes; for the service and the
  distributed phases, every SPMD loop their executor cache compiled.

``--chips 4`` runs only ``distributed.search(n_devices=4)`` on ta022 LB2
and, to compare against, the same search on device 0 alone, at chunk
8192 per chip, each with the same kernel check.

Every solve runs twice; the first call includes compilation (``first_s``)
and the second is warm (``solve_s``). Each explored tree and optimum must
equal tests/golden/pfsp_20x20_full.jsonl. One JSON line per phase; the
last line is ``{"ok": true, "device": {...}}`` and is printed only when
every phase passed. Exits nonzero when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "tests", "golden", "pfsp_20x20_full.jsonl")
CHUNK = 65536          # tune/defaults.BENCH_CHUNK_DEFAULT, the 20x20 row
CAPACITY = 1 << 22     # pool rows per device: above the chunk*jobs margin
CLI_CASES = ((22, 2), (30, 1))
SERVICE_CASES = ((14, 1), (22, 2), (29, 2))
DIST_CASE = (22, 2)
SEGMENT_ITERS = 64     # the 4-chip run's heartbeat period
# the 4-chip comparison checks the mesh, the collectives and the state
# spread, none of which depends on the chunk; at 65536 each of its two
# LB2 programs compiles cold for ~2-3 min of four chips' time
FOUR_CHIP_CHUNK = 8192


def load_golden(path: str = GOLDEN) -> dict:
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return {(r["inst"], r["lb"]): r for r in rows}


def twice(fn):
    """Run `fn` twice: (second result, first-call s, warm-call s)."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, first, time.perf_counter() - t0


def solve_cli(inst: int, lb: int, chunk: int, capacity: int) -> dict:
    """One ``pfsp`` CLI solve at UB=opt; parses the reference-format
    report. A truncated run prints no "Optimal makespan" line."""
    from tpu_tree_search import cli
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.main(["pfsp", "-i", str(inst), "-l", str(lb), "-u", "1",
                       "--chunk", str(chunk), "--capacity", str(capacity)])
    text = out.getvalue()
    tree = re.search(r"Size of the explored tree: (\d+)", text)
    best = re.search(r"Optimal makespan: (\d+)", text)
    if rc != 0 or tree is None or best is None:
        raise RuntimeError(f"pfsp CLI rc={rc}: {text[-400:]!r}")
    return {"tree": int(tree[1]), "best": int(best[1])}


def solve_service(server, inst: int, lb: int, chunk: int,
                  capacity: int) -> dict:
    from tpu_tree_search.problems import taillard
    from tpu_tree_search.service.server import SearchRequest
    rec = server.result(server.submit(SearchRequest(
        p_times=taillard.processing_times(inst), lb_kind=lb,
        init_ub=taillard.optimal_makespan(inst), chunk=chunk,
        capacity=capacity)), timeout=300)
    if rec.state != "DONE":
        raise RuntimeError(f"request ended {rec.state}: {rec.error}")
    return {"tree": int(rec.result.explored_tree),
            "best": int(rec.result.best)}


def solve_distributed(inst: int, lb: int, n_devices: int, chunk: int,
                      capacity: int, **kw) -> dict:
    """`loop_cache` (an ExecutorCache) keeps the compiled loop between
    calls, as the service does; without it each call traces again and
    reloads the executable."""
    from tpu_tree_search.engine import distributed
    from tpu_tree_search.problems import taillard
    res = distributed.search(
        taillard.processing_times(inst), lb_kind=lb,
        init_ub=taillard.optimal_makespan(inst), n_devices=n_devices,
        chunk=chunk, capacity=capacity, **kw)
    if not res.complete:
        raise RuntimeError("pools did not drain")
    pd = res.per_device
    return {"tree": int(res.explored_tree), "best": int(res.best),
            "sent": int(pd["sent"].sum()), "recv": int(pd["recv"].sum()),
            "iters": [int(x) for x in pd["iters"]]}


KERNEL_MARK = "tpu_custom_call"     # a Pallas call in compiled HLO


def kernels_in_cache(cache) -> bool:
    """Whether every loop an ExecutorCache compiled (the service's, or
    the one handed to distributed.search) contains a Pallas kernel. An
    entry that fell back to plain jit has no executable to read, and
    fails the check."""
    exes = cache.executables()
    return bool(exes) and all(x is not None and KERNEL_MARK in x.as_text()
                              for x in exes)


def kernels_in_step(inst: int, lb: int, chunk: int, capacity: int) -> bool:
    """Whether the compiled single-device search loop, at the shapes the
    CLI solve runs, contains a Pallas kernel. Served from the persistent
    compile cache when the CLI solve compiled the same program."""
    import jax.numpy as jnp

    from tpu_tree_search.engine import device
    from tpu_tree_search.ops import batched
    from tpu_tree_search.problems import taillard
    p = taillard.processing_times(inst)
    state = device.init_state(p.shape[1], capacity,
                              taillard.optimal_makespan(inst), p_times=p)
    ceiling = jnp.iinfo(state.iters.dtype).max
    lowered = device._run.lower(
        batched.make_tables(p), state, lb, chunk,
        jnp.asarray(ceiling, dtype=state.iters.dtype),
        jnp.asarray(1, dtype=jnp.int32), tile=1024)
    return KERNEL_MARK in lowered.compile().as_text()


class Smoke:
    """Runs phases, prints one JSON line each, remembers failures."""

    def __init__(self, golden: dict):
        self.golden = golden
        self.failed: list[str] = []

    def phase(self, name: str, inst: int, lb: int, fn, kernels=None,
              **extra) -> dict:
        """Solve twice and check tree and optimum against the golden;
        `kernels` (optional) then says whether the step that ran has
        its Pallas kernels, and a step without them fails the phase."""
        line = {"phase": name, "inst": inst, "lb": lb}
        try:
            out, first, warm = twice(fn)
            want = self.golden[(inst, lb)]
            line.update(out, first_s=round(first, 3), solve_s=round(warm, 3),
                        compile_s=round(first - warm, 3), **extra)
            line["ok"] = (out["tree"] == want["tree"]
                          and out["best"] == want["best"])
            if not line["ok"]:
                line["want"] = {"tree": want["tree"], "best": want["best"]}
            if kernels is not None:
                line["kernels"] = kernels()
                line["ok"] = line["ok"] and line["kernels"]
        except Exception as e:  # noqa: BLE001 — report, run the rest
            line.update(ok=False, error=f"{type(e).__name__}: {e}")
        self.record(line)
        return line

    def record(self, line: dict) -> None:
        if not line["ok"]:
            self.failed.append(line["phase"])
        print(json.dumps(line), flush=True)


def one_chip(smoke: Smoke, chunk: int, capacity: int) -> None:
    from tpu_tree_search.service.executors import ExecutorCache
    from tpu_tree_search.service.server import SearchServer

    for inst, lb in CLI_CASES:
        smoke.phase("cli", inst, lb,
                    lambda: solve_cli(inst, lb, chunk, capacity),
                    kernels=lambda: kernels_in_step(inst, lb, chunk,
                                                    capacity))
    with tempfile.TemporaryDirectory(prefix="tts_smoke_") as workdir:
        server = SearchServer(n_submeshes=1, workdir=workdir)
        try:
            for inst, lb in SERVICE_CASES:
                smoke.phase("service", inst, lb,
                            lambda: solve_service(server, inst, lb, chunk,
                                                  capacity),
                            kernels=lambda: kernels_in_cache(server.cache))
        finally:
            server.close()
    inst, lb = DIST_CASE
    loops = ExecutorCache()
    smoke.phase("distributed", inst, lb,
                lambda: solve_distributed(inst, lb, 1, chunk, capacity,
                                          loop_cache=loops),
                kernels=lambda: kernels_in_cache(loops), n_devices=1)


def four_chips(smoke: Smoke, chunk: int, capacity: int) -> None:
    """ta022 LB2 over four chips, then on device 0 alone. The 4-chip run
    must exchange nodes, and every device must iterate and hold state:
    a state that sat on the first chip alone would still count right."""
    import jax

    from tpu_tree_search.service.executors import ExecutorCache
    from tpu_tree_search.utils import device_info

    inst, lb = DIST_CASE
    held: dict[int, int] = {}
    loops, ones = ExecutorCache(), ExecutorCache()

    def heartbeat(_report):
        for rec in device_info.memory_snapshot():
            held[rec["id"]] = max(held.get(rec["id"], 0),
                                  rec.get("bytes_in_use", 0))

    four = smoke.phase(
        "distributed", inst, lb,
        lambda: solve_distributed(inst, lb, 4, chunk, capacity,
                                  segment_iters=SEGMENT_ITERS,
                                  heartbeat=heartbeat, loop_cache=loops),
        kernels=lambda: kernels_in_cache(loops), n_devices=4)
    one = smoke.phase(
        "distributed", inst, lb,
        lambda: solve_distributed(inst, lb, 1, chunk, capacity,
                                  loop_cache=ones),
        kernels=lambda: kernels_in_cache(ones), n_devices=1)
    mesh_ids = [d.id for d in jax.devices()[:4]]
    held = {i: held.get(i, 0) for i in mesh_ids}
    spread = {
        "phase": "spread", "bytes_in_use": held,
        "sent": four.get("sent"), "recv": four.get("recv"),
        "iters": four.get("iters"),
    }
    spread["ok"] = bool(
        four.get("ok") and one.get("ok")
        and four["tree"] == one["tree"]
        and four["sent"] > 0 and four["recv"] > 0
        and len(four["iters"]) == 4 and min(four["iters"]) > 0
        and min(held.values()) > 0)
    smoke.record(spread)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip distributed comparison")
    args = ap.parse_args(argv)
    started = time.time()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1

    from tpu_tree_search.utils import compile_cache
    cache_dir = compile_cache.enable()
    print(json.dumps({"phase": "setup", "compile_cache": cache_dir,
                      "ok": True}), flush=True)

    smoke = Smoke(load_golden())
    if args.chips == 4:
        four_chips(smoke, FOUR_CHIP_CHUNK, CAPACITY)
    else:
        one_chip(smoke, CHUNK, CAPACITY)

    stats = devices[0].memory_stats() or {}
    print(json.dumps({"phase": "memory",
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "bytes_limit": stats.get("bytes_limit"),
                      # wall since start, and the clock at this line:
                      # the time to process exit is runtime teardown
                      "wall_s": round(time.time() - started, 3),
                      "unix": round(time.time(), 3)}), flush=True)
    if smoke.failed:
        print(f"chip_smoke: failed phases: {smoke.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
