"""Perf regression sentry over the bench-row trajectory.

Every round the driver runs ``bench.py`` and a multichip dry-run and
archives the result as ``BENCH_r0N.json`` / ``MULTICHIP_r0N.json``.
Until this tool, NOBODY read them: every BENCH row to date was a
silently-ignored ``rc=1`` backend failure. The sentry makes the
trajectory a gate:

- **rc failures are loud**: any row with ``rc != 0`` (or a multichip
  row with ``ok: false``) is a FAIL verdict — a benchmark that did not
  run is a regression of the *measurement*, the worst kind to ignore;
- **rate regressions are caught**: each metric in the latest round is
  compared against the best prior value of the SAME metric across
  earlier rounds (plus any ``published`` number in BASELINE.json),
  with a per-metric relative threshold (default 10%; LB2's window is
  shorter and noisier, so it gets 15%);
- **platforms don't mix**: a row is never rate-compared against
  history from another platform (bench.py stamps ``platform`` on every
  row) — a CPU rate "regressing" from a TPU rate is not a finding —
  but its rc still gates, platform recorded in the report.

Inputs it understands: the driver's wrapper objects
(``{"rc": ..., "tail": ..., "parsed": ...}`` — metric rows are
re-extracted from the tail, the wrapper's single ``parsed`` row drops
the LB2 line), multichip wrappers (``{"n_devices", "rc", "ok",
"skipped", "tail"}``), and raw ``bench.py`` stdout (one JSON row per
line — what the CI leg pipes in).

    python tools/perf_sentry.py                       # latest round in .
    python tools/perf_sentry.py --report-only bench_row.jsonl
    python tools/perf_sentry.py --threshold 0.2 --out sentry.md

Exit status: nonzero when any verdict is FAIL (rc failure, not-ok
multichip, or regression beyond threshold) — unless ``--report-only``,
which always exits 0 and is how CI runs it while the trajectory is
still all-CPU (the markdown lands as a build artifact either way).
"""

import argparse
import glob
import json
import os
import re
import sys

# per-metric relative regression thresholds; _default backstops the rest
THRESHOLDS = {
    "_default": 0.10,
    # LB2 benches on a half-length window (bench.py) — noisier
    "lb2": 0.15,
}

# metric-name substrings whose values regress UPWARD (latencies, idle
# gaps, cold-start executor-ready time, ramp/drain phase seconds and
# the ramp/drain solve wall): the reference best is the MINIMUM prior
# value and a value above it by more than the threshold FAILs.
# Everything else is a rate (higher is better). First matching
# substring wins.
LOWER_IS_BETTER = ("segment_gap", "cold_start", "_seconds", "latency",
                   "_ramp_s", "_drain_s", "_wall_s", "hbm_bytes")

PASS, FAIL, NEW, SKIP = "PASS", "FAIL", "NEW", "SKIP"


def threshold_for(metric: str, overrides: dict) -> float:
    for pat, th in {**THRESHOLDS, **overrides}.items():
        if pat != "_default" and pat in metric:
            return th
    return overrides.get("_default", THRESHOLDS["_default"])


def direction_for(metric: str) -> int:
    """+1 = higher is better (rates, the default); -1 = lower is
    better (the segment-gap / latency family)."""
    return -1 if any(s in metric for s in LOWER_IS_BETTER) else 1


def row_mode(row: dict):
    """The comparison-mode a metric row was measured under, as a
    (channel, value) pair — TTS_OVERLAP for the segment-gap family,
    cache_mode (cold|warm) for the cold-start family, TTS_LADDER for
    the ramp/drain family, and the bench's tuned-chunk mode — or None.
    Rows of different modes are never judged against each other: a
    cold trace+compile latency 'regressing' from a warm disk-replay
    reference is not a finding, it is the cache doing its job; a
    fixed-chunk ramp judged against a laddered ~0 one (or a tuned-
    chunk rate against fixed-chunk history) is the same non-finding.
    The bench stamps "tuned" ONLY on tuned rows, so untuned throughput
    rows stay modeless and keep comparing against their history."""
    if row.get("overlap") is not None:
        return ("overlap", row["overlap"])
    if row.get("cache_mode") is not None:
        return ("cache", row["cache_mode"])
    if row.get("ladder") is not None:
        return ("ladder", row["ladder"])
    if row.get("megabatch") is not None:
        # the serve-rps family (HIGHER is better, the rate default):
        # a batched requests/s figure must never rate-judge against
        # solo serving history — different execution modes entirely
        return ("megabatch", row["megabatch"])
    if row.get("portfolio") is not None:
        # the portfolio-speedup family (service/portfolio): a K=3
        # race ratio must never be judged against a differently-sized
        # race's history — cross-width rows SKIP, never FAIL
        return ("portfolio", row["portfolio"])
    if row.get("tuned") is not None:
        return ("tuned", row["tuned"])
    return None


def _round_of(path: str) -> int:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def _json_lines(text: str) -> list[dict]:
    """Metric rows embedded in free text (bench.py stdout / wrapper
    tails): any line that parses as a JSON object with a 'metric'."""
    rows = []
    for ln in text.splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            obj = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            rows.append(obj)
    return rows


def load_source(path: str) -> dict:
    """Normalize one input file to
    {source, rc, ok, skipped, rows: [metric rows]}."""
    with open(path) as f:
        text = f.read()
    out = {"source": os.path.basename(path), "rc": 0, "ok": True,
           "skipped": False, "rows": []}
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict) and ("rc" in obj or "tail" in obj):
        # driver wrapper (BENCH_rNN / MULTICHIP_rNN)
        out["rc"] = int(obj.get("rc", 0))
        out["ok"] = bool(obj.get("ok", True))
        out["skipped"] = bool(obj.get("skipped", False))
        rows = _json_lines(obj.get("tail") or "")
        if not rows and isinstance(obj.get("parsed"), dict):
            rows = [obj["parsed"]]
        out["rows"] = rows
    elif isinstance(obj, dict) and "metric" in obj:
        out["rows"] = [obj]
    else:
        # raw bench stdout: JSON rows one per line
        out["rows"] = _json_lines(text)
    return out


def load_history(directory: str, before_round: int,
                 baseline_path: str | None,
                 exclude: set | None = None) -> dict:
    """Best prior value per (metric, mode): earlier BENCH_r*.json
    rounds in `directory` plus BASELINE.json's published numbers.
    Keying by mode keeps each measurement family's OWN reference —
    a cold-cache executor-ready row regresses against the best prior
    COLD value, never against the warm disk-replay minimum (which
    would otherwise permanently own a metric-keyed slot and turn
    every later cold row into a SKIP). `exclude` holds the abspaths of
    the files under judgment: explicit-file mode has no round cutoff,
    and a row that can find ITSELF in its mode slot would always PASS
    at +0.0% instead of being judged against real priors."""
    best: dict = {}
    exclude = exclude or set()

    def offer(metric, value, src, platform=None, mode=None):
        if value is None:
            return
        key = (metric, mode)
        better = (value > best[key][0] if direction_for(metric) > 0
                  else value < best[key][0]) \
            if key in best else True
        if better:
            best[key] = (float(value), src, platform, mode)

    for path in sorted(glob.glob(os.path.join(directory,
                                              "BENCH_*.json"))):
        rnd = _round_of(path)
        if before_round >= 0 and rnd >= before_round:
            continue
        if os.path.abspath(path) in exclude:
            continue
        src = load_source(path)
        if src["rc"] != 0:
            continue
        for row in src["rows"]:
            offer(row.get("metric"), row.get("value"), src["source"],
                  row.get("platform"), row_mode(row))
    if baseline_path and os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                published = json.load(f).get("published") or {}
            for metric, value in published.items():
                if isinstance(value, (int, float)):
                    offer(metric, value,
                          os.path.basename(baseline_path))
        except (OSError, json.JSONDecodeError, AttributeError):
            pass
    return best


def judge(sources: list[dict], history: dict,
          overrides: dict) -> list[dict]:
    """One verdict dict per finding, FAILs first."""
    verdicts = []
    for src in sources:
        name = src["source"]
        if src["skipped"]:
            verdicts.append({"verdict": SKIP, "source": name,
                             "detail": "round marked skipped"})
            continue
        if src["rc"] != 0:
            verdicts.append({
                "verdict": FAIL, "source": name,
                "detail": f"rc={src['rc']} — the benchmark itself "
                          "failed to run (previously ignored "
                          "silently)"})
            continue
        if not src["ok"]:
            verdicts.append({"verdict": FAIL, "source": name,
                             "detail": "ok=false"})
            continue
        if not src["rows"]:
            verdicts.append({"verdict": PASS, "source": name,
                             "detail": "rc=0, no metric rows "
                                       "(smoke-only round)"})
            continue
        for row in src["rows"]:
            metric = row.get("metric", "?")
            value = row.get("value")
            v = {"source": name, "metric": metric, "value": value,
                 "platform": row.get("platform")}
            # rows carry their measurement mode precisely so an
            # overlap-off gap is never judged against an overlap-on
            # ~0.0 reference, and a cold-cache executor-ready latency
            # never against a warm disk-replay one: the same-mode
            # reference is the bar; when only an OTHER mode has
            # history, the row is SKIPped (not FAILed, not NEW — the
            # cross-mode value is stated for context)
            mode = row_mode(row)
            ref = history.get((metric, mode))
            if ref is None and mode is not None:
                ref = next((history[k] for k in sorted(
                    history, key=repr) if k[0] == metric), None)
            refplat = ref[2] if ref is not None else None
            refmode = (ref[3] if ref is not None and len(ref) > 3
                       else None)
            plat_mismatch = (ref is not None and refplat
                             and row.get("platform")
                             and refplat != row["platform"])
            # a MODELESS reference (a BASELINE.json number) counts as
            # a mismatch for a mode-carrying row too: the baseline's
            # measurement mode is unknown, and rate-judging a cold
            # compile against a possibly-warm published number is the
            # exact false-FAIL this machinery exists to prevent
            mode_mismatch = (ref is not None and mode is not None
                             and refmode != mode)
            if ref is not None and (plat_mismatch or mode_mismatch):
                # a different-platform (or different-mode) value
                # compared against the reference best would always
                # "regress" — a CPU rate is not a TPU finding, a sync
                # gap not a pipelined one, a cold compile not a warm
                # replay
                ref_mode_desc = (repr(refmode[1]) if refmode
                                 else "unknown (modeless baseline)")
                why = (f"{mode[0]} mode {mode[1]!r} vs "
                       f"reference mode {ref_mode_desc}"
                       if mode_mismatch
                       else f"platform {row.get('platform')!r} vs "
                       f"reference platform {refplat!r}")
                v.update(verdict=SKIP,
                         detail=f"{why}; rate not compared "
                                f"(reference {ref[0]:.4g})")
            elif ref is None:
                v.update(verdict=NEW,
                         detail="no prior value for this metric")
            else:
                refv, refsrc = ref[0], ref[1]
                th = threshold_for(metric, overrides)
                direction = direction_for(metric)
                # a 0.0 reference is REAL for the lower-is-better
                # family (a perfect-overlap gap round); floor the
                # denominator so a later nonzero gap still reads as a
                # huge upward move instead of silently passing
                delta = (value - refv) / max(refv, 1e-9)
                v.update(reference=refv, reference_source=refsrc,
                         delta=delta, threshold=th,
                         direction=("lower" if direction < 0
                                    else "higher"))
                # regression = the metric moved AGAINST its direction
                # by more than the threshold: rates fail below -th,
                # lower-is-better metrics (segment_gap_s) fail above +th
                regressed = (delta < -th if direction > 0
                             else delta > th)
                word = "best" if direction > 0 else "lowest"
                if regressed:
                    sign = "-" if direction > 0 else "+"
                    v.update(verdict=FAIL,
                             detail=f"{delta:+.1%} vs {word} prior "
                                    f"{refv:.4g} ({refsrc}); "
                                    f"threshold {sign}{th:.0%}")
                else:
                    v.update(verdict=PASS,
                             detail=f"{delta:+.1%} vs {word} prior "
                                    f"{refv:.4g} ({refsrc})")
            verdicts.append(v)
    order = {FAIL: 0, NEW: 1, SKIP: 2, PASS: 3}
    verdicts.sort(key=lambda v: (order.get(v["verdict"], 9),
                                 v.get("metric", "")))
    return verdicts


def render_json(verdicts: list[dict], latest_round: int) -> dict:
    """Machine-readable verdict (written next to the markdown report):
    the schema the CI leg uploads and the health layer's `perf` rule
    ingests (obs/health.py, TTS_HEALTH_PERF_JSON)."""
    n_fail = sum(v["verdict"] == FAIL for v in verdicts)
    return {
        "schema": 1,
        "round": latest_round if latest_round >= 0 else None,
        "verdict": FAIL if n_fail else PASS,
        "n_findings": len(verdicts),
        "n_fail": n_fail,
        "reasons": [f"{v.get('source')}: {v.get('metric', '-')} "
                    f"{v['detail']}"
                    for v in verdicts if v["verdict"] == FAIL],
        "metrics": [
            {k: v.get(k) for k in
             ("verdict", "source", "metric", "value", "reference",
              "reference_source", "delta", "threshold", "direction",
              "platform", "detail")}
            for v in verdicts],
    }


def render_markdown(verdicts: list[dict]) -> str:
    n_fail = sum(v["verdict"] == FAIL for v in verdicts)
    lines = ["# Perf sentry", "",
             ("**FAIL** — " if n_fail else "**PASS** — ")
             + f"{len(verdicts)} finding(s), {n_fail} failing", "",
             "| verdict | source | metric | value | reference | Δ | "
             "detail |",
             "|---|---|---|---|---|---|---|"]
    for v in verdicts:
        delta = (f"{v['delta']:+.1%}" if v.get("delta") is not None
                 else "-")
        ref = (f"{v['reference']:.4g}" if v.get("reference") is not None
               else "-")
        val = (f"{v['value']:.4g}" if isinstance(v.get("value"),
                                                 (int, float)) else "-")
        lines.append(
            f"| {v['verdict']} | {v['source']} "
            f"| {v.get('metric', '-')} | {val} | {ref} | {delta} "
            f"| {v['detail']} |")
    lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail loudly on rc!=0 bench rows and >threshold "
                    "rate regressions in the latest BENCH_*/MULTICHIP_* "
                    "round (or explicit row files)")
    ap.add_argument("files", nargs="*",
                    help="row files to judge (driver wrappers or raw "
                         "bench.py stdout); default: the latest "
                         "BENCH_r*/MULTICHIP_r* round in --dir")
    ap.add_argument("--dir", default=".",
                    help="where the round archives live (history is "
                         "always read from here)")
    ap.add_argument("--baseline", default=None,
                    help="BASELINE.json path (its `published` numbers "
                         "join the reference set); default: "
                         "<dir>/BASELINE.json")
    ap.add_argument("--threshold", type=float, default=None,
                    help="override the default relative regression "
                         "threshold (e.g. 0.2 = fail below -20%%)")
    ap.add_argument("--metric-threshold", action="append", default=[],
                    metavar="SUBSTR=FRACTION",
                    help="per-metric threshold override, repeatable "
                         "(e.g. lb2=0.25)")
    ap.add_argument("--report-only", action="store_true",
                    help="always exit 0 (CI mode while the trajectory "
                         "is CPU-only); the report still says FAIL")
    ap.add_argument("--out", default=None,
                    help="also write the markdown summary here")
    ap.add_argument("--json", default=None, dest="json_out",
                    help="also write the machine-readable verdict here "
                         "(schema: round, per-metric deltas, verdict, "
                         "reasons — the health layer's `perf` rule "
                         "ingests it via TTS_HEALTH_PERF_JSON)")
    args = ap.parse_args(argv)

    overrides = {}
    if args.threshold is not None:
        overrides["_default"] = args.threshold
    for spec in args.metric_threshold:
        key, _, val = spec.partition("=")
        overrides[key] = float(val)

    if args.files:
        paths = args.files
        latest_round = -1
    else:
        rounds = [p for p in
                  glob.glob(os.path.join(args.dir, "BENCH_*.json"))
                  + glob.glob(os.path.join(args.dir,
                                           "MULTICHIP_*.json"))
                  if _round_of(p) >= 0]
        if not rounds:
            print(f"error: no BENCH_r*/MULTICHIP_r* rounds in "
                  f"{args.dir} and no files given", file=sys.stderr)
            return 2
        latest_round = max(_round_of(p) for p in rounds)
        paths = sorted(p for p in rounds
                       if _round_of(p) == latest_round)

    sources = [load_source(p) for p in paths]
    baseline = args.baseline or os.path.join(args.dir, "BASELINE.json")
    history = load_history(args.dir, latest_round, baseline,
                           exclude={os.path.abspath(p) for p in paths})
    verdicts = judge(sources, history, overrides)

    md = render_markdown(verdicts)
    print(md)
    if args.out:
        with open(args.out, "w") as f:
            f.write(md)
        print(f"# wrote {args.out}", file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(render_json(verdicts, latest_round), f, indent=1)
            f.write("\n")
        print(f"# wrote {args.json_out}", file=sys.stderr)

    n_fail = sum(v["verdict"] == FAIL for v in verdicts)
    if n_fail and not args.report_only:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
