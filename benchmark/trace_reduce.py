"""From a profiler trace to device numbers.

The JAX profiler writes an `.xplane.pb`. On a TPU each chip is a plane
`/device:TPU:<n>` whose line `XLA Ops` holds one event per executed
HLO op, nested: a `while` op's event spans its whole loop and holds
the events of the ops inside it. An event's name is the op's HLO text,
`%<op>.<k> = <result shape> <opcode>(<operand shapes> ...)`.

- busy: the union of the intervals of all op events on a chip, inside
  the traced window, averaged over the chips; idle share is 1 minus
  busy over the window;
- op time: the summed durations of the leaf events (those holding no
  other event) by op name, the `.<k>` suffix dropped: a Pallas kernel's
  op is named after the function that launched it;
- kernel launches: for each Pallas call (`tpu_custom_call`), its
  duration and its result and operand shapes, which
  `kernel_work.py` turns into operations and bytes;
- idle gaps: the stretches of the window with no op on the chip, each
  labelled with what the host was doing then.

The traced window is the host span named `window` that the harness
opens and closes; host spans are on the same clock as device events.
"""

from __future__ import annotations

import dataclasses
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPANS = ("window", "probe", "solve", "submit")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
PROBE_OP = "bench_vpu_probe"      # benchmark/probe.py's kernel

_SHAPE = re.compile(r"\b(pred|s8|s16|s32|s64|u8|u16|u32|u64|bf16|f16|f32|"
                    r"f64)\[([0-9,]*)\]")
_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?:\s|=|$)")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    op: str          # op name without its `.<k>` suffix
    start_ns: float
    dur_ns: float
    shapes: tuple | None = None   # Pallas calls: ((dtype, dims), ...)


def op_name(text: str) -> str:
    """`%expand_bounds_tpu.576 = s32[...] custom-call(...)` ->
    `expand_bounds_tpu`."""
    m = _NAME.match(text.strip())
    return m.group(1) if m else text.split(" ", 1)[0]


def shapes_of(text: str) -> tuple:
    """Result shape first, then operand shapes, as (dtype, dims)."""
    head = text.split(", custom_call_target", 1)[0]
    return tuple((dt, tuple(int(d) for d in dims.split(",") if d))
                 for dt, dims in _SHAPE.findall(head))


def parse_name(text: str, cache: dict) -> tuple:
    """(op key, shapes). A Pallas call's key is its function's name,
    shared by all its launches; any other op's key is its HLO name and
    result shape (`sort.45 u32[1310720]`), one op of the program."""
    got = cache.get(text)
    if got is None:
        if KERNEL_MARK in text:
            got = (op_name(text), shapes_of(text))
        else:
            name = text.strip().split(" ", 1)[0].lstrip("%")
            m = _SHAPE.search(text)
            got = (f"{name} {m.group(0)}" if m else name, None)
        cache[text] = got
    return got


def load_xplane(path: str) -> list[Event]:
    """The events the reduction reads: device ops and the harness's
    host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: list[Event] = []
    cache: dict = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    op, shapes = parse_name(e.name, cache)
                    out.append(Event(plane.name, line.name, op,
                                     float(e.start_ns),
                                     float(e.duration_ns), shapes))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out.append(Event(plane.name, line.name, e.name,
                                         float(e.start_ns),
                                         float(e.duration_ns)))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaves(events: list[Event]) -> list[Event]:
    """Events of one line that hold no other event of that line."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, e in enumerate(evs):
        end = e.start_ns + e.dur_ns
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start_ns >= end or (
                nxt.dur_ns == 0 and nxt.start_ns == end):
            out.append(e)
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over chips
    chips: int
    op_seconds: dict              # leaf op name -> seconds (all chips)
    launches: dict                # kernel op -> [(seconds, shapes)]
    gaps: list                    # [(label, seconds)], longest first
    probe: list                   # [(seconds, shapes)] of the probe span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int) -> list:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def top_gaps(self, n: int) -> list:
        return [[label, s] for label, s in self.gaps[:n]]


def summarize(events: list[Event], label_at=lambda t: "none",
              host_t0: float = 0.0) -> Summary:
    """Reduce the events of one traced run. `host_t0` is the host's
    monotonic clock at the `window` span's start, which maps a gap's
    trace time to the host time `label_at` takes."""
    spans = {name: [e for e in events if e.plane.startswith("/host")
                    and e.op == name] for name in ("window", "probe")}
    if not spans["window"]:
        raise ValueError("trace has no `window` span")
    w = spans["window"][0]
    lo, hi = w.start_ns, w.start_ns + w.dur_ns
    by_chip: dict[str, list[Event]] = {}
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX):
            by_chip.setdefault(e.plane, []).append(e)
    if not by_chip:
        raise ValueError("trace has no device ops")

    busy_total = 0.0
    gaps: list[tuple[str, float]] = []
    op_s: dict[str, float] = {}
    launches: dict[str, list] = {}
    probe: list = []
    p_lo = p_hi = None
    if spans["probe"]:
        p = spans["probe"][0]
        p_lo, p_hi = p.start_ns, p.start_ns + p.dur_ns
    for plane, evs in sorted(by_chip.items()):
        inside = [e for e in evs if e.start_ns < hi
                  and e.start_ns + e.dur_ns > lo]
        busy = clip(union((e.start_ns, e.start_ns + e.dur_ns)
                          for e in inside), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = host_t0 + ((s + e) / 2 - lo) / 1e9
                gaps.append((label_at(mid), (e - s) / 1e9))
        for e in leaves(inside):
            dur = (min(e.start_ns + e.dur_ns, hi)
                   - max(e.start_ns, lo)) / 1e9
            op_s[e.op] = op_s.get(e.op, 0.0) + dur
            if e.shapes is not None:
                launches.setdefault(e.op, []).append((e.dur_ns / 1e9,
                                                      e.shapes))
        if p_lo is not None:
            # the program's own kernels may still run beside the probe
            probe += [(e.dur_ns / 1e9, e.shapes) for e in evs
                      if e.op == PROBE_OP and p_lo <= e.start_ns <= p_hi]
    gaps.sort(key=lambda g: -g[1])
    chips = len(by_chip)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_total / chips / 1e9,
                   chips=chips, op_seconds=op_s,
                   launches=launches, gaps=gaps, probe=probe)
