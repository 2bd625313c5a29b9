"""The reduction from trace to device numbers, and the kernel work and
peak arithmetic it feeds, on a recorded chip trace slice and on hand-
built events."""

import json
import math
import pathlib

import pytest

from benchmark import kernel_work, probe, trace_reduce as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_lb2_slice.json"

LB2_NAME = (
    "%lb2_bounds_tpu.16 = s32[1,20480]{1,0:T(1,128)S(1)} custom-call("
    "f32[24,20]{1,0:T(8,128)S(1)} %fusion.679, f32[24,20]{1,0:T(8,128)S(1)} "
    "%fusion.681, bf16[20,24,20]{2,1,0:T(8,128)(2,1)S(1)} %fusion.676, "
    "f32[24,20]{1,0:T(8,128)S(1)} %convert_element_type.1748, "
    "f32[24,20]{1,0:T(8,128)S(1)} %convert_element_type.1749, "
    "f32[24,20]{1,0:T(8,128)S(1)} %convert_element_type.1750, "
    "f32[24,1]{1,0:T(8,128)S(1)} %copy.1300, f32[24,1]{1,0:T(8,128)S(1)} "
    "%copy.1301, s32[20,20480]{1,0:T(8,128)S(1)} %slice.8289, "
    "bf16[20,20480]{1,0:T(8,128)(2,1)S(1)} %fusion.674), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{f32[24,20]{1,0}, f32[24,20]{1,0}, bf16[20,24,20]{2,1,0}, "
    "f32[24,20]{1,0}, f32[24,20]{1,0}, f32[24,20]{1,0}, f32[24,1]{1,0}, "
    "f32[24,1]{1,0}, s32[20,20480]{1,0}, bf16[20,20480]{1,0}}, "
    "frontend_attributes={kernel_metadata={}}")
EXPAND_NAME = (
    "%expand_bounds_tpu.1024 = s32[1,10240]{1,0:T(1,128)S(1)} custom-call("
    "f32[20,20]{1,0:T(8,128)} %get-tuple-element.9826, "
    "s32[1,20]{1,0:T(1,128)S(1)} %bitcast.3612, "
    "s16[20,512]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.8607, "
    "s32[1,512]{1,0:T(1,128)S(1)} %get-tuple-element.8608, "
    "s32[20,512]{1,0:T(8,128)S(1)} %get-tuple-element.8609), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{f32[20,20]{1,0}, s32[1,20]{1,0}, s16[20,512]{1,0}, s32[1,512]{1,0}, "
    "s32[20,512]{1,0}}, frontend_attributes={kernel_metadata={}}")
PEAKS = {"vector_ops_per_s": 5.0e12, "hbm_bytes_per_s": 8.19e11}


def recorded():
    d = json.loads(FIXTURE.read_text())
    return [tr.Event(pl, ln, op, st, du,
                     None if sh is None else tuple((dt, tuple(dims))
                                                   for dt, dims in sh))
            for pl, ln, op, st, du, sh in d["events"]]


def ev(op, start_ms, dur_ms, plane="/device:TPU:0", shapes=None):
    line = tr.OPS_LINE if plane.startswith(tr.DEVICE_PREFIX) else "python"
    return tr.Event(plane, line, op, start_ms * 1e6, dur_ms * 1e6, shapes)


def test_kernel_names_and_shapes_parse():
    cache = {}
    op, shapes = tr.parse_name(LB2_NAME, cache)
    assert op == "lb2_bounds_tpu"
    assert shapes[0] == ("s32", (1, 20480))
    assert shapes[1] == ("f32", (24, 20))
    assert shapes[3] == ("bf16", (20, 24, 20))
    assert len(shapes) == 11           # result + 10 operands, no layouts
    op, shapes = tr.parse_name(EXPAND_NAME, cache)
    assert op == "expand_bounds_tpu" and len(shapes) == 6
    op, shapes = tr.parse_name(
        "%sort.45 = u32[1310720]{0:T(1024)S(1)} sort(u32[1310720] %x)", cache)
    assert (op, shapes) == ("sort.45 u32[1310720]", None)


def test_kernel_work_from_launch_shapes():
    _, shapes = tr.parse_name(LB2_NAME, {})
    ops, nbytes = kernel_work.lb2_sweep(shapes)
    assert ops == 20480 * (5 * 20 * 24 + 2 * 24)
    assert nbytes == (4 * 20480 + 2 * 4 * 24 * 20 + 2 * 20 * 24 * 20
                      + 3 * 4 * 24 * 20 + 2 * 4 * 24 + 4 * 20 * 20480
                      + 2 * 20 * 20480)
    _, shapes = tr.parse_name(EXPAND_NAME, {})
    ops, nbytes = kernel_work.expand_bounds(shapes)
    assert ops == 10240 * 6 * 20
    assert nbytes == (4 * 10240 + 4 * 400 + 4 * 20 + 2 * 20 * 512 + 4 * 512
                      + 4 * 20 * 512)


def test_roofline_share_takes_the_binding_roof_per_launch():
    _, shapes = tr.parse_name(EXPAND_NAME, {})
    ops, nbytes = kernel_work.expand_bounds(shapes)
    least = max(ops / PEAKS["vector_ops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    share, bound = kernel_work.roofline_share(
        {"expand_bounds_tpu": [(least * 4, shapes), (least * 4, shapes)]},
        "expand_bounds_tpu", PEAKS)
    assert share == pytest.approx(25.0)
    assert bound == "compute"      # 1.2 M ops against 104 KB
    assert kernel_work.roofline_share({}, "expand_bounds_tpu", PEAKS) is None


def test_probe_peak_arithmetic():
    shapes = (("s32", (64, 128)), ("s32", (64, 128)))
    f32 = (("f32", (64, 128)), ("f32", (64, 128)))
    s = tr.Summary(window_s=1, busy_s=1, chips=1, op_seconds={},
                   launches={}, gaps=[],
                   probe=[(0.02, shapes), (0.025, shapes),
                          (0.00002, shapes), (0.03, f32), (0.03, f32),
                          (0.03, f32)])
    # each dtype's median launch, the faster dtype: one misread launch
    # cannot move it
    want = 64 * 128 * probe.ITERS * probe.OPS_PER_ITER / 0.02
    assert probe.peak_from_trace(s) == pytest.approx(want)


@pytest.mark.parametrize("measured,used,remark", [
    (5.9e12, 5.9e12, False), (2.0e12, 5.84e12, True),
    (6.17e15, 5.84e12, True), (None, 5.84e12, True)])
def test_a_probe_reading_far_from_the_recorded_peak_is_set_aside(
        measured, used, remark):
    peak, why = probe.checked_peak(measured, 5.84e12)
    assert peak == used and bool(why) == remark


def test_recorded_slice():
    events = recorded()
    sm = tr.summarize(events)
    # busy time against a 1 us grid over the window
    w = next(e for e in events if e.op == "window")
    dev = [e for e in events if e.plane.startswith(tr.DEVICE_PREFIX)]
    grid = range(int(w.start_ns), int(w.start_ns + w.dur_ns), 1000)
    on = sum(any(e.start_ns <= t < e.start_ns + e.dur_ns for e in dev)
             for t in grid)
    assert sm.busy_s == pytest.approx(on * 1e-6, abs=2e-6)
    assert 0 < sm.idle_share < 0.2
    assert len(sm.launches["expand_bounds_tpu"]) == 128
    assert len(sm.launches["lb2_bounds_tpu"]) == 1
    # parents (while, conditional) hold other ops and are not leaves
    assert not any(k.startswith(("while", "conditional"))
                   for k in sm.op_seconds)
    assert sum(sm.op_seconds.values()) <= sm.window_s
    # the probe launch after the window is the probe's, not the window's
    assert len(sm.probe) == 1 and "bench_vpu_probe" not in sm.op_seconds
    share, _ = kernel_work.roofline_share(sm.launches, "lb2_bounds_tpu",
                                          PEAKS)
    assert 0 < share < 100


def test_busy_union_idle_share_and_gap_labels():
    events = [
        ev("window", 0, 100, plane="/host:CPU"),
        ev("a", -5, 15),                  # clipped to [0, 10]
        ev("b", 5, 10), ev("c", 40, 20),  # b overlaps a: union [0, 15]
        ev("a", 0, 50, plane="/device:TPU:1"),
        ev("d", 90, 30, plane="/device:TPU:1"),
    ]
    sm = tr.summarize(events, label_at=lambda t: f"host@{t:.3f}",
                      host_t0=1000.0)
    # chip 0: 15 + 20 busy; chip 1: 50 + 10 busy -> mean 47.5 ms
    assert sm.chips == 2
    assert sm.window_s == pytest.approx(0.1)
    assert sm.busy_s == pytest.approx(0.0475)
    assert sm.idle_share == pytest.approx(0.525)
    # longest gap: chip 0's [60, 100], mid 80 ms -> host 1000.080
    label, s = sm.gaps[0]
    assert s == pytest.approx(0.04)
    assert label == "host@1000.080"
    assert math.isclose(sum(g for _, g in sm.gaps), 0.105)


def test_leaves_of_nested_events():
    outer, inner1, inner2, after = (ev("while", 0, 10), ev("x", 1, 2),
                                    ev("y", 4, 6), ev("z", 11, 1))
    assert tr.leaves([outer, inner1, inner2, after]) == [inner1, inner2,
                                                         after]


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        tr.summarize([ev("a", 0, 1)])
    with pytest.raises(ValueError):
        tr.summarize([ev("window", 0, 1, plane="/host:CPU")])


def test_probe_reads_only_its_own_kernel():
    shapes = (("s32", (64, 128)), ("s32", (64, 128)))
    other = (("s32", (1, 1280)), ("f32", (24, 10)))
    events = [ev("window", 0, 10, plane="/host:CPU"), ev("a", 0, 10),
              ev("probe", 20, 100, plane="/host:CPU"),
              ev(tr.PROBE_OP, 30, 20, shapes=shapes),
              # a served request's kernel still running beside the probe
              ev("lb2_bounds_tpu", 55, 0.01, shapes=other)]
    sm = tr.summarize(events)
    assert sm.probe == [(0.02, shapes)]
    assert probe.peak_from_trace(sm) == pytest.approx(
        64 * 128 * probe.ITERS * probe.OPS_PER_ITER / 0.02)
