"""chip_smoke.py's phases, rehearsed at a tiny size on the CPU mesh.

The script itself refuses to run without a TPU; these tests drive its
phase functions with ta003 LB2 (tree 80,062, Cmax 1081, the
pfsp_lb2_ub1 golden) and a small chunk, so a wrong path, argument or
check fails here before it costs chip time.
"""

import json

import pytest

import chip_smoke

GOLDEN = {(3, 2): {"tree": 80062, "best": 1081}}
CHUNK, CAPACITY = 256, 1 << 16


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(chip_smoke, "CLI_CASES", ((3, 2),))
    monkeypatch.setattr(chip_smoke, "SERVICE_CASES", ((3, 2),))
    monkeypatch.setattr(chip_smoke, "DIST_CASE", (3, 2))
    monkeypatch.setattr(chip_smoke, "SEGMENT_ITERS", 4)
    return chip_smoke.Smoke(GOLDEN)


def _lines(capsys):
    return [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]


def test_golden_file_holds_the_smoke_cases():
    golden = chip_smoke.load_golden()
    for case in (*chip_smoke.CLI_CASES, *chip_smoke.SERVICE_CASES,
                 chip_smoke.DIST_CASE):
        row = golden[case]
        assert row["ub"] == "opt" and row["tree"] > 0 and row["source"]
    assert golden[(22, 2)]["best"] == 2099
    assert (golden[(14, 1)]["tree"], golden[(14, 1)]["best"]) \
        == (2573652, 1377)


def test_one_chip_phases_on_cpu(tiny, capsys):
    chip_smoke.one_chip(tiny, CHUNK, CAPACITY)
    lines = _lines(capsys)
    assert [x["phase"] for x in lines] == ["cli", "service", "distributed"]
    for x in lines:
        assert (x["tree"], x["best"]) == (80062, 1081), x
        assert x["first_s"] > 0 and x["solve_s"] > 0
        # the CPU step has no Pallas kernel, and that fails the phase
        assert x["kernels"] is False and not x["ok"]
    assert tiny.failed == ["cli", "service", "distributed"]


def test_four_chip_phase_on_cpu_mesh(tiny, monkeypatch, capsys):
    # a narrow chunk; the small tree's pools drift apart past the donor
    # threshold (min_transfer = 2 * min_seed) for the balance round to
    # move nodes
    monkeypatch.setattr(chip_smoke, "SEGMENT_ITERS", 64)
    # the CPU loop has no Pallas call; its while loop stands in for one,
    # so the check below reads each phase's own compiled SPMD loop
    monkeypatch.setattr(chip_smoke, "KERNEL_MARK", "while")
    chip_smoke.four_chips(tiny, 32, 1 << 14)
    lines = _lines(capsys)
    four, one, spread = lines
    assert four["n_devices"] == 4 and one["n_devices"] == 1
    assert four["kernels"] is True and one["kernels"] is True
    assert four["tree"] == one["tree"] == 80062
    assert spread["ok"], spread
    assert tiny.failed == []


class _Exe:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


class _Cache:
    def __init__(self, exes):
        self.exes = exes

    def executables(self):
        return self.exes


@pytest.mark.parametrize("exes,found", [
    ([_Exe("custom-call tpu_custom_call"), _Exe("tpu_custom_call")], True),
    ([], False),                                 # nothing compiled
    ([_Exe("tpu_custom_call"), None], False),    # an entry fell to jit
    ([_Exe("tpu_custom_call"), _Exe("fusion")], False),   # XLA path
])
def test_kernel_check_reads_every_compiled_loop(exes, found):
    assert chip_smoke.kernels_in_cache(_Cache(exes)) is found


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""
