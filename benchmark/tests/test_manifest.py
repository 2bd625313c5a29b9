"""BENCHMARK.json against the rules its reader holds it to, and every
file it names by name."""

import json
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MAN = manifest.manifest()


def line_text(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MAN)) <= 64 * 1024
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert 1 <= len(MAN["command"]) <= 32
    assert all(line_text(w) for w in MAN["command"])
    for w in MAN["command"][1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in MAN["paths"])
    assert isinstance(MAN["run_seconds"], int)
    assert 1 <= MAN["run_seconds"] <= 51
    cells = 24
    budget = ((2 + 14 * cells) * (MAN["run_seconds"] + 60)
              + cells * 2 * 90 + 1200)
    assert budget <= 43200


def test_configs():
    names = [c["name"] for c in MAN["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_text(c["source"]) and line_text(c["why"])
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        cfg = manifest.config(MAN, c["name"])
        assert cfg["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert cfg["reduced"] == c["reduced"]
        # oracle rows for every row and bound the configuration runs
        oracle = manifest.oracle(c["name"])
        for lb in cfg["bounds"]:
            for i in cfg["rows"]:
                if (i, lb) in oracle:
                    assert oracle[(i, lb)]["tree"] >= 0


def test_workloads():
    names = [w["name"] for w in MAN["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in MAN["workloads"]}
    assert len(pairs) == len(names)
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(names) // 2)
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_text(w["why"])
        tr = manifest.traffic(w["traffic"])
        manifest.loop(tr["kind"])             # the loop exists
        oracle = manifest.oracle(w["config"])
        for i in tr["rows"]:
            assert (i, tr["lb"]) in oracle


def test_metrics():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    cells = {w["name"] for w in MAN["workloads"]}
    names = list(e2e) + [m["name"] for m in MAN["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert 1 <= len(e2e) <= 16 and 1 <= len(MAN["per_layer"]) <= 128

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and line_text(m["layer"])
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(moved, cell)
        manifest.reader(m["name"])            # the reader exists
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer one
    for cell in cells:
        assert sum(reports(m, cell) for m in MAN["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in MAN["per_layer"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert layers == {"step", "search loop", "kernels", "device",
                      "service"}


@pytest.mark.parametrize("name", [m["name"] for m in MAN["per_layer"]])
def test_readers_find_nothing_in_an_empty_run(name):
    class Empty:
        trace = None
        items: list = []
        counters: dict = {}
        peaks: dict = {}
        notes: dict = {}

    assert manifest.reader(name)(Empty()) is None
