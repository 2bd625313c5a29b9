"""Everything the harness finds by name: the manifest's entries, and the
configuration, traffic, oracle, loop and reader files they name.

A new configuration, traffic mix or per-layer metric is a new file here
plus a new entry in BENCHMARK.json; nothing in this module changes.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def oracle(config_name: str) -> dict:
    """The plain reference's recorded answers: (inst, lb) -> row."""
    rows = {}
    with open(HERE / "oracle" / f"{config_name}.jsonl") as f:
        for line in f:
            if line.strip():
                r = json.loads(line)
                rows[(r["inst"], r["lb"])] = r
    return rows


def metrics_for(man: dict, section: str, cell: str) -> list[dict]:
    """The metrics of `section` ("end_to_end" or "per_layer") that this
    cell reports: those without a `workloads` key, and those naming it."""
    return [m for m in man[section]
            if "workloads" not in m or cell in m["workloads"]]


def _module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise KeyError(f"no file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop(kind: str):
    """The traffic loop named by a mix's `kind`."""
    return _module(HERE / "loops" / f"{kind}.py", f"bench_loop_{kind}")


def reader(metric: str):
    """The reader of a per-layer metric, as a function of the run that
    returns the number or None when it finds nothing: `metrics/<name>.py`
    with its own `read(run)`, or `metrics/<name>.json` naming a shared
    reader `readers/<reader>.py` and its arguments, whose
    `read(run, metric, **arguments)` serves several metrics."""
    own = HERE / "metrics" / f"{metric}.py"
    if own.is_file():
        return _module(own, "bench_metric_" + metric.replace(".", "_")).read
    spec = load_json(HERE / "metrics" / f"{metric}.json")
    name = spec.pop("reader")
    shared = _module(HERE / "readers" / f"{name}.py", f"bench_reader_{name}")
    return lambda run: shared.read(run, metric, **spec)
