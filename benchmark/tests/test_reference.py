"""The plain references (benchmark/reference.py and benchmark/reference.c,
which take nothing of the program) derive the recorded oracle again,
and the program's instances are Taillard's."""

import json
import shutil
import subprocess

import numpy as np
import pytest

from benchmark import manifest, reference

ROWS = [("serve-taillard-small", i, 2) for i in (1, 2, 3, 4, 7, 9, 14, 19)]
ROWS += [("taillard-20x20-table", i, lb)
         for i, lb in ((2, 2), (4, 2), (19, 2), (2, 1), (19, 1))]
# the rows the C reference alone counts in a test's time
C_ROWS = ROWS + [("taillard-20x20-table", 7, 1)]


def cell_rows():
    """(inst) of every row a cell or its rehearsal runs."""
    man = manifest.manifest()
    out = set()
    for w in man["workloads"]:
        tr = manifest.traffic(w["traffic"])
        out |= set(tr["rows"]) | set(tr.get("rehearsal", {}).get("rows", []))
    return sorted(out)


@pytest.fixture(scope="module")
def reference_c(tmp_path_factory):
    cc = shutil.which("cc") or shutil.which("gcc")
    if cc is None:
        pytest.skip("no C compiler")
    exe = tmp_path_factory.mktemp("refc") / "reference_c"
    subprocess.check_call([cc, "-O2", "-o", str(exe),
                           str(manifest.HERE / "reference.c")])
    return str(exe)


@pytest.mark.parametrize("config,inst,lb", ROWS)
def test_reference_reproduces_the_oracle(config, inst, lb):
    want = manifest.oracle(config)[(inst, lb)]
    tree, _, best = reference.search(reference.instance(inst), lb,
                                     reference.OPTIMA[inst - 1])
    assert (tree, best) == (want["tree"], want["best"])


@pytest.mark.parametrize("config,inst,lb", C_ROWS)
def test_c_reference_reproduces_the_oracle(reference_c, config, inst, lb):
    want = manifest.oracle(config)[(inst, lb)]
    got = json.loads(subprocess.check_output([reference_c, str(inst),
                                              str(lb)]))
    assert (got["tree"], got["best"]) == (want["tree"], want["best"])


@pytest.mark.parametrize("inst", [1, 14, 19, 22, 29, 30])
def test_c_reference_has_the_same_instances(reference_c, inst):
    out = subprocess.check_output([reference_c, "--instance", str(inst)])
    got = np.array([[int(x) for x in line.split()]
                    for line in out.decode().splitlines()])
    assert (got == reference.instance(inst)).all()


@pytest.mark.parametrize("inst", cell_rows())
def test_the_program_runs_taillards_instances(inst):
    from tpu_tree_search.problems import taillard
    assert (np.asarray(taillard.processing_times(inst))
            == reference.instance(inst)).all()
    assert taillard.optimal_makespan(inst) == reference.OPTIMA[inst - 1]


def test_instances_are_taillards():
    # first processing times of ta001 and ta021 as Taillard lists them
    assert reference.instance(1)[0, :5].tolist() == [54, 83, 15, 71, 77]
    assert reference.instance(21).shape == (20, 20)
    assert reference.OPTIMA[21] == 2099
