"""Exact tree/sol/best parity with the reference engine on real instances.

tests/golden/pfsp_lb2_ub1.jsonl holds (tree, sol, best) of the reference's
sequential engine (driven through its own library: decompose + lb2_bound,
PFSP_lib.c/c_bound_johnson.c) on Taillard instances with LB2 and ub=opt.
With ub=opt the B&B tree is exploration-order independent, so the native
C++ engine and the JAX device engine must reproduce the counts exactly —
the strongest cross-implementation invariant the reference offers
(SURVEY.md §4).
"""

import json
import pathlib

import numpy as np
import pytest

from tpu_tree_search import native
from tpu_tree_search.problems import taillard

GOLDEN = pathlib.Path(__file__).parent / "golden" / "pfsp_lb2_ub1.jsonl"
# 50-job class (counts regenerated from the reference compiled with
# MAX_JOBS=50 per its own recipe, pfsp/README.md:52 / macro.h:9-11 —
# the multi-word-bitmask LB2 path must reproduce them too)
GOLDEN_WIDE = pathlib.Path(__file__).parent / "golden" \
    / "pfsp_lb2_ub1_wide.jsonl"
# DEEP wide coverage: synthetic 40-50-job instances with 10^4-10^6-node
# trees at a fixed valid ub, goldened against the reference's own
# decompose/lb2_bound via the matrix-input wrapper main
# (tools/gen_matrix_goldens.py; .ref_build/wrap/pfsp/pfsp_mat.c) — the
# Taillard 50-job instances are all root-pruned or >2^31 nodes, so only
# synthetic instances can pin the multi-word two-phase path at depth
GOLDEN_MATRIX = pathlib.Path(__file__).parent / "golden" \
    / "pfsp_lb2_matrix.jsonl"
# LB1 / LB1_d counts from the reference's own decompose/lb1_bound /
# lb1_children_bounds (tools/gen_lb1_goldens.py): full trees where
# tractable, exact PREFIX counts at a fixed popped-parent budget for
# the billion-node instances (native reproduces the reference's DFS
# order — LIFO pool, slot-order pushes — so prefixes are invariant)
GOLDEN_LB1 = pathlib.Path(__file__).parent / "golden" \
    / "pfsp_lb1_ub1.jsonl"
GOLDEN_LB1D = pathlib.Path(__file__).parent / "golden" \
    / "pfsp_lb1d_ub1.jsonl"
CASES = [json.loads(l) for l in GOLDEN.read_text().splitlines()]
CASES += [json.loads(l) for l in GOLDEN_WIDE.read_text().splitlines()]
MATRIX_CASES = [json.loads(l)
                for l in GOLDEN_MATRIX.read_text().splitlines()]
LB1_CASES = [json.loads(l) for l in GOLDEN_LB1.read_text().splitlines()]
LB1_CASES += [json.loads(l) for l in GOLDEN_LB1D.read_text().splitlines()]

# keep CI bounded: native handles everything below a million nodes quickly
NATIVE_CASES = [c for c in CASES if c["tree"] <= 700_000]
# the compiled engine on the CPU test backend is slower; smallest cases only
DEVICE_CASES = [c for c in CASES if c["tree"] <= 150_000]


@pytest.mark.parametrize("case", NATIVE_CASES,
                         ids=lambda c: f"ta{c['inst']:03d}")
def test_native_matches_reference(case):
    p = taillard.processing_times(case["inst"])
    ub = taillard.optimal_makespan(case["inst"])
    tree, sol, best, _ = native.search(p, lb_kind=2, init_ub=ub)
    assert (tree, sol, best) == (case["tree"], case["sol"], case["best"])


@pytest.mark.parametrize("case", DEVICE_CASES,
                         ids=lambda c: f"ta{c['inst']:03d}")
def test_device_engine_matches_reference(case):
    from tpu_tree_search.engine import device
    p = taillard.processing_times(case["inst"])
    ub = taillard.optimal_makespan(case["inst"])
    out = device.search(p, lb_kind=2, init_ub=ub, chunk=64,
                        capacity=1 << 16)
    assert (out.explored_tree, out.explored_sol, out.best) == \
           (case["tree"], case["sol"], case["best"])


# complete rows are order-invariant (any engine); prefix rows are exact
# only for engines sharing the reference's DFS order (native)
LB1_NATIVE = [c for c in LB1_CASES
              if not c["complete"] or c["tree"] <= 700_000]
LB1_DEVICE = [c for c in LB1_CASES
              if c["complete"] and c["tree"] <= 150_000]


def _lb1_id(c):
    kind = {0: "lb1d", 1: "lb1"}[c["lb"]]
    tag = "" if c["complete"] else "_prefix"
    return f"ta{c['inst']:03d}_{kind}{tag}"


@pytest.mark.parametrize("case", LB1_NATIVE, ids=_lb1_id)
def test_native_matches_reference_lb1(case):
    """LB1/LB1_d counting semantics against the reference's own library
    (PFSP_lib.c:7-43; sgpu_launch.sh:84 pins -l 1) — including exact
    500k-popped-parent prefixes of the billion-node ta022/27/29/30
    trees (their full counts: tests/golden/pfsp_20x20_full.jsonl)."""
    p = taillard.processing_times(case["inst"])
    ub = taillard.optimal_makespan(case["inst"])
    tree, sol, best, _ = native.search(
        p, lb_kind=case["lb"], init_ub=ub, max_nodes=case["max_nodes"])
    assert (tree, sol, best) == (case["tree"], case["sol"], case["best"])


@pytest.mark.parametrize("case", LB1_DEVICE, ids=_lb1_id)
def test_device_engine_matches_reference_lb1(case):
    from tpu_tree_search.engine import device
    p = taillard.processing_times(case["inst"])
    ub = taillard.optimal_makespan(case["inst"])
    out = device.search(p, lb_kind=case["lb"], init_ub=ub, chunk=64,
                        capacity=1 << 16)
    assert (out.explored_tree, out.explored_sol, out.best) == \
           (case["tree"], case["sol"], case["best"])


def _matrix_id(c):
    return f"{c['jobs']}x{c['machines']}s{c['seed']}_{c['tree']}"


@pytest.mark.parametrize("case", MATRIX_CASES, ids=_matrix_id)
def test_native_matches_reference_deep_wide(case):
    """>=10^4-node trees with jobs > 32: the native engine against the
    reference's own library on arbitrary matrices."""
    p = np.asarray(case["p"], np.int32).reshape(case["machines"],
                                                case["jobs"])
    tree, sol, best, _ = native.search(p, lb_kind=2, init_ub=case["ub"])
    assert (tree, sol, best) == (case["tree"], case["sol"], case["best"])


@pytest.mark.parametrize("case", MATRIX_CASES, ids=_matrix_id)
def test_device_engine_matches_reference_deep_wide(case):
    """Same invariant through the batched engine — on the CPU backend
    this drives the XLA multi-word LB2 path; under TTS_TEST_TPU=1 on
    hardware it drives the two-phase pallas path (prefilter + multi-word
    bitmask) through trees five orders deeper than the round-2 wide
    goldens."""
    from tpu_tree_search.engine import device
    p = np.asarray(case["p"], np.int32).reshape(case["machines"],
                                                case["jobs"])
    out = device.search(p, lb_kind=2, init_ub=case["ub"], chunk=256,
                        capacity=1 << 18)
    assert (out.explored_tree, out.explored_sol, out.best) == \
           (case["tree"], case["sol"], case["best"])
