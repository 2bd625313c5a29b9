"""`batched.make_tables` stores the machine pairs strongest-first, in the
order of the host oracle `reference.calibrate_pair_order`, which it
computes as one compiled program: the order and every pair array it
emits equal the oracle's exactly. With too few pairs for the prefilter
(P <= 2 * PAIR_PREFILTER) or J < 3, the pairs keep their natural order.
"""

import numpy as np
import pytest

from tpu_tree_search.ops import batched, reference as ref
from tpu_tree_search.problems import taillard

ARRAYS = ("ma0", "ma1", "js", "ptm0_js", "ptm1_js", "lag_js")


def _natural_pairs(p):
    """The LB1 data and the pair arrays in natural (m1 < m2, row-major)
    order, built as `make_tables` builds them."""
    lb1 = ref.make_lb1_data(p)
    lb2 = ref.make_lb2_data(lb1)
    p = np.asarray(p, dtype=np.int32)
    ma0, ma1, js = lb2.pairs_m1, lb2.pairs_m2, lb2.johnson_schedules
    arrays = dict(ma0=ma0, ma1=ma1, js=js, ptm0_js=p[ma0[:, None], js],
                  ptm1_js=p[ma1[:, None], js],
                  lag_js=np.take_along_axis(lb2.lags, js, axis=1))
    return lb1, arrays


def _check(p, calibrates=True):
    lb1, nat = _natural_pairs(p)
    if calibrates:
        want = ref.calibrate_pair_order(
            np.asarray(p, dtype=np.int32), nat["ma0"], nat["ma1"],
            nat["js"], nat["ptm0_js"], nat["ptm1_js"], nat["lag_js"],
            lb1.min_tails)
    else:
        want = np.arange(len(nat["ma0"]))
    t = batched.make_tables(p)
    # the device's order, read back from the pairs it emitted
    index = {(a, b): i for i, (a, b) in enumerate(zip(nat["ma0"],
                                                      nat["ma1"]))}
    got = [index[(a, b)] for a, b in zip(np.asarray(t.ma0).tolist(),
                                         np.asarray(t.ma1).tolist())]
    assert np.array_equal(got, want)
    for name in ARRAYS:
        out = np.asarray(getattr(t, name))
        assert out.dtype == np.int32, name
        assert np.array_equal(out, nat[name][want]), name


@pytest.mark.parametrize("inst", range(21, 31))
def test_taillard_20x20_order_matches_oracle(inst):
    _check(taillard.processing_times(inst))


@pytest.mark.parametrize("jobs", [3, 8, 20, 50])
@pytest.mark.parametrize("machines", [11, 15, 20])
def test_synthetic_order_matches_oracle(machines, jobs):
    rng = np.random.default_rng(1000 * machines + jobs)
    _check(rng.integers(1, 100, (machines, jobs)).astype(np.int32))


def test_tied_frequencies_keep_first_max_and_stable_order(monkeypatch):
    # two copies of one 6x6 block of small times: mirrored machine pairs
    # attain equal maxima (first-max rule) and equal counts (stable rule)
    rng = np.random.default_rng(1)
    p = np.vstack([rng.integers(1, 9, (6, 6))] * 2).astype(np.int32)
    counts = []
    real = np.bincount
    monkeypatch.setattr(np, "bincount",
                        lambda *a, **k: counts.append(real(*a, **k))
                        or counts[-1])
    _check(p)
    freq, = counts
    nonzero = freq[freq > 0].tolist()
    assert len(set(nonzero)) < len(nonzero)      # ties among used pairs
    assert (freq == 0).sum() > 1                 # and among unused ones


@pytest.mark.parametrize("machines,jobs", [(10, 20), (11, 2)])
def test_uncalibrated_tables_keep_natural_order(machines, jobs):
    rng = np.random.default_rng(machines + jobs)
    _check(rng.integers(1, 100, (machines, jobs)).astype(np.int32),
           calibrates=False)
