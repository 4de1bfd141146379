"""The control comes out as not correct, on the cell's own mixes cut to a
size the CPU holds, on several seeds; the reference itself passes."""
import numpy as np
import pytest

import control
import reference
from traffic import generate

TINY = {"kron18": {"scale": 10}, "er18": {"log2_n": 10},
        "mixed-steady": {"size_hint": 128}, "mixed-overload": {"size_hint": 128}}


@pytest.mark.parametrize("traffic", sorted(TINY))
def test_control_fails_on_every_seed(traffic):
    mix = dict(generate.load(traffic), **TINY[traffic])
    for seed in (1, 22, 2**33 + 333):
        r = control.control_readings(mix, seed, seconds=2.0)
        assert r["correct"] is False
        assert r["checks"]["card_gap"]["value"] >= 1
        assert r["checks"]["invalid_entries"]["value"] == 0


def test_reference_matching_passes_and_breaks_are_seen():
    g = generate.random_bipartite(np.random.default_rng(4), 300, 280, 3.0)
    best = reference.maximum_size(g)
    cm = reference.reference_matching(g).astype(np.int64)
    rm = np.full(g.nr, -1, np.int64)
    cols = np.flatnonzero(cm >= 0)
    rm[cm[cols]] = cols
    cmp = reference.Comparison()
    assert cmp.add(g, cm, rm, best) and cmp.correct
    # a matched pair that is no edge of the graph
    bad_cm, bad_rm = cm.copy(), rm.copy()
    c = int(cols[0])
    free_rows = np.setdiff1d(np.arange(g.nr), np.concatenate(
        [cm[cols], g.cadj[g.cxadj[c]:g.cxadj[c + 1]]]))
    bad_rm[cm[c]] = -1
    bad_cm[c] = free_rows[0]
    bad_rm[free_rows[0]] = c
    assert not cmp.add(g, bad_cm, bad_rm, best)
    assert cmp.values["invalid_entries"] == 1
    # a row claimed by two columns
    cmp = reference.Comparison()
    two = cm.copy()
    two[cols[1]] = cm[cols[0]]
    assert not cmp.add(g, two, rm, best) and not cmp.correct
    # wrong shape
    assert reference.violations(g, cm[:-1], rm)[0] > 0
