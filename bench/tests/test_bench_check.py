"""The correctness check on the CPU at small sizes, in both model
families: the timed path agrees with the plain reference in every cell;
the control, the reference computed in bfloat16 in the program's place,
comes out not correct; and so does the reference with a fault planted in
its rounds, as ``bench/calibrate.py`` reads them."""
import pytest

import bench_tiny
import calibrate
from benchlib import check


def _correct(p, got):
    return check.correct({k: {"value": v, "limit": p["limits"][k]}
                          for k, v in got.items()})


@pytest.mark.parametrize("cell, family", bench_tiny.CASES)
def test_program_agrees_with_reference(cell, family):
    result = bench_tiny.run(bench_tiny.parts(cell, family))
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1
    # float32 rounding only: a leaf's change since initialization is a
    # few hundred times smaller than the leaf, so its norm keeps about
    # 1e-5 of relative rounding
    for name, n in result["check"].items():
        assert n["value"] < 1e-4, (name, n)


@pytest.mark.parametrize("cell, family", bench_tiny.CASES)
def test_control_is_not_correct(cell, family):
    p = bench_tiny.parts(cell, family)
    got = calibrate.readings(p, 77, faults=False)
    assert _correct(p, got["program"]), got
    assert not _correct(p, got["control"]), got


@pytest.mark.parametrize("family", bench_tiny.FAMILIES)
def test_planted_faults_are_not_correct(family):
    p = bench_tiny.parts("paper.study60", family)
    got = calibrate.readings(p, 78, faults=True)
    for plant in calibrate.PLANTS:
        assert not _correct(p, got[plant]), (plant, got)
