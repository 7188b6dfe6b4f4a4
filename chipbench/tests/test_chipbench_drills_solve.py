"""A solve cell driven end to end on the CPU at a tiny size: a sound run is
correct, and the control and every planted fault make ``correct`` false."""
import pytest

from chipbench.tests._tiny import SOLVE, run_tiny

TINY = {"max_theta": 1024, "batch": 256, "k": 5}
LIMITS = {"ris_sets": 4000, "probe_nodes": 20}


@pytest.mark.parametrize("drill,fails", [
    (None, None),
    ("control", "greedy_mismatch"),
    ("roots_only", "sample_share_gap"),
    ("half_pool", "greedy_mismatch"),
    ("altered_answer", "greedy_mismatch"),
])
def test_solve_drill(drill, fails):
    res = run_tiny(SOLVE, TINY, drill, limits=LIMITS)
    checked = res["checked"]
    if drill is None:
        assert res["correct"], checked
        assert res["attempted"] >= 1 and res["failed"] == 0
        assert res["metrics"]["solve_rr_per_s"]["value"] > 0
        return
    assert not res["correct"]
    assert checked[fails]["value"] > checked[fails]["limit"], checked
