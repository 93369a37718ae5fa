"""A whole benchmark run (all but the look for a chip) of tiny hymba
cells on the CPU: the program's first SCAFFOLD rounds agree with the
plain reference, and the result line has the contract's keys."""
import pytest

from cells import run_tiny


@pytest.mark.parametrize("traffic", ["silo-2k", "short-20"])
def test_tiny_hymba_cell_is_correct(traffic):
    res = run_tiny("hymba-1.5b", traffic)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "round_s"}
    # float32 program against the float32 reference: rounding only
    assert all(v["value"] < 1e-5 for v in res["checks"].values()), res["checks"]
    assert res["correct"]
