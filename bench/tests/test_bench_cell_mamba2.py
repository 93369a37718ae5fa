"""A whole benchmark run of tiny Mamba-2 cells on the CPU (see
``test_bench_cell_hymba.py``)."""
import pytest

from cells import run_tiny


@pytest.mark.parametrize("traffic", ["silo-2k", "short-20"])
def test_tiny_mamba2_cell_is_correct(traffic):
    res = run_tiny("mamba2-2.7b", traffic, limits_of="mamba2-silo-2k")
    assert all(v["value"] < 1e-5 for v in res["checks"].values()), res["checks"]
    assert res["correct"]
