"""A whole benchmark run of a tiny cell whose stack mixes two kinds of
layer, M M | F | M, on the CPU (see ``test_bench_cell_hymba.py``)."""
import tiny
from cells import run_tiny


def test_tiny_mixed_cell_is_correct():
    config = tiny.mixed([{"kind": "M", "count": 2}, {"kind": "F", "count": 1},
                         {"kind": "M", "count": 1}], "wq,wv,w_down,w_in,w_out")
    res = run_tiny(config, "silo-2k", limits_of="mamba2-silo-2k")
    assert res["attempted"] >= 1 and res["failed"] == 0
    # float32 program against the float32 reference: rounding only
    assert all(v["value"] < 1e-5 for v in res["checks"].values()), res["checks"]
    assert res["correct"]
