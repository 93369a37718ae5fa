"""The comparison that decides ``correct``.

Every number but the loss is a worst case over the leaves of the LoRA delta tree (a
leaf is one factor, A or B, of one target, stacked over the layers). For a
leaf the gap is between the two norms, not the norm of the difference:

    gap = | ||program|| - ||reference|| | / max(||reference||, median)

with ``median`` the reference's median leaf norm, since some leaves are
all but zero. Leaves whose reference movement is under a thousandth of
the median leaf's are left out of a number (round-off alone moves them).

The round compared is the first, from the benchmark's resumed state, so
every sampled client reads a non-zero c_i:

  loss  relative gap of the round's mean local loss (reported by
        ``numbers``; compared only in a cell whose limits name it)
  dx    the server step x1 - x0 (the aggregated dy)
  dc    the server control step c1 - c0 (the aggregated dc)
  ci    the new c_i of each sampled client (the c - c_i correction acts
        on it through the client's K steps)
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

NAMES = ("loss", "dx", "dc", "ci")


def leaf_norms(tree) -> Dict[str, float]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = float(np.linalg.norm(np.asarray(leaf, np.float64).ravel()))
    return out


def sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
                        a, b)


def worst_gap(prog, ref) -> float:
    rn, pn = leaf_norms(ref), leaf_norms(prog)
    med = float(np.median(list(rn.values())))
    keys = [k for k in rn if rn[k] >= 1e-3 * med]
    return max(abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in keys)


def numbers(state0: Dict, prog: Dict, ref: Dict) -> Dict[str, float]:
    """``state0``: ``{"x", "c"}`` before the round; ``prog``: host copies
    ``{"loss", "x", "c", "c_i": [..]}`` of the program after it; ``ref``:
    ``scaffold.run_rounds``' entry for the same round."""
    return {
        "loss": abs(prog["loss"] - ref["loss"]) / abs(ref["loss"]),
        "dx": worst_gap(sub(prog["x"], state0["x"]), sub(ref["x"], state0["x"])),
        "dc": worst_gap(sub(prog["c"], state0["c"]), sub(ref["c"], state0["c"])),
        "ci": max(worst_gap(p, r) for p, r in zip(prog["c_i"], ref["c_i"])),
    }


def as_program(ref: Dict) -> Dict:
    """A reference run (the control, a planted fault) laid out like the
    program's snapshot, to be compared in the program's place."""
    return {k: ref[k] for k in ("loss", "x", "c", "c_i")}


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number that the cell's limits name is finite and within its
    limit; a number with no limit in the cell is not compared."""
    return all(np.isfinite(nums[k]) and nums[k] <= limits[k] for k in limits)
