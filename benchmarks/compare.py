"""The comparisons that decide `correct`, kept apart from the runners so
that `prove.py` and the tests read the same numbers the runs do."""
from __future__ import annotations

import statistics

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone; it is left out of the change
DEAD_GRAD_SHARE = 1e-3


def flat_norms(norms: dict) -> dict:
    """{leaf: scalar or [layers]} -> {"leaf" or "leaf.<layer>": float}."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim == 0:
            out[k] = float(v)
        else:
            for i, x in enumerate(v):
                out[f"{k}.{i}"] = float(x)
    return out


def worst_leaf_gap(got: dict, ref: dict, leaves=None):
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Returns (gap, leaf)."""
    leaves = sorted(ref) if leaves is None else sorted(leaves)
    median = statistics.median(ref[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        if k not in got:
            return float("nan"), k
        gap = abs(got[k] - ref[k]) / max(ref[k], median, 1e-300)
        if not gap <= worst:  # a NaN wins
            worst, where = gap, k
    return worst, where


def live_leaves(ref_grad_norms: dict) -> list:
    median = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items()
            if v >= DEAD_GRAD_SHARE * median]


def train_numbers(got: dict, ref: dict) -> dict:
    """``got`` and ``ref`` hold ``losses`` (a list), ``grad_norms`` and
    ``change_norms`` (flat leaf -> norm).  Returns name -> (number, leaf
    or step it was read at)."""
    n = min(len(got["losses"]), len(ref["losses"]))
    gaps = [abs(g - r) / abs(r) for g, r in
            zip(got["losses"][:n], ref["losses"][:n])]
    step = int(np.argmax(gaps))
    grad = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    change = worst_leaf_gap(got["change_norms"], ref["change_norms"],
                            live_leaves(ref["grad_norms"]))
    return {"loss_gap": (gaps[step] if n else float("nan"),
                         f"step {step + 1}"),
            "grad_norm_gap": grad, "change_norm_gap": change}
