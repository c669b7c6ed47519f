"""The numbers that decide ``correct``, each held against its limit in
``limits/<cell>.json``. How each limit was set is in PERF.md."""
from __future__ import annotations

import statistics
from typing import Dict, List

from .harness import BenchError, Compared

# A leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone (Adam divides by its own scale); it is
# left out of the gradient and update comparisons.
NOUGHT_SHARE = 1e-3


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep) -> float:
    """max over kept leaves of |norm_prog - norm_ref| measured against the
    larger of the leaf's reference norm and the median leaf's."""
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def train_numbers(prog: Dict, ref: Dict, store_mismatch: float
                  ) -> Dict[str, float]:
    if sorted(prog["grad1"]) != sorted(ref["grad1"]):
        raise BenchError("the program's and the reference's leaves differ")
    med = statistics.median(ref["grad1"].values())
    keep = [k for k, g in ref["grad1"].items() if g >= NOUGHT_SHARE * med]
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]):
        loss_gap = float("inf")
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(prog["grad1"], ref["grad1"], keep),
            "update_gap": worst_leaf_gap(prog["delta"], ref["delta"], keep),
            "store_mismatch": store_mismatch}


def with_limits(numbers: Dict[str, float], limits: Dict[str, float]
                ) -> List[Compared]:
    """The numbers that ``limits/<cell>.json`` names, each beside its
    limit. A number read but not named there (``loss_gap``, which has no
    upper reading: PERF.md) is not compared."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise BenchError(f"a limit names a number that was not read: "
                         f"{missing}")
    return [Compared(k, float(numbers[k]), limits[k]) for k in limits]
