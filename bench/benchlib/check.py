"""Whether the timed path computed what the plain reference computes.

Set-up drives the entry through its warm-up calls, the same entry and
data the window then continues with. The reference follows the first
call, and three numbers compare the two:

- ``loss``: the largest gap between a round's loss on a node in the
  program and in the reference, over every round, node and variant of
  the call, against the reference's loss there or its median
  loss, whichever is larger;
- ``moment``: after the call, the largest gap between the norm of
  a leaf's Adam first moment in the program and in the reference;
- ``change``: after the call, the largest gap between the
  norm of a leaf's change since initialization in the two.

A leaf gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger. Leaves whose first moment is
nought to rounding in the reference (under a thousandth of the median
leaf's) are left out of both leaf numbers.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss", "moment", "change")
QUIET = 1e-3


def leaf_gap(prog: np.ndarray, ref: np.ndarray, live: np.ndarray) -> float:
    """Largest ``|prog - ref| / max(ref, median(ref))`` over live leaves."""
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(scale, 1e-30)
    return float(np.max(np.where(live, gap, 0.0)))


def numbers(prog: dict, ref: dict) -> dict:
    lp = np.concatenate([np.ravel(a) for a in prog["loss"]])
    lr = np.concatenate([np.ravel(a) for a in ref["loss"]])
    live = ref["m"] >= QUIET * np.median(ref["m"])
    scale = np.maximum(np.abs(lr), np.median(np.abs(lr)))
    return {
        "loss": float(np.max(np.abs(lp - lr) / np.maximum(scale, 1e-30))),
        "moment": leaf_gap(prog["m"], ref["m"], live),
        "change": leaf_gap(prog["change"], ref["change"], live),
    }


def reference_inputs(parts: dict, run: dict) -> dict:
    """The model family's data for the reference, and the seeds of the
    run's variants, fault plan and redundancy plan."""
    desc = run["desc"]
    return dict(parts["family"].reference_inputs(run["inputs"]),
                variants=desc["variants"], faults=desc["faults"],
                ingest=desc["ingest"])


def compare(parts: dict, run: dict) -> dict:
    """``{number: {"value", "limit"}}`` for the cell; a number that is not
    finite (the program produced NaN or Inf) reads ``"not finite"``."""
    ref = parts["reference"].run(parts["config"], parts["traffic"],
                                 reference_inputs(parts, run),
                                 calls=1)
    got = numbers(run["readings"], ref)
    limits = parts["limits"]
    return {name: {"value": got[name] if np.isfinite(got[name])
                   else "not finite", "limit": limits[name]}
            for name in NUMBERS}


def correct(checked: dict) -> bool:
    return all(isinstance(n["value"], float) and n["value"] <= n["limit"]
               for n in checked.values())
