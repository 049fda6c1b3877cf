"""The comparison that decides `correct` for a training cell: the program's
first steps against the plain reference's, number by number, each with a
limit of its own (perf/limits/<cell>.json; PERF.md gives the readings each
was set from).

A gap of norms is taken by the worst leaf: the distance between the
program's norm and the reference's, against the reference's norm of that
leaf or of the median leaf, whichever is larger (some leaves' are all but
nought).
"""
from __future__ import annotations

import math

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves under the optimizer by round-off alone: left out of the change
NO_GRADIENT = 1e-3


def leaf_gaps(got, want, keep=None):
    """Each leaf's gap of norms, or None where the trees differ."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return None
    gap = np.abs(got - want) / np.maximum(want, np.median(want))
    return gap if keep is None else gap[keep]


def _over_leaves(pick, got, want, keep=None) -> float:
    gap = leaf_gaps(got, want, keep)
    if gap is None:
        return math.inf
    if gap.size == 0:
        return 0.0
    return float(pick(gap)) if np.all(np.isfinite(gap)) else math.inf


def worst_leaf_gap(got, want, keep=None) -> float:
    return _over_leaves(np.max, got, want, keep)


def median_leaf_gap(got, want, keep=None) -> float:
    """The median leaf's gap: steady from seed to seed where the worst
    leaf's swings with the rounding of one deep network's first layers."""
    return _over_leaves(np.median, got, want, keep)


def numbers(program: dict, reference: dict) -> dict:
    """Every number compared, by its short name."""
    out = {}
    for i, want in enumerate(reference["losses"], 1):
        got = program["losses"][i - 1]
        gap = abs(got - want) / abs(want)
        out[f"loss{i}"] = gap if math.isfinite(gap) else math.inf
    ref_grads = np.asarray(reference["grad_norms"], np.float64)
    out["grad"] = worst_leaf_gap(program["grad_norms"], ref_grads)
    out["grad_median"] = median_leaf_gap(program["grad_norms"], ref_grads)
    moved = ref_grads >= NO_GRADIENT * np.median(ref_grads)
    for name, over in (("change", worst_leaf_gap),
                       ("change_median", median_leaf_gap)):
        out[name] = over(program["change_norms"], reference["change_norms"],
                         moved)
    if len(reference["state_norms"]):
        out["state"] = worst_leaf_gap(program["state_norms"],
                                      reference["state_norms"])
    return out


def decide(found: dict, limits: dict):
    """(correct, {name: [number, limit]}): every number has to have an
    entry in the limits and stay within it. A limit of null marks a number
    that PERF.md names as shown and not compared."""
    missing = [k for k in found if k not in limits]
    if missing:
        raise KeyError(f"no limit for {missing}")
    compared = {k: [v, limits[k]] for k, v in found.items()}
    ok = all(v <= lim for v, lim in compared.values() if lim is not None)
    return ok, compared
