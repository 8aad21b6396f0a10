"""The arithmetic of the comparison that decides ``correct``.

Training: each step's loss (the worst step is the number), and per-leaf norms
of the first gradient, of the parameters' change and of the change of further
state (running batch statistics), program against plain reference. A gap is
the distance between the two NORMS of a leaf (not the norm of a difference),
over the reference's norm of that leaf or of the median leaf, whichever is
larger: some gradients are all but zero. The worst leaf's gap is the number;
the median leaf's and the worst kernel's (a leaf of two axes or more, told by
its shape in the reference's tree) stand beside it for a cell whose norm
scales and biases are noise.
Serving: the widest gap by which a served token's reference logit lies
below the reference's best."""

from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

# A leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone; its change is not compared (its gradient is).
DEAD_GRADIENT_SHARE = 1e-3


def loss_gaps(program: List[float], reference: List[float]) -> List[float]:
    return [abs(p - r) / abs(r) for p, r in zip(program, reference)]


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              skip: Tuple[str, ...] = ()) -> Dict[str, float]:
    if set(program) != set(reference):
        raise ValueError(
            f"leaves differ: {sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - ref) / max(ref, floor)
            for leaf, ref in reference.items() if leaf not in skip}


def worst_and_median(gaps: Dict[str, float]) -> Tuple[float, float]:
    """(the worst leaf's gap, the median leaf's). NaN is the worst."""
    values = list(gaps.values())
    if any(v != v for v in values):
        return float("nan"), float("nan")
    return max(values), statistics.median(values)


def worst_kernel(gaps: Dict[str, float], rank: Dict[str, int]) -> float:
    """The worst gap among the leaves of two axes or more: convolution and
    matrix kernels, not the vectors (norm scales, biases). NaN is the worst;
    a tree with no such leaf is an error, not a pass."""
    values = [v for leaf, v in gaps.items() if rank[leaf] >= 2]
    if not values:
        raise ValueError("no leaf of two axes or more to compare")
    return float("nan") if any(v != v for v in values) else max(values)


def dead_leaves(reference_grad: Dict[str, float]) -> Tuple[str, ...]:
    floor = DEAD_GRADIENT_SHARE * statistics.median(reference_grad.values())
    return tuple(k for k, v in reference_grad.items() if v < floor)


def training_numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """``program`` / ``reference``: {"loss": [..], "grad": {leaf: norm},
    "delta": {leaf: norm}, and "stats" like them where a step moves further
    state}; the reference also gives ``"rank"``, every leaf's number of axes.
    Returns every number read, by short name: the worst step's loss gap, and
    for each tree the worst leaf's gap, (``_med``) the median leaf's and
    (``_kernel``) the worst among the leaves of two axes or more. Which of
    them a cell compares is its traffic file's ``limits``."""
    out = {"loss": max(loss_gaps(program["loss"], reference["loss"]))}
    grad = leaf_gaps(program["grad"], reference["grad"])
    delta = leaf_gaps(program["delta"], reference["delta"],
                      skip=dead_leaves(reference["grad"]))
    out["grad1"], out["grad1_med"] = worst_and_median(grad)
    out["delta"], out["delta_med"] = worst_and_median(delta)
    out["grad1_kernel"] = worst_kernel(grad, reference["rank"])
    out["delta_kernel"] = worst_kernel(delta, reference["rank"])
    if "stats" in reference:        # running batch statistics, where kept
        out["stats"], out["stats_med"] = worst_and_median(
            leaf_gaps(program["stats"], reference["stats"]))
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, List[float]]]:
    """(correct, {name: [number, limit]}) over the numbers the cell's files
    give a limit. A limit for a number that was not read is an error, not a
    pass; NaN fails. (PERF.md says, for each cell, why a number that is read
    has no limit.)"""
    table = {}
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"a limit for {name!r}, which was not read "
                           f"(read: {sorted(numbers)})")
        table[name] = [numbers[name], limit]
        ok = ok and bool(numbers[name] <= limit)
    return ok, table
