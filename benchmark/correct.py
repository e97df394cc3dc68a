"""The comparison that decides ``correct``: what the timed path
produced against the plain reference, each number beside its limit.
The limits are data (the cell's ``limits``); how each was set is in
PERF.md.  A number without a limit is not compared.
"""
from __future__ import annotations

import numpy as np

#: leaves whose first gradient, in the reference, is under this share
#: of the median leaf's are nought to rounding (a key's bias under
#: softmax): Adam moves them by round-off alone, so they are left out
#: of the change comparison -- by this rule, not by name
QUIET_GRADIENT = 1e-3


def _flat(norms: dict) -> dict:
    out = {}
    for name, v in norms.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        for i, x in enumerate(v):
            out[name if len(v) == 1 else f"{name}[{i}]"] = float(x)
    return out


def leaf_gaps(program: dict, ref: dict, skip=(), shares: bool = False) -> dict:
    """{leaf: gap}: the gap between the program's norm of a leaf and
    the reference's (not the norm of their difference), measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  With ``shares`` each side's norms are first
    divided by the norm of its whole tree: what is compared is how the
    tree's norm is spread over the leaves, whatever its size."""
    p, r = _flat(program), _flat(ref)
    if p.keys() != r.keys():
        raise ValueError(f"leaves differ: {sorted(p.keys() ^ r.keys())[:6]}")
    keep = [k for k in r if k not in skip]
    if shares:
        whole = lambda t: float(np.sqrt(sum(t[k] ** 2 for k in keep))) or 1.0
        wp, wr = whole(p), whole(r)
        p, r = {k: p[k] / wp for k in keep}, {k: r[k] / wr for k in keep}
    median = float(np.median([r[k] for k in keep]))
    return {k: abs(p[k] - r[k]) / max(r[k], median) for k in keep}


def worst(gaps: dict) -> tuple:
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def quiet_leaves(ref_grad_norms: dict) -> set:
    r = _flat(ref_grad_norms)
    floor = QUIET_GRADIENT * float(np.median(list(r.values())))
    return {k for k, v in r.items() if v < floor}


def training_numbers(program: dict, ref: dict) -> dict:
    """{name: (value, the leaf or step it is about)}"""
    out = {f"loss{i + 1}_gap": (abs(p - r) / abs(r), f"step {i + 1}")
           for i, (p, r) in enumerate(zip(program["losses"], ref["losses"]))}
    quiet = quiet_leaves(ref["grad_norms"])
    out["grad_norm_gap"] = worst(leaf_gaps(program["grad_norms"], ref["grad_norms"]))
    out["change_norm_gap"] = worst(leaf_gaps(
        program["change_norms"], ref["change_norms"], skip=quiet))
    shares = leaf_gaps(program["change_norms"], ref["change_norms"], skip=quiet,
                       shares=True)
    out["change_share_gap_median"] = (
        float(np.median(list(shares.values()))),
        f"the median of {len(shares)} leaves' shares of the change")
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit", "about"}}) over the numbers
    that have a limit; a value that is not finite fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value, about = numbers[name]
        compared[name] = {"value": float(value), "limit": limit,
                          "about": str(about)}
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return ok, compared
