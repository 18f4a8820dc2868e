"""The comparison that decides ``correct``.

For training, each compared number is a worst leaf: the gap between the
norm of a leaf's change in the program and in the reference, over the
larger of the reference's norm of that leaf and of the median leaf,

    max_leaf |‖Δ_prog‖ - ‖Δ_ref‖| / max(‖Δ_ref‖, median_leaf ‖Δ_ref‖)

Leaves whose change in the reference's first round is under a thousandth
of the median leaf's are left out: they move by round-off alone. A leaf
that one side leaves unmoved reads 1.
"""
from __future__ import annotations

import numpy as np


def flat_leaves(tree, prefix: str = "") -> dict:
    """{dotted name: array} of a nested dict of arrays."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat_leaves(v, name + "."))
        else:
            out[name] = v
    return out


def split_layers(leaves: dict, key: str = "layers.") -> dict:
    """Stacked per-layer leaves ``layers.x`` (leading layer axis) split
    into ``layers.<i>.x``."""
    out = {}
    for name, a in leaves.items():
        if name.startswith(key):
            for i in range(a.shape[0]):
                out[f"{key}{i}.{name[len(key):]}"] = a[i]
        else:
            out[name] = a
    return out


def norm_gap(prog: dict, ref: dict, keep: set) -> tuple[float, str]:
    """Worst-leaf gap of norms over the leaves in ``keep``, and which."""
    med = float(np.median([ref[k] for k in keep]))
    worst, which = 0.0, ""
    for k in sorted(keep):
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst or not which:
            worst, which = gap, k
    return worst, which


def kept_leaves(ref_first: dict) -> set:
    """Leaves whose first-round change in the reference is at least a
    thousandth of the median leaf's."""
    med = float(np.median(list(ref_first.values())))
    return {k for k, v in ref_first.items() if v >= 1e-3 * med}


def judge(checks: dict) -> bool:
    """True when every compared number is finite and within its limit."""
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())
