"""The numbers that decide ``correct``: the program's first steps against
the reference's, each by a relative gap.

  loss         worst step of |loss - ref| / |ref| over the checked steps
  grad_norm    |norm - ref| / ref of the first step's global gradient norm,
               before the clip (the step's own ``grad_norm`` output)
  grad_leaf    worst leaf of | |g| - |g_ref| | / max(|g_ref|, median leaf),
               g the first gradient as the optimizer got it, clipped, worked
               out from the first moment after one step: m / (1 - b1)
  change_leaf  the same gap of the norm of each leaf's change over the
               checked steps, leaves whose reference gradient is under a
               thousandth of the median leaf's left out (they move by
               round-off alone)

A leaf is one tensor of one layer (every stage's layers apart), or the
embedding, the head or the final norm.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

NAMES = ("loss", "grad_norm", "grad_leaf", "change_leaf")
NOUGHT_SHARE = 1e-3  # a leaf with a smaller reference gradient moves by round-off


def _flat(leaves: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for name, x in leaves.items():
        x = np.asarray(x, np.float64)
        if x.ndim == 0:
            out[name] = float(x)
        else:
            for i, v in enumerate(x):
                out[f"{name}[{i}]"] = float(v)
    return out


def worst_leaf_gap(got, ref, keep=None) -> Tuple[float, str]:
    """Largest gap of per-leaf norms, against max(ref leaf, median ref leaf)."""
    g, r = _flat(got), _flat(ref)
    if set(g) != set(r):
        raise ValueError(f"leaves differ: {sorted(set(g) ^ set(r))}")
    keys = [k for k in r if keep is None or k in keep]
    med = float(np.median([r[k] for k in keys]))
    gaps = {k: abs(g[k] - r[k]) / max(r[k], med, 1e-30) for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moving_leaves(ref_grad) -> set:
    r = _flat(ref_grad)
    med = float(np.median(list(r.values())))
    return {k for k, v in r.items() if v >= NOUGHT_SHARE * med}


def numbers(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """name -> (gap, where).  ``prog`` and ``ref`` hold losses, grad_norms,
    grad_leaf and change_leaf as ``Reference.run`` returns them."""
    k = len(ref["losses"])
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["losses"][:k], ref["losses"])]
    step = int(np.argmax(gaps))
    g0, r0 = prog["grad_norms"][0], ref["grad_norms"][0]
    return {
        "loss": (float(gaps[step]), f"step {step + 1}"),
        "grad_norm": (abs(g0 - r0) / r0, "step 1"),
        "grad_leaf": worst_leaf_gap(prog["grad_leaf"], ref["grad_leaf"]),
        "change_leaf": worst_leaf_gap(
            prog["change_leaf"], ref["change_leaf"], keep=moving_leaves(ref["grad_leaf"])
        ),
    }


def judge(nums: Dict[str, Tuple[float, str]], limits: Dict[str, Optional[float]]) -> Tuple[bool, Dict]:
    """(correct, checks): every number with a limit must lie at or under it,
    and be finite.  A limit of None prints the number without comparing it."""
    checks, ok = {}, True
    for name in NAMES:
        value, where = nums[name]
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit, "at": where}
        if not np.isfinite(value) or (limit is not None and value > limit):
            ok = False
    return ok, checks
