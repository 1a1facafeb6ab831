"""The numbers the output check compares, each a gap between what the
timed path produced and the plain reference (``bench/ref.py``).

Norm gaps follow one rule: per leaf, the gap between the program's norm
and the reference's, against the reference's norm of that leaf or of the
median leaf of the same model, whichever is larger, since some leaves'
values are all but zero. The worst leaf is the number.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: its change is not compared
NOUGHT_GRAD = 1e-3


def leaf_norms(leaves: Sequence[np.ndarray]) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in leaves])


def norm_gap(prog: Sequence[np.ndarray], ref: Sequence[np.ndarray],
             keep: Optional[np.ndarray] = None) -> Tuple[float, int]:
    """Worst leaf's |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖), over the
    leaves ``keep`` selects; returns the gap and the leaf's index."""
    p, r = leaf_norms(prog), leaf_norms(ref)
    if keep is None:
        keep = np.ones(len(r), bool)
    if not keep.any():
        return 0.0, -1
    floor = np.median(r[keep])
    gap = np.where(keep, np.abs(p - r) / np.maximum(np.maximum(r, floor),
                                                   1e-30), 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def rel_gap(prog, ref) -> float:
    """Largest |prog - ref| / |ref| over all elements."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))


def training(prog_loss: List[Dict[str, np.ndarray]],
             ref_loss: List[Dict[str, np.ndarray]],
             prog_grad: Dict[str, List[np.ndarray]],
             ref_grad: Dict[str, List[np.ndarray]],
             prog_delta: Dict[str, List[np.ndarray]],
             ref_delta: Dict[str, List[np.ndarray]]) -> Dict[str, float]:
    """The training comparison: every step's per-client loss, the first
    gradient by leaf, and the weights' change over the steps by leaf
    (leaves whose reference gradient is nought to rounding left out)."""
    loss = max(rel_gap(p[f], r[f]) for p, r in zip(prog_loss, ref_loss)
               for f in r)
    grad = delta = 0.0
    for f in ref_grad:
        grad = max(grad, norm_gap(prog_grad[f], ref_grad[f])[0])
        g = leaf_norms(ref_grad[f])
        keep = g >= NOUGHT_GRAD * np.median(g)
        delta = max(delta, norm_gap(prog_delta[f], ref_delta[f], keep)[0])
    return {"loss": loss, "grad": grad, "change": delta}
