"""The comparisons that decide ``correct`` for training cells."""
from __future__ import annotations

import jax
import numpy as np

# a leaf whose reference gradient norm is under this share of the median
# leaf's moves under the optimizer by round-off alone: left out
NEGLIGIBLE = 1e-3


def leaf_dict(tree) -> dict:
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def worst_leaf_gap(prog: dict, ref: dict, keep) -> float:
    """max over kept leaves of |prog - ref| / max(ref, median ref)."""
    med = float(np.median([ref[k] for k in keep]))
    return float(np.max([abs(prog[k] - ref[k]) / max(ref[k], med)
                         for k in keep]))


def train_gaps(p_losses, p_first, p_change, r_losses, r_first, r_change):
    p_first, r_first = leaf_dict(p_first), leaf_dict(r_first)
    p_change, r_change = leaf_dict(p_change), leaf_dict(r_change)
    if set(p_first) != set(r_first) or len(p_losses) != len(r_losses):
        raise ValueError("program and reference trees or step counts differ")
    med = float(np.median(list(r_first.values())))
    keep = [k for k, v in r_first.items() if v >= NEGLIGIBLE * med]
    loss = np.max([abs(a - b) / abs(b) for a, b in zip(p_losses, r_losses)])
    return {"loss_gap": float(loss),
            "first_grad_gap": worst_leaf_gap(p_first, r_first, keep),
            "change_gap": worst_leaf_gap(p_change, r_change, keep)}
