"""The comparison that decides `correct`.

The loss of each checked step, the first gradient as the
optimizer got it, and the parameters' change over the checked steps, each
against the plain reference. Gradients and changes are compared leaf by
leaf as the gap between the two norms, over the larger of the
reference's norm of that leaf and of the median leaf; the worst leaf is
the number compared. Leaves whose first reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of the change.

Each number compared has its limit in bench/limits/<workload>.json; a
number at or under its limit passes. A number the file does not name is
not compared (a training number with no upper reading to set a limit
below).
"""
from __future__ import annotations

import math
from statistics import median

import jax


def flat(tree) -> dict:
    """{'/'-joined key path: float} of a tree of scalars."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): float(v) for path, v in leaves}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref)."""
    med = median(ref.values())
    gaps = [abs(prog[k] - r) / max(r, med) for k, r in ref.items()
            if keep is None or k in keep]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def moving_leaves(grad_raw: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    med = median(grad_raw.values())
    return {k for k, g in grad_raw.items() if g >= 1e-3 * med}


def check(name: str, value: float, limits: dict) -> dict:
    lim = limits[name]["limit"]
    ok = math.isfinite(value) and value <= lim
    return {"name": name, "value": value, "limit": lim, "ok": ok}


def train_readings(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's and the reference's
    readings (losses, grad1, change as flat dicts; grad1_raw for the
    reference)."""
    loss = max(abs(a - b) if math.isfinite(a) else math.inf
               for a, b in zip(prog["losses"], ref["losses"]))
    return {
        "loss_gap": loss,
        "grad_norm_gap": worst_leaf_gap(prog["grad1"], ref["grad1"]),
        "change_norm_gap": worst_leaf_gap(
            prog["change"], ref["change"], moving_leaves(ref["grad1_raw"])),
    }


def train_checks(prog: dict, ref: dict, limits: dict) -> list:
    return [check(k, v, limits)
            for k, v in train_readings(prog, ref).items() if k in limits]

