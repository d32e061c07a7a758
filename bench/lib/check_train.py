"""What decides `correct` for a training cell.

Set-up drives the compiled step from the seed through its first steps;
the reference (bench/lib/ref_train.py) follows the same steps on the same
batches. Read, each as the worst case:

  loss_gap    |loss - ref loss| / ref loss over the steps (reported, not
              compared: no fault and no control moves it to three times
              what sound runs read, PERF.md);
  grad_gap    the first gradient as the optimizer got it (Adam's first
              moment after one step / (1 - beta1)), per leaf:
              |norm - ref norm| / max(ref norm of the leaf, median leaf's);
  change_gap  the parameters' change over the steps, per leaf, the same
              way, leaving out leaves whose reference gradient is under a
              thousandth of the median leaf's (they move by round-off).
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

MIN_GRAD_SHARE = 1e-3


def leaf_norms(tree) -> dict:
    """{leaf path: float32 norm of the leaf}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32))))) for p, x in flat}


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    floor = statistics.median(ref.values())
    worst = (0.0, "")
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        g = abs(prog[k] - r) / max(r, floor)
        worst = max(worst, (g, k))
    return worst


def numbers(prog: dict, ref: dict) -> dict:
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(prog["loss"], ref["loss"]))
    grad_gap, grad_leaf = leaf_gap(prog["grad"], ref["grad"])
    floor = statistics.median(ref["grad"].values()) * MIN_GRAD_SHARE
    keep = {k for k, v in ref["grad"].items() if v >= floor}
    change_gap, change_leaf = leaf_gap(prog["change"], ref["change"], keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_leaf": grad_leaf, "change_gap": change_gap,
            "change_leaf": change_leaf,
            "left_out": sorted(set(ref["grad"]) - keep)}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Every number that `limits` names is held to its limit."""
    n = numbers(prog, ref)
    out = {k: {"value": n[k], "limit": limits[k]} for k in limits}
    correct = all(v["value"] <= v["limit"] for v in out.values())
    return {"correct": correct, "numbers": out, "detail": n}
