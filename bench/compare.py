"""The comparison that decides ``correct``.

The readings of the program and of the plain reference over the same
first rounds from the seed: the global model's update in the first
round, the per-round allocated dropout rates, and the L2 norm of each
leaf's change from the initial weights after the checked rounds (global
model; all clients together).  Each number below is a gap between the
two sides, held to its own limit (``bench/limits/<cell>.json``; the
readings each limit was set from are in PERF.md).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

# a leaf whose reference change is under this share of the median leaf's
# moved by round-off alone and is left out of the change gaps
STILL_LEAF = 1e-3


def leaves(tree) -> Dict[str, Any]:
    """Leaf path -> leaf of a nested pytree, the path's keys joined by
    dots (``fc0.w``, ``stack.super.p0.w``)."""
    return {jax.tree_util.keystr(path, simple=True, separator="."): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_norms(params, origin) -> Dict[str, float]:
    """Leaf path -> L2 norm of ``params - origin`` (float32 sums)."""
    o = leaves(origin)
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - o[k].astype(jnp.float32)))))
        for k, a in leaves(params).items()}


@jax.jit
def _sq_change(p, origin):
    return jax.tree_util.tree_map(
        lambda a, o: jnp.sum(jnp.square(
            a.astype(jnp.float32)
            - o[tuple(slice(0, s) for s in a.shape)].astype(jnp.float32))),
        p, origin)


def clients_change_norms(client_params: Sequence, origin) -> Dict[str, float]:
    """Leaf path -> L2 norm, over all clients, of each client's change from
    the initial global (sliced to the client's widths)."""
    total: Dict[str, float] = {}
    for p in client_params:
        for k, v in leaves(jax.device_get(_sq_change(p, origin))).items():
            total[k] = total.get(k, 0.0) + float(v)
    return {k: math.sqrt(v) for k, v in total.items()}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float]
              ) -> Dict[str, float]:
    """Per leaf, ``|n_prog - n_ref| / max(n_ref, median n_ref)``; leaves
    that did not move in the reference are left out."""
    med = float(np.median(list(ref.values())))
    return {leaf: abs(prog[leaf] - r) / max(r, med)
            for leaf, r in ref.items() if r >= STILL_LEAF * med}


def _norm_gap(prog: Dict[str, float], ref: Dict[str, float]) -> float:
    return max(leaf_gaps(prog, ref).values())


def sorted_rates_l1(prog, ref, groups) -> float:
    """Worst round of the L1 distance between the two sides' allocated
    rates, sorted within each group of clients of one model size.  Where
    the LP has budget to spare it hands it out in order of the clients'
    losses, so two clients of one size whose losses lie close swap rates
    between two sound runs; sorted, their rates still agree."""
    groups = np.asarray(groups)
    worst = 0.0
    for p, r in zip(np.asarray(prog, np.float64), np.asarray(ref)):
        worst = max(worst, sum(
            float(np.sum(np.abs(np.sort(p[groups == g])
                                - np.sort(r[groups == g]))))
            for g in np.unique(groups)))
    return worst


def budget_gap(prog, ref, model_bytes) -> float:
    """Worst round of the gap between the two sides' uploaded share of the
    fleet's parameters, sum U_n (1 - D_n) / sum U_n: the LP's equality
    constraint, which every sound allocation meets however the budget it
    has to spare is handed out."""
    u = np.asarray(model_bytes, np.float64)
    share = lambda d: (1.0 - np.asarray(d, np.float64)) @ u / u.sum()
    return float(np.max(np.abs(share(prog) - share(ref))))


def rel_l2(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]) -> float:
    """``||a - b|| / ||b||`` over all leaves (float64)."""
    num = sum(float(np.sum((np.asarray(a[k], np.float64) - b[k]) ** 2))
              for k in b)
    den = sum(float(np.sum(np.asarray(b[k], np.float64) ** 2)) for k in b)
    return math.sqrt(num / max(den, 1e-300))


def gaps(prog: Dict, ref: Dict, groups) -> Dict[str, float]:
    """Every number a cell may compare, by name.  ``groups``: each
    client's model size in bytes (clients of one size share a group)."""
    out = {
        "first_update_gap": rel_l2(prog["first_update"],
                                   ref["first_update"]),
        "rates_gap": sorted_rates_l1(prog["rates"], ref["rates"], groups),
        "budget_gap": budget_gap(prog["rates"], ref["rates"], groups),
        "global_change_gap": _norm_gap(prog["global_change"],
                                       ref["global_change"]),
        "clients_change_gap": _norm_gap(prog["clients_change"],
                                        ref["clients_change"]),
    }
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> List[Dict]:
    """Each number the cell's limits name, beside its limit (PERF.md says
    why a cell leaves a number out)."""
    return [{"name": k, "value": numbers[k], "limit": lim,
             "ok": numbers[k] <= lim} for k, lim in limits.items()]
