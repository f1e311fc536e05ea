"""The comparison that decides ``correct``.

What is compared is what the timed path produced in the set-up's warm
job, which is the same ``Federation`` object, engine and programs the
window then drives: the first edge round of global round 0 (every
member's K local steps from the initial adapters, through its dynamic
split and its SS-OP + sketch channel), the edge aggregate of that
round, and the job's second eval (the logits of the 512 test rows under
the global adapters after cloud aggregation). The reference
(``refkit``) recomputes the same round and eval from the seed and the
recorded inputs. The numbers compared:

- ``loss_gap``: the largest relative gap of a member's loss at local
  steps 1, 2 and 3 (step 1 is the forward; steps 2 and 3 carry the
  first updates);
- ``update_gap``: the worst leaf of the members' adapter change over
  the K steps;
- ``edge_agg_gap``: the worst leaf of the edge aggregate's change;
- ``eval_gap``: the widest gap of an eval logit, over the largest
  reference logit (``eval_gap``);
- ``basis_gap`` and ``rotation_gap``: the SS-OP basis the round used,
  against the reference's own singular values, and each member's
  rotation against the reference's (``harness.basis_check``).

A leaf gap is ``| ||prog|| - ||ref|| | / max(||ref||, median)``, where
``median`` is the median leaf norm of the reference's change, and a
layer of a layer-stacked adapter is a leaf of its own. Leaves whose
reference change is under a thousandth of the median are nought to
rounding and left out.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "update_gap", "edge_agg_gap", "eval_gap",
           "basis_gap", "rotation_gap")


def leaf_arrays(tree, prefix=""):
    """{name: array}; stacked ``blocks`` leaves split by layer, and the
    entries of a list (layers kept one by one, as a ``prefix`` of
    leading layers) named by index: ``/prefix[0]/attn/q_a``."""
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(leaf_arrays(tree[k], f"{prefix}/{k}"))
        return out
    if isinstance(tree, (list, tuple)):
        for i, t in enumerate(tree):
            out.update(leaf_arrays(t, f"{prefix}[{i}]"))
        return out
    a = np.asarray(tree, np.float64)
    if prefix.startswith("/blocks/"):
        for i in range(a.shape[0]):
            out[f"{prefix}[{i}]"] = a[i]
    else:
        out[prefix] = a
    return out


def change(tree, base):
    t, b = leaf_arrays(tree), leaf_arrays(base)
    return {k: t[k] - b[k] for k in b}


def worst_leaf_gap(prog_change, ref_change):
    """(gap, leaf) of the worst leaf, by the rule in the docstring."""
    rn = {k: float(np.linalg.norm(v)) for k, v in ref_change.items()}
    pn = {k: float(np.linalg.norm(prog_change[k])) for k in rn}
    med = float(np.median(list(rn.values())))
    worst, where = 0.0, None
    for k in rn:
        if rn[k] < 1e-3 * med:
            continue
        g = abs(pn[k] - rn[k]) / max(rn[k], med)
        if not math.isfinite(g):
            return math.inf, k
        if g >= worst:
            worst, where = g, k
    return worst, where


def loss_gap(prog_losses, ref_losses, steps=3):
    p = np.asarray(prog_losses, np.float64)[:steps]
    r = np.asarray(ref_losses, np.float64)[:steps]
    g = np.abs(p - r) / np.abs(r)
    return float(np.max(g)) if np.all(np.isfinite(g)) else math.inf


def eval_gap(prog_logits, ref_logits):
    p = np.asarray(prog_logits, np.float64)
    r = np.asarray(ref_logits, np.float64)
    g = float(np.max(np.abs(p - r)) / np.max(np.abs(r)))
    return g if math.isfinite(g) else math.inf


def readings(prog, ref):
    """The four numbers for one program record and one reference
    evaluation of the same round and eval: each ``{"members": {n:
    (losses, lora)}, "agg": tree, "lora0": tree, "eval_logits": array}``.
    """
    lg, ug, where_u = 0.0, 0.0, None
    for n, (p_losses, p_lora) in prog["members"].items():
        r_losses, r_lora = ref["members"][n]
        lg = max(lg, loss_gap(p_losses, r_losses))
        g, leaf = worst_leaf_gap(change(p_lora, prog["lora0"]),
                                 change(r_lora, ref["lora0"]))
        if g >= ug:
            ug, where_u = g, f"client {n} {leaf}"
    ag, where_a = worst_leaf_gap(change(prog["agg"], prog["lora0"]),
                                 change(ref["agg"], ref["lora0"]))
    return {"loss_gap": lg, "update_gap": ug, "edge_agg_gap": ag,
            "eval_gap": eval_gap(prog["eval_logits"], ref["eval_logits"]),
            "update_gap_at": where_u, "edge_agg_gap_at": where_a}


def verdict(values, limits):
    """(correct, [(name, value, limit), ...]) over ``NUMBERS``."""
    rows = [(k, values[k], limits[k]) for k in NUMBERS]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
