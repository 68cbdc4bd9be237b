"""The comparison that decides `correct`: each number beside its limit.

Training (see PERF.md, "How correct is decided here"):
  grad_norm     worst compared leaf of | ||g_prog|| - ||g_ref|| | over
                max(||g_ref|| of that leaf, of the median leaf), for the
                first gradient as the optimizer got it
  grad_proj     root mean square over the leaves of |<g_prog, r> -
                <g_ref, r>| over the same denominator, r a fixed vector of
                +-1 drawn from the seed: a norm feels rounding at second
                order only, this at first
  change_norm   the same for the parameters' change over the steps;
                leaves whose reference gradient is under a thousandth of
                the median leaf's are left out (Adam moves them by
                round-off alone)
Serving:
  token_gap     the widest gap by which a served token's reference
                logit lies below the reference's best
  token_gap_mean  the mean of that gap over the sample's served tokens
  answered      requests of the sample that never came whole (limit 0)
The limits are data: benchmarks/limits/<cell>.json.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from harness.spec import BENCH_DIR


def limits_of(cell_name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "limits", cell_name + ".json")) as fh:
        return json.load(fh)


def _flat_raw(tree: dict) -> tuple:
    """(names, values) of a {leaf: scalar or [L]} tree, in sorted order."""
    names, vals = [], []
    for k in sorted(tree):
        v = np.asarray(tree[k], np.float64).reshape(-1)
        for i, x in enumerate(v):
            names.append(k if v.size == 1 else f"{k}[{i}]")
            vals.append(float(x))
    return names, np.asarray(vals)


def _flat(norms_sq: dict) -> tuple:
    names, vals = _flat_raw(norms_sq)
    return names, np.sqrt(vals)


def norm_gap(prog_sq: dict, ref_sq: dict, keep=None) -> tuple:
    """(worst gap, its leaf): | ||prog|| - ||ref|| | against the larger of
    the reference's norm of that leaf and of the median leaf."""
    names, p = _flat(prog_sq)
    names_r, r = _flat(ref_sq)
    if names != names_r:
        raise ValueError("program and reference compare different leaves: "
                         f"{sorted(set(names) ^ set(names_r))[:6]}")
    if keep is None:
        keep = np.ones(len(names), bool)
    floor = float(np.median(r[keep]))
    gap = np.abs(p - r) / np.maximum(r, floor)
    gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def projection_gap(prog_proj: dict, ref_proj: dict, proj_sq: dict) -> tuple:
    """(root mean square gap, the worst leaf): |<g_prog, r> - <g_ref, r>|
    of a leaf against the larger of the reference's norm of that leaf's
    gradient and of the median leaf's. `proj_sq`: the reference gradient's
    squared norms under the projections' names (a leaf that is compared
    in parts and projected whole is folded by the family)."""
    names, r_norm = _flat(proj_sq)
    names_p, p = _flat_raw(prog_proj)
    names_r, r = _flat_raw(ref_proj)
    if not (names_p == names_r == ["proj." + n for n in names]):
        raise ValueError("projections and norms name different leaves")
    gap = np.abs(p - r) / np.maximum(r_norm, float(np.median(r_norm)))
    i = int(np.argmax(gap))
    # one leaf's projection swings like |N(0, 1)|; the root mean square
    # over the leaves is the steady reading of the gradient's relative error
    return float(np.sqrt(np.mean(gap ** 2))), names[i]


def moving_leaves(ref_grad_sq: dict) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    _names, g = _flat(ref_grad_sq)
    return g >= 1e-3 * float(np.median(g))


def train_checks(prog: dict, ref: dict, limits: dict) -> dict:
    """prog / ref: {"losses": [..], "grad_sq": {...}, "grad_proj": {...},
    "change_sq": {...}}, and ref also "proj_sq" (a family's
    `reference_readings` gives all five; the names of the leaves are the
    family's). -> {name: [value, limit]} in the order they are printed."""
    out = {}
    # the program returns its loss in bfloat16 (10.875 +- 0.03 here): the
    # gap says nothing, has no upper reading and is not compared (PERF.md)
    loss_gaps = [abs(float(lp) - float(lr)) / abs(float(lr))
                 for lp, lr in zip(prog["losses"], ref["losses"])]
    out["loss_finite"] = [0.0 if np.all(np.isfinite(prog["losses"])) else 1.0, 0]
    g, g_leaf = norm_gap(prog["grad_sq"], ref["grad_sq"])
    out["grad_norm"] = [g, limits["grad_norm"]]
    gp, gp_leaf = projection_gap(prog["grad_proj"], ref["grad_proj"],
                                 ref["proj_sq"])
    out["grad_proj"] = [gp, limits["grad_proj"]]
    c, c_leaf = norm_gap(prog["change_sq"], ref["change_sq"],
                         keep=moving_leaves(ref["grad_sq"]))
    out["change_norm"] = [c, limits["change_norm"]]
    out["_worst"] = {"grad_norm": g_leaf, "grad_proj": gp_leaf,
                     "change_norm": c_leaf, "loss_gaps_not_compared": loss_gaps}
    return out


def verdict(checks: dict) -> bool:
    return all(np.isfinite(v[0]) and v[0] <= v[1]
               for k, v in checks.items() if not k.startswith("_"))


def report(checks: dict) -> None:
    """Each number compared beside its limit, on standard error."""
    for k, v in checks.items():
        if k.startswith("_"):
            print(f"[correct] worst leaves: {json.dumps(v)}", file=sys.stderr)
        else:
            ok = "ok" if (np.isfinite(v[0]) and v[0] <= v[1]) else "FAIL"
            print(f"[correct] {k} = {v[0]:.6g} (limit {v[1]:g}) {ok}",
                  file=sys.stderr)
    sys.stderr.flush()
