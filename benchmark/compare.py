"""The comparison that decides `correct`: numbers, each beside its limit.

Training: a candidate's readings (the program's, or the reference's at a
lower precision or with a fault planted) against the float32 reference's.
Serving: served tokens against the reference's logits. The limits live in
the workload's file; how they were set is in PERF.md.
"""
import statistics

import numpy as np

from . import weights


def flatten(tree, n_layers, to=float):
    """Stacked host tree of per-leaf numbers (or rows) -> {program name:
    float (or row)}."""
    return {prog: to(tree[leaf] if i is None else tree["blocks"][leaf][i])
            for prog, leaf, i in weights.flat_names(n_layers)}


def _by_leaf(err, size, leaves):
    """Every leaf's `err` against the reference's `size` of that leaf or
    of the median leaf, whichever is larger (some gradients are all but
    zero); a reading that is no number counts as infinite."""
    med = statistics.median(size[k] for k in leaves)
    out = {k: err[k] / max(size[k], med) for k in leaves}
    return {k: x if np.isfinite(x) else float("inf") for k, x in out.items()}


def _worst(by_leaf):
    """(value, leaf) of the worst leaf."""
    leaf = max(by_leaf, key=by_leaf.get)
    return by_leaf[leaf], leaf


def _norm_gaps(cand, ref):
    """Per leaf the gap between the candidate's norm and the reference's
    (not the norm of a difference)."""
    return {k: abs(cand[k] - r) for k, r in ref.items()}


def _norm(row):
    return float(np.linalg.norm(np.asarray(row, np.float64)))


def train_numbers(cand, ref):
    """cand/ref: {"loss": [3], "grad_norm": {leaf: x}, "grad_sample": {leaf:
    row}, "delta_norm": {...}} (flattened). Returns ({number: value},
    {number: worst leaf})."""
    loss_gap = max(abs(float(c) - float(r)) / abs(float(r))
                   if np.isfinite(c) else float("inf")
                   for c, r in zip(cand["loss"], ref["loss"]))
    leaves = sorted(ref["grad_norm"])
    grad_gap, g_leaf = _worst(_by_leaf(
        _norm_gaps(cand["grad_norm"], ref["grad_norm"]), ref["grad_norm"],
        leaves))
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: a rule on the reference's gradient
    g_med = statistics.median(ref["grad_norm"].values())
    moved = [k for k in leaves if ref["grad_norm"][k] >= 1e-3 * g_med]
    delta = _by_leaf(_norm_gaps(cand["delta_norm"], ref["delta_norm"]),
                     ref["delta_norm"], moved)
    delta_gap, d_leaf = _worst(delta)
    # the worst leaf is one small bias whose change swings with rounding
    # and hides a lower precision; among the leaves of two dimensions the
    # rounding averages out (PERF.md section 2)
    delta_gap_matrix, m_leaf = _worst(
        {k: x for k, x in delta.items() if weights.is_matrix(k)})
    # direction as well as length: the norm of the DIFFERENCE of the first
    # gradients, on an evenly spaced sample of each leaf's elements
    diff = {k: _norm(np.asarray(cand["grad_sample"][k], np.float64)
                     - np.asarray(ref["grad_sample"][k], np.float64))
            for k in leaves}
    size = {k: _norm(ref["grad_sample"][k]) for k in leaves}
    grad_diff, s_leaf = _worst(_by_leaf(diff, size, leaves))
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "grad_diff": grad_diff, "delta_gap": delta_gap,
             "delta_gap_matrix": delta_gap_matrix},
            {"grad_gap": g_leaf, "grad_diff": s_leaf, "delta_gap": d_leaf,
             "delta_gap_matrix": m_leaf})


def token_gaps(ref_logits, prompt_len, tokens):
    """ref_logits [s, rows] of prompt + served tokens; for every served
    token how far its logit lies below the reference's best."""
    rows = ref_logits[prompt_len - 1: prompt_len - 1 + len(tokens)]
    best = rows.max(axis=-1)
    return best - rows[np.arange(len(tokens)), np.asarray(tokens)]


def verdict(numbers, limits):
    """[(name, value, limit)] of every number the workload's file sets a
    limit for, whether each is within it, and the numbers it sets none for
    (shown, not compared). A limit for a number that was not read is an
    error; a limit is never guessed at run time."""
    checked = [(name, float(numbers[name]), float(lim))
               for name, lim in limits.items()]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in checked)
    shown = {k: float(v) for k, v in numbers.items() if k not in limits}
    return checked, ok, shown
