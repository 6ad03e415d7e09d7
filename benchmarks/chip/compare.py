"""The numbers that decide ``correct``, each against a limit of its own.

Training: a run is summarised by its three losses, the norm of each
table's first gradient (its change after one step over the learning rate)
and the norm of each table's change after three steps. Two summaries are
compared leaf by leaf: the gap between the two norms, not the norm of
their difference, over the reference's norm of that leaf or of the median
leaf, whichever is larger, and the worst leaf counts. A leaf whose
reference gradient is under a thousandth of the median leaf's is left
out (it moves by round-off alone).

Serving: for each sampled request, the exact score of the i-th id served
must reach the exact i-th best score (``rank_gap``), the score served
must be the exact score of its id (``score_gap``), and the ids must be
distinct real rows.
"""
from __future__ import annotations

import numpy as np

LEAVES = ("vert", "ctx")


def train_numbers(run: dict, ref: dict) -> dict:
    """run/ref: {"loss": [l1, l2, l3], "grad": {leaf: norm},
    "change": {leaf: norm}}."""
    med_g = float(np.median([ref["grad"][t] for t in LEAVES]))
    keep = [t for t in LEAVES if ref["grad"][t] >= 1e-3 * med_g]
    med_c = float(np.median([ref["change"][t] for t in keep]))

    def worst(key, med):
        return max(abs(run[key][t] - ref[key][t]) / max(ref[key][t], med)
                   for t in keep)
    loss = max(abs(a - b) / abs(b) for a, b in zip(run["loss"], ref["loss"]))
    return {"loss_gap": float(loss),
            "grad_gap": float(worst("grad", med_g)),
            "change_gap": float(worst("change", med_c))}


def serve_numbers(ids, vals, ref_vals, exact_of_ids, valid: int) -> dict:
    """ids/vals: (Q, k) served; ref_vals: (Q, k) exact best scores;
    exact_of_ids: (Q, k) exact scores of the served ids."""
    ids = np.asarray(ids)
    bad = (ids < 0) | (ids >= valid)
    dup = np.array([len(set(r.tolist())) < r.size for r in ids])
    if bad.any() or dup.any():
        return {"rank_gap": float("inf"), "score_gap": float("inf")}
    rank = np.max(np.asarray(ref_vals) - np.asarray(exact_of_ids))
    score = np.max(np.abs(np.asarray(vals) - np.asarray(exact_of_ids)))
    return {"rank_gap": float(max(rank, 0.0)), "score_gap": float(score)}


def checks(numbers: dict, limits: dict) -> list[dict]:
    """One check per number; a number with no limit is an error."""
    out = []
    for name, value in numbers.items():
        limit = limits[name]
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": bool(np.isfinite(value) and value <= limit)})
    return out
