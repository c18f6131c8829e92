"""The comparison that decides ``correct``.

Each sampled answer is a query's top-l (scores, rows) as the timed path
returned it. Against the reference's scores of every row, and its own
top l, one answer gives:

* ``score_err``: the widest gap between a returned score and the
  reference's score of the same row;
* ``topl_gap``: the widest gap between the reference's scores of the
  returned rows, sorted, and the reference's own top-l scores. It is 0
  to rounding when the same rows, or rows tied with them, came back, and
  grows when a worse row took a better one's place;
* ``recall``: the share of the returned rows that belong to the
  reference's top l: rows whose reference score is at most the l-th best
  times ``1 + tie`` (so rows tied with the l-th count);

the gaps as a share of the query's scale, the reference's l-th best
score. Over the sample, ``recall_miss`` is 1 less the mean recall.
An answer of the wrong shape, with a score that is not finite, or with a
row out of range or repeated, is a ``bad_answer`` (with recall 0); a
request that was never answered is ``lost``.
"""
from __future__ import annotations

import numpy as np

#: The numbers compared, in the order they are printed.
NUMBERS = ("score_err", "topl_gap", "bad_answers", "lost")

#: The numbers compared for a cascade fed by a candidate source, which is
#: approximate by design: its users are promised a recall, and recall
#: bounds what ``topl_gap`` would (that is printed as a reading).
SOURCED_NUMBERS = ("score_err", "recall_miss", "bad_answers", "lost")


def judge_answer(scores, rows, ref_scores, ref_top, n: int,
                 tie: float = 0.0) -> dict:
    """Numbers of one answer against the reference."""
    scores, rows = np.asarray(scores), np.asarray(rows)
    l = ref_top.shape[0]
    if (scores.shape != (l,) or rows.shape != (l,)
            or not np.isfinite(scores).all()
            or rows.min() < 0 or rows.max() >= n
            or np.unique(rows).shape[0] != l):
        return dict(score_err=0.0, topl_gap=0.0, recall=0.0, bad_answers=1)
    ref_scores = np.asarray(ref_scores, np.float64)
    scale = max(float(ref_top[-1]), 1e-12)
    at = ref_scores[rows]
    return dict(
        score_err=float(np.max(np.abs(scores - at)) / scale),
        topl_gap=float(np.max(np.abs(np.sort(at) - ref_top)) / scale),
        recall=float(np.mean(at <= ref_top[-1] * (1.0 + tie))),
        bad_answers=0)


def combine(per_answer: list[dict], lost: int) -> dict:
    """Widest gaps, the mean recall's miss and counts over the sample."""
    out = dict(score_err=0.0, topl_gap=0.0, recall_miss=0.0, bad_answers=0,
               lost=lost)
    for a in per_answer:
        out["score_err"] = max(out["score_err"], a["score_err"])
        out["topl_gap"] = max(out["topl_gap"], a["topl_gap"])
        out["bad_answers"] += a["bad_answers"]
    if per_answer:
        out["recall_miss"] = 1.0 - float(
            np.mean([a["recall"] for a in per_answer]))
    return out


def verdict(numbers: dict, limits: dict,
            names: tuple = NUMBERS) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when each of
    ``names`` is at most its limit."""
    missing = [k for k in names if k not in limits]
    if missing:
        raise ValueError(f"no limit for {missing}")
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in names}
    return all(numbers[k] <= limits[k] for k in names), checks
