"""The comparison that decides ``correct``.

Each answer the window produced is set beside the plain reference's: the
same rows valid, the same key in each, and each score within the limit.
The numbers, each with its limit:

- ``missing``: answers that never came (a failed query); limit 0.
- ``rows_wrong``: rows whose validity or key differs from the reference's;
  an exact comparison, limit 0.  Where the query filters on the model's
  own output (``PREDICT(...) > 7``), a row whose reference score lies
  within the score limit of the threshold may fall on either side in a
  sound run, and its validity is not compared; its score still is.
- ``score_gap``: the widest gap between a served score and the
  reference's, as ``|got - ref| / (1 + |ref|)``; its limit is the
  configuration's, set from the readings in ``PERF.md``.
"""

from __future__ import annotations

import operator
from typing import Dict, List, Optional, Tuple

import numpy as np

# (rows that pass the relational WHERE, every row's key, the reference's
# score of each such row (NaN elsewhere), the served answer's host columns)
Want = Tuple[np.ndarray, np.ndarray, np.ndarray, Dict[str, np.ndarray]]

OPS = {">": operator.gt, ">=": operator.ge, "<": operator.lt,
       "<=": operator.le}


def where_mask(rows: Dict[str, np.ndarray], where: Dict[str, float]
               ) -> np.ndarray:
    """Rows that satisfy every ``column = value`` of the query's WHERE."""
    n = len(next(iter(rows.values())))
    mask = np.ones(n, bool)
    for col, value in where.items():
        mask &= rows[col] == value
    return mask


def output_mask(candidates: np.ndarray, scores: np.ndarray,
                output_filter: Optional[dict]) -> np.ndarray:
    """The rows an answer holds: the candidates whose score passes the
    query's filter on the model's output, if it has one."""
    if not output_filter:
        return candidates.copy()
    passes = OPS[output_filter["op"]](
        np.where(candidates, scores, np.inf if output_filter["op"][0] == "<"
                 else -np.inf), output_filter["value"])
    return candidates & passes


def near_threshold(candidates: np.ndarray, ref: np.ndarray,
                   output_filter: Optional[dict], score_gap: float
                   ) -> np.ndarray:
    """Candidates whose reference score lies within the score limit of the
    output filter's threshold: a sound answer may hold them or not."""
    if not output_filter:
        return np.zeros_like(candidates)
    thr = float(output_filter["value"])
    return candidates & (np.abs(np.where(candidates, ref, np.inf) - thr)
                         <= score_gap * (1.0 + abs(thr)))


def numbers(answers: List[Want], key: str, output: str,
            limits: Dict[str, float], missing: int,
            output_filter: Optional[dict] = None
            ) -> Dict[str, Dict[str, float]]:
    wrong, gap = 0, 0.0
    for cand, keys, ref, got in answers:
        valid = got["__valid__"]
        if valid.shape != cand.shape:
            wrong += max(len(valid), len(cand))
            continue
        want = output_mask(cand, ref, output_filter)
        near = near_threshold(cand, ref, output_filter, limits["score_gap"])
        wrong += int(((valid != want) & ~near).sum())
        # keys and scores of the rows the answer holds that the reference
        # scores; a served row outside them is wrong above
        both = valid & cand
        wrong += int((got[key][both] != keys[both]).sum())
        served = got[output][both].astype(np.float64).reshape(-1)
        r = ref[both].astype(np.float64)
        if served.size:
            gap = max(gap, float(np.max(np.abs(served - r)
                                        / (1.0 + np.abs(r)))))
    return {
        "missing": {"value": missing, "limit": 0},
        "rows_wrong": {"value": wrong, "limit": 0},
        "score_gap": {"value": gap if answers else None,
                      "limit": limits["score_gap"]},
    }


def correct(numbers: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in numbers.values())
