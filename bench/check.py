"""The comparison that decides `correct`.

Every answer the server gave, warm-up and window alike, is compared with
the plain reference's logits for the pool image its request carried. So
the check covers the whole network and the server's batching: a padding
row returned, or an answer handed to the wrong request, reads as a large
error. Two numbers are compared, each with its limit:

  logit_err   the largest, over answers, of max|y - ref| / max|ref| of the
              answer's logits (a non-finite answer reads inf);
  unanswered  requests admitted that never reached an answer or an error
              (limit 0: an exact comparison).

Requests refused at admission, or answered with an error, are `failed`
and not for `correct`.
"""

from __future__ import annotations

import math

import numpy as np


def logit_err(y, ref) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    if y.shape != ref.shape or not np.all(np.isfinite(y)):
        return math.inf
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def numbers(requests, ref: dict[int, np.ndarray]) -> dict[str, float]:
    """`ref` maps a pool index to its reference logits."""
    err, unanswered = 0.0, 0
    for r in requests:
        t = r.ticket
        if t is None:
            continue
        if not t.done():
            unanswered += 1
        elif t.status == "ok":
            err = max(err, logit_err(t.result(0), ref[r.image]))
    return {"logit_err": err, "unanswered": unanswered}


def verdict(nums: dict[str, float], limits: dict[str, float]
            ) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): each number at or under its
    limit. A number without a limit fails."""
    lim = {"unanswered": 0, **limits}
    checks = {k: {"value": v, "limit": lim.get(k)} for k, v in nums.items()}
    ok = all(c["limit"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
