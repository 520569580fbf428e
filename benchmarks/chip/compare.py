"""The comparison that decides ``correct``: a served frame against the plain
reference's answer for the same inputs.

Each reading is a number with a limit from the configuration
(``limits`` in its JSON file); the run is correct only when every reading
is at or under its limit:

- ``max_ulp``: the largest distance, in float32 units in the last place,
  between a float leaf and the reference (NaN where the reference has no
  NaN, or the reverse, reads as 2**32);
- ``mismatched``: integer elements that differ from the reference, summed
  over the frames compared (values are compared, not the carrier dtype);
- ``unanswered``: frames sampled for the check whose answer never came.

A leaf whose shape or count differs from the reference reads as 2**32 on
both numbers.
"""
from __future__ import annotations

import numpy as np

WRONG = 2 ** 32


def leaves(v):
    return [np.asarray(e) for e in (v if isinstance(v, tuple) else (v,))]


def ulp_distance(a, b) -> int:
    def lex(x):
        u = np.asarray(x, np.float32).view(np.uint32).astype(np.int64)
        return np.where(u < 2 ** 31, u + 2 ** 31, 2 ** 32 - u)
    return int(np.max(np.abs(lex(a) - lex(b)), initial=0))


def frame_readings(got, ref) -> dict:
    """{"max_ulp": ..., "mismatched": ...} of one frame."""
    g, r = leaves(got), leaves(ref)
    if len(g) != len(r) or any(a.shape != b.shape for a, b in zip(g, r)):
        return {"max_ulp": WRONG, "mismatched": WRONG}
    ulp = mism = 0
    for a, b in zip(g, r):
        if b.dtype.kind == "f":
            if a.dtype.kind != "f" or not np.array_equal(np.isnan(a),
                                                         np.isnan(b)):
                ulp = WRONG
            else:
                ulp = max(ulp, ulp_distance(a, b))
        else:
            if a.dtype.kind not in "iub":
                mism += a.size
            else:
                mism += int(np.count_nonzero(
                    a.astype(np.int64) != b.astype(np.int64)))
    return {"max_ulp": ulp, "mismatched": mism}


def readings(pairs, unanswered: int) -> dict:
    """Readings over (served, reference) pairs: the worst ULP distance,
    the mismatched elements summed, and the unanswered count."""
    out = {"max_ulp": 0, "mismatched": 0, "unanswered": unanswered}
    for got, ref in pairs:
        r = frame_readings(got, ref)
        out["max_ulp"] = max(out["max_ulp"], r["max_ulp"])
        out["mismatched"] = min(WRONG, out["mismatched"] + r["mismatched"])
    return out


def verdict(values: dict, limits: dict) -> tuple:
    """(correct, checks): every limited reading beside its limit; the
    unanswered count is always limited to 0."""
    limits = dict(limits, unanswered=0)
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
