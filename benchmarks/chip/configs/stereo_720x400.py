"""Plain reference for STEREO (HWTool paper, section 7): 8x8 SAD block
matching over 64 disparities on a uint8 image pair.

Independent of the system under test: numpy only, nothing imported from
the program.  It follows the app's HWImg definition:

- candidate ``d`` of pixel ``(y, x)`` is ``right[y, x - (nd-1) + d]``,
  zero outside the frame;
- the cost of candidate ``d`` is the sum of ``|left - candidate|`` over
  the 8x8 block whose bottom-right corner is the pixel (zero outside the
  frame), accumulated at 16 bits;
- the answer is the index of the first smallest cost (a u6 disparity).

Block sums use a summed-area table, exact in int64.

``control`` accumulates the block cost at 8 bits, the width below the
16 bits the configuration states.
"""
from __future__ import annotations

import numpy as np

BW = BH = 8


def _blocksum(x):
    h, w = x.shape
    s = np.zeros((h + BH, w + BW), dtype=np.int64)
    s[BH:, BW:] = x.cumsum(0).cumsum(1)
    return s[BH:, BW:] - s[:h, BW:] - s[BH:, :w] + s[:h, :w]


def _disparity(left, right, nd, acc_bits):
    left = np.asarray(left, np.int64)
    right = np.asarray(right, np.int64)
    h, w = left.shape
    ext = np.zeros((h, w + nd - 1), dtype=np.int64)
    ext[:, nd - 1:] = right
    mask = (1 << acc_bits) - 1
    best = best_d = None
    for d in range(nd):
        cost = _blocksum(np.abs(left - ext[:, d:d + w])) & mask
        if best is None:
            best, best_d = cost, np.zeros((h, w), dtype=np.int64)
        else:
            better = cost < best
            best = np.where(better, cost, best)
            best_d = np.where(better, d, best_d)
    return best_d


def reference(left, right, w=None, h=None, nd=64):
    """The disparity map: index of the first least 16-bit SAD cost."""
    return (_disparity(left, right, nd, 16),)


def control(left, right, w=None, h=None, nd=64):
    """The reference with the block cost accumulated at 8 bits."""
    return (_disparity(left, right, nd, 8),)


def dag(w, h, nd=64):
    """The app's HWImg nodes at their declared shapes, for the work count
    (work.py): |left - candidate| per disparity, widening to 16 bits, the
    8x8 block reduction per disparity, and the arg-min over disparities."""
    p = (h, w)
    return [("Map", p + (1, nd)),                          # AbsDiff
            ("Map", p + (1, nd)),                          # AddMSBs
            ("Reduce", p + (BH, BW, 1, nd)),               # ReducePatch
            ("Reduce", p + (1, nd))]                       # ArgMin


def io_bits(w, h, nd=64):
    """Declared widths: a u8 pair in, a u6 disparity out."""
    n = w * h
    return {"in": [(8, n), (8, n)], "out": [(6, n)]}
