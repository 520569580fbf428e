"""Plain reference for FLOW (HWTool paper, section 7): dense Lucas-Kanade
optical flow on a uint8 image pair.

Independent of the system under test: numpy only, nothing imported from
the program.  It follows the app's HWImg definition node by node:

- 3x3 Sobel gradients of the first image (zero outside the frame),
  summed at 16 bits (i16 gradient);
- the temporal difference ``i2 - i1``;
- five 8x8 window sums (window ending at the pixel, zero outside) of the
  products ``ix*ix, ix*iy, iy*iy, ix*it, iy*it``, each product wrapped to
  32 bits, the sum exact;
- the 2x2 solve in float32, every operation rounded to float32:
  ``u = (a12*b2 - a22*b1) / det``, ``v = (a12*b1 - a11*b2) / det`` with
  ``det = a11*a22 - a12*a12``, and 0 where ``det == 0``.

Window sums use a summed-area table, which is exact in int64 and gives
the same integers as summing the 64 taps.

``control`` is the same computation with the float stage in bfloat16, the
precision below the float32 the configuration states.
"""
from __future__ import annotations

import numpy as np

WIN = 8
SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], dtype=np.int64)
SOBEL_Y = SOBEL_X.T.copy()


def _wrap(x, bits):
    half = 1 << (bits - 1)
    return ((x + half) & ((1 << bits) - 1)) - half


def _grad(img, k):
    h, w = img.shape
    ext = np.zeros((h + 2, w + 2), dtype=np.int64)
    ext[1:1 + h, 1:1 + w] = img
    g = np.zeros((h, w), dtype=np.int64)
    for dy in range(3):
        for dx in range(3):
            if k[dy, dx]:
                g += ext[dy:dy + h, dx:dx + w] * k[dy, dx]
    return _wrap(g, 16)


def _winsum(x):
    """Sum over the 8x8 window whose bottom-right corner is the pixel."""
    h, w = x.shape
    s = np.zeros((h + WIN, w + WIN), dtype=np.int64)
    s[WIN:, WIN:] = x.cumsum(0).cumsum(1)
    return s[WIN:, WIN:] - s[:h, WIN:] - s[WIN:, :w] + s[:h, :w]


def _moments(i1, i2):
    i1 = np.asarray(i1, np.int64)
    i2 = np.asarray(i2, np.int64)
    ix, iy = _grad(i1, SOBEL_X), _grad(i1, SOBEL_Y)
    it = i2 - i1
    return [_winsum(_wrap(p, 32)) for p in
            (ix * ix, ix * iy, iy * iy, ix * it, iy * it)]


def _solve(sums, ft):
    a11, a12, a22, b1, b2 = (s.astype(np.float32).astype(ft) for s in sums)
    det = (a11 * a22).astype(ft) - (a12 * a12).astype(ft)
    nu = (a12 * b2).astype(ft) - (a22 * b1).astype(ft)
    nv = (a12 * b1).astype(ft) - (a11 * b2).astype(ft)
    safe = np.where(det == 0, ft(1), det).astype(ft)
    u = np.where(det != 0, (nu / safe).astype(ft), ft(0))
    v = np.where(det != 0, (nv / safe).astype(ft), ft(0))
    return u.astype(np.float32), v.astype(np.float32)


def reference(i1, i2, w=None, h=None):
    """(u, v) float32 planes of the frame pair."""
    return _solve(_moments(i1, i2), np.float32)


def control(i1, i2, w=None, h=None):
    """The reference with its float stage computed in bfloat16."""
    import ml_dtypes
    return _solve(_moments(i1, i2), ml_dtypes.bfloat16)


def dag(w, h):
    """The app's HWImg nodes at their declared shapes, for the work count
    (work.py): 3x3 gradients, temporal difference, five 8x8 window sums
    (widen + reduce), five int-to-float conversions, the 2x2 solve."""
    p = (h, w)
    nodes = []
    for _ in range(2):                                     # ix, iy
        nodes += [("Map", p + (3, 3)), ("Reduce", p + (3, 3))]
    nodes += [("Map", p)]                                  # it = i2 - i1
    for _ in range(5):                                     # window sums
        nodes += [("Map", p),                              # product
                  ("Map", p + (WIN, WIN)),                 # AddMSBs
                  ("Reduce", p + (WIN, WIN))]
    nodes += [("Map", p)] * 5                              # ToFloat
    nodes += [("Map", p)] * 9                              # det, nu, nv
    nodes += [("Map", p)] * 2                              # u, v divides
    return nodes


def io_bits(w, h):
    """Declared widths of the pipeline's inputs and outputs, in bits per
    scalar with the scalar count: a u8 pair in, two f32 planes out."""
    n = w * h
    return {"in": [(8, n), (8, n)], "out": [(32, n), (32, n)]}
