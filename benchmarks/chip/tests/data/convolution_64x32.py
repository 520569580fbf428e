"""Plain reference for CONVOLUTION (HWTool paper, section 7): an 8x8
convolution of one uint8 image, for the harness's tests of one-image
pipelines.

Independent of the system under test: numpy only.  It follows the app's
HWImg definition: the image padded by 8 columns left and right and 4 rows
above and below, the 8x8 window whose bottom-right corner is the pixel,
products summed in a 32-bit accumulator, shifted right by 11, kept to 8
bits, and cropped back to the image's size.  The coefficients are the
app's fixed kernel: 8x8 draws in [1, 64) from ``RandomState(0)``, scaled
to sum below 2**11.

``control`` accumulates at 16 bits, the width below the 32 bits the
configuration states.
"""
from __future__ import annotations

import numpy as np

K = 8
SHIFT = 11


def _kernel():
    k = np.random.RandomState(0).randint(1, 64, size=(K, K)).astype(np.int64)
    return np.clip(k * (2 ** SHIFT - 1) // k.sum(), 0, 255)


def _convolve(img, acc_bits):
    h, w = img.shape
    ext = np.zeros((h + 8 + K - 1, w + 16 + K - 1), dtype=np.int64)
    ext[K - 1 + 4:K - 1 + 4 + h, K - 1 + 8:K - 1 + 8 + w] = img
    win = np.lib.stride_tricks.sliding_window_view(ext, (K, K))
    sums = np.einsum("hwij,ij->hw", win, _kernel()) & ((1 << acc_bits) - 1)
    return ((sums >> SHIFT) & 0xFF)[:h, 12:12 + w]


def reference(img, w=None, h=None):
    return (_convolve(img, 32),)


def control(img, w=None, h=None):
    return (_convolve(img, 16),)
