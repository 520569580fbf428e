"""Public wrapper for the SAD disparity kernel."""
from __future__ import annotations

import jax.numpy as jnp

from ..stream import interpret_default
from .kernel import plane_rows, sad_strips


def sad_disparity(l, r, *, nd: int = 64, bh: int = 8, bw: int = 8):
    """Best-match disparity per pixel (see ref.py contract) of 8-bit
    images: values in [0, 256), blocks at most 9 rows high."""
    l = jnp.asarray(l, jnp.int32)
    r = jnp.asarray(r, jnp.int32)
    h = l.shape[0] - bh + 1
    w = l.shape[1] - bw + 1 - (nd - 1)
    extra = plane_rows(h, l.shape[1]) - l.shape[0]
    if extra > 0:
        l = jnp.pad(l, ((0, extra), (0, 0)))
        r = jnp.pad(r, ((0, extra), (0, 0)))
    out = sad_strips(l, r, nd=nd, bh=bh, bw=bw, w_out=w,
                     interpret=interpret_default())
    return out[:h]


def sad_hwimg_site(left, right, *, nd: int, bh: int, bw: int):
    """HWImg-site adapter (registry fusion ``sad``): implements the fused
    Stencil(-(nd-1),0,0,0) -> Map(AbsDiff)(Replicate(left), .) ->
    Stencil(-(bw-1),0,-(bh-1),0) -> ReducePatch(Add) -> ArgMin subgraph on
    an (h, w) image pair (trailing-window STEREO form).

    Both images are placed at row offset bh-1 / column offset nd-1+bw-1 in
    zero-extended planes, which makes the kernel's tap reads reproduce the
    executor's per-level zero-fill exactly (out-of-range candidate reads
    hit zeros, out-of-range patch taps read |0-0|).
    """
    left = jnp.asarray(left, jnp.int32)
    right = jnp.asarray(right, jnp.int32)
    h, w = left.shape
    shape = (h + bh - 1, w + bw - 1 + nd - 1)
    L = jnp.zeros(shape, jnp.int32).at[bh - 1:, nd - 1 + bw - 1:].set(left)
    R = jnp.zeros(shape, jnp.int32).at[bh - 1:, nd - 1 + bw - 1:].set(right)
    return sad_disparity(L, R, nd=nd, bh=bh, bw=bw)
