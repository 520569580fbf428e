"""Public wrapper: pads/aligns, invokes the Pallas kernel, crops."""
from __future__ import annotations

import jax.numpy as jnp

from ..stream import interpret_default
from .kernel import TILE_ROWS, conv2d_strips


def conv2d_stencil(p, k, shift: int = 11):
    """'Valid' convolution on a pre-padded image (see ref.py contract).

    p: (H + kh - 1, W + kw - 1) integer image; k: (kh, kw) coefficients.
    Returns (H, W) int32 == (conv >> shift) & 0xFF, bit-exact vs ref.py.
    """
    p = jnp.asarray(p, jnp.int32)
    k = jnp.asarray(k, jnp.int32)
    kh, kw = k.shape
    h = p.shape[0] - kh + 1
    w = p.shape[1] - kw + 1
    # align rows to TILE_ROWS and add one full halo strip; lanes stay as-is
    # (callers use W multiples of 128 in production; tests sweep odd sizes)
    h_pad = (-h) % TILE_ROWS
    rows_needed = h + h_pad + TILE_ROWS
    extra_rows = rows_needed - p.shape[0]
    p2 = jnp.pad(p, ((0, max(0, extra_rows)), (0, 0)))
    out = conv2d_strips(p2, k, kh=kh, kw=kw, w_out=w, shift=shift,
                        interpret=interpret_default())
    return out[:h]


def conv2d_hwimg_site(x, k, *, l: int, b: int, shift: int):
    """HWImg-site adapter (registry fusion ``conv2d``): implements the fused
    Stencil(l,r,b,t) -> Map(Mul)(., Const(k)) -> Reduce(Add) -> Rshift ->
    RemoveMSBs(->u8) subgraph on an (h, w) image.

    The stencil's arbitrary window offsets are realized by zero-fill
    pre-shifting (executor._np_stencil semantics), then the row-strip Pallas
    kernel runs its 0..kh-1 / 0..kw-1 tap loops on the shifted image.
    """
    from ..util import shift2d
    x = jnp.asarray(x, jnp.int32)
    k = jnp.asarray(k, jnp.int32)
    kh, kw = k.shape
    h, w = x.shape
    p = shift2d(x, b, l, h + kh - 1, w + kw - 1)
    return conv2d_stencil(p, k, shift=shift)
