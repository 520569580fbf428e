"""Pallas TPU kernel: 8x8 stencil convolution with row-strip tiling.

TPU adaptation of the paper's CONVOLUTION pipeline (DESIGN.md §2): the
FPGA line buffer becomes a VMEM row strip; the Rigel2-solved vector width
becomes the lane dimension (W, multiple of 128); the halo rows that the
FPGA holds in BRAM are expressed as a second row-strip block, so each grid
step sees its 8 output rows plus the 7 halo rows below without overlapping
DMA.

Grid: (H / TILE_ROWS,). For output strip i we read input strips i and i+1
(TILE_ROWS rows each): output rows [8i, 8i+8) need padded-input rows
[8i, 8i+15).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_ROWS = 8


def _conv_kernel(x_cur_ref, x_nxt_ref, k_ref, o_ref, *, kh: int, kw: int,
                 w_out: int, shift: int):
    a = x_cur_ref[...]                    # (TILE_ROWS, Wp) int32
    b = x_nxt_ref[...]                    # (TILE_ROWS, Wp) int32
    full = jnp.concatenate([a, b], axis=0)   # (2*TILE_ROWS, Wp)
    k = k_ref[...]                        # (kh, kw) int32
    acc = jnp.zeros((TILE_ROWS, w_out), jnp.int32)
    for dy in range(kh):                  # unrolled taps: VPU adds over the
        for dx in range(kw):              # 128-lane W dimension
            acc = acc + k[dy, dx] * full[dy:dy + TILE_ROWS,
                                         dx:dx + w_out]
    o_ref[...] = (acc >> shift) & 0xFF


@functools.partial(jax.jit,
                   static_argnames=("kh", "kw", "w_out", "shift",
                                    "interpret"))
def conv2d_strips(p: jnp.ndarray, k: jnp.ndarray, *, kh: int, kw: int,
                  w_out: int, shift: int, interpret: bool = True):
    """p: padded input (Hp, Wp) int32 with Hp = H + TILE_ROWS (one extra
    strip of halo rows), Wp >= w_out + kw - 1. Returns (H, w_out) int32."""
    hp, wp = p.shape
    h = hp - TILE_ROWS
    assert h % TILE_ROWS == 0, (h, TILE_ROWS)
    grid = (h // TILE_ROWS,)
    with jax.enable_x64(False):         # 32-bit kernel, int32 index maps
        return pl.pallas_call(
            functools.partial(_conv_kernel, kh=kh, kw=kw, w_out=w_out,
                              shift=shift),
            grid=grid,
            in_specs=[
                pl.BlockSpec((TILE_ROWS, wp), lambda i: (i, 0)),  # strip i
                pl.BlockSpec((TILE_ROWS, wp), lambda i: (i + 1, 0)),  # halo
                pl.BlockSpec((kh, kw), lambda i: (0, 0)),         # coeffs
            ],
            out_specs=pl.BlockSpec((TILE_ROWS, w_out), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((h, w_out), jnp.int32),
            interpret=interpret,
        )(p, p, k)
