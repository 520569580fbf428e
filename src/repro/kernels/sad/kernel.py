"""Pallas TPU kernel: STEREO SAD block matching over nd disparities.

Row-strip tiling like conv2d (two strips = strip + halo).  The disparity
loop is a ``fori_loop`` carrying the running best cost and its disparity,
so only one disparity's (2*TILE_ROWS, W) working set is live in VMEM at a
time; the right strip is rotated by the disparity along the lanes (a lane
rotate is native on a TPU, a lane slice at a traced offset is not), and
the 8x8 block sum is separable — a row sum of the absolute differences,
then a lane sum — exact in int32.  The vector width of the paper's stereo
array (fig. 9) maps to the 128-lane W dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 8


def _sad_kernel(l_cur, l_nxt, r_cur, r_nxt, o_ref, *, nd, bh, bw, w_out):
    lf = jnp.concatenate([l_cur[...], l_nxt[...]], axis=0)
    rf = jnp.concatenate([r_cur[...], r_nxt[...]], axis=0)
    wp, wd = lf.shape[1], w_out + bw - 1
    lw = lf[:, nd - 1:nd - 1 + wd]

    def cost(d, carry):
        best, best_d = carry
        # rf rotated left by d: lane j holds rf[:, j + d] for j < wd
        rw = pltpu.roll(rf, (wp - d) % wp, 1)[:, :wd]
        diff = jnp.abs(lw - rw)
        rows = diff[0:TILE_ROWS]
        for dy in range(1, bh):
            rows = rows + diff[dy:dy + TILE_ROWS]
        acc = rows[:, 0:w_out]
        for dx in range(1, bw):
            acc = acc + rows[:, dx:dx + w_out]
        take = acc < best
        return jnp.where(take, acc, best), jnp.where(take, d, best_d)

    big = jnp.iinfo(jnp.int32).max
    init = (jnp.full((TILE_ROWS, w_out), big, jnp.int32),
            jnp.zeros((TILE_ROWS, w_out), jnp.int32))
    o_ref[...] = jax.lax.fori_loop(0, nd, cost, init)[1]


@functools.partial(jax.jit, static_argnames=("nd", "bh", "bw", "w_out",
                                             "interpret"))
def sad_strips(l, r, *, nd, bh, bw, w_out, interpret: bool = True):
    # a traced lane rotate needs whole 128-lane registers: zero-extend the
    # lanes (the cost reads never reach the extension)
    ext = (-l.shape[1]) % 128
    l = jnp.pad(l, ((0, 0), (0, ext)))
    r = jnp.pad(r, ((0, 0), (0, ext)))
    hp, wp = l.shape
    h = hp - TILE_ROWS
    assert h % TILE_ROWS == 0
    grid = (h // TILE_ROWS,)
    strip = lambda off: pl.BlockSpec((TILE_ROWS, wp),
                                     lambda i, off=off: (i + off, 0))
    with jax.enable_x64(False):         # 32-bit kernel, int32 index maps
        return pl.pallas_call(
            functools.partial(_sad_kernel, nd=nd, bh=bh, bw=bw,
                              w_out=w_out),
            grid=grid,
            in_specs=[strip(0), strip(1), strip(0), strip(1)],
            out_specs=pl.BlockSpec((TILE_ROWS, w_out), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((h, w_out), jnp.int32),
            interpret=interpret,
        )(l, l, r, r)
