"""Pallas TPU kernel: STEREO SAD block matching over nd disparities.

Row tiles taller than the halo.  One grid step (per frame, under vmap)
computes ``T`` output rows: it reads a ``T``-row block of each image and
the ``HALO``-row block below it, so the absolute differences run on
``T + HALO`` rows for ``T`` rows of output.  ``T`` follows from the shape
the kernel is handed (``row_tile``): the fewest steps of at most
``MAX_TILE`` rows, and of at most what a VMEM budget allows at the padded
width, each a multiple of ``HALO``, balanced so that the last pads least.
A short image is one step.

Per disparity, in ascending ``d`` (a ``fori_loop`` over ``nd / unroll``
trips of ``unroll`` disparities each; a strict ``<`` keeps the first
least cost):

- the right tile, carried in the loop, turns one lane left (a static lane
  rotate), so lane ``j`` holds ``R[j + d]``; the left tile was turned once
  so that lane ``j`` holds ``L[j + nd - 1]``.  Both read only lanes that
  are real or zero-extended for every column that reaches an output;
- ``|L - R|`` on the lanes that reach an output;
- the row box sum is one matmul on the MXU with a 0/1 band of shape
  ``(T, T + HALO)``: exact, since ``|L - R| <= 255`` is exact in bfloat16
  and the at most ``bh`` terms add up in float32;
- the lane box sum is log-step: doubling sums ``x + roll(x, 1)``,
  ``s2 + roll(s2, 2)``, ``s4 + roll(s4, 4)`` (lane rotates), combined by
  the binary digits of ``bw``: three rotates for 8 lanes, where a slice at
  each offset would cost seven unaligned relayouts;
- the running least cost (float32, exact below 2^24) and its disparity.

Nothing is sliced at an unaligned offset inside the loop.  The vector
width of the paper's stereo array (fig. 9) maps to the 128-lane W axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HALO = 8                    # rows read below a tile: the block is bh <= 9 high
MAX_TILE = 40               # output rows a step, at most
VMEM_BUDGET = 16 << 20      # a v5e kernel's default scoped VMEM, in bytes
# (T + HALO, lanes) 32-bit planes a step keeps in VMEM, with twice the
# room the v5e compiler shows: it refuses 88 rows at 3 968 lanes
LIVE_PLANES = 24
MAX_UNROLL = 2              # disparities a loop trip, at most


def lanes(w: int) -> int:
    """Lanes of a w-wide plane zero-extended to whole 128-lane registers."""
    return -(-w // 128) * 128


def row_tile(h: int, w: int) -> int:
    """Output rows a grid step, for an ``h``-row output from ``w``-wide
    planes: the fewest steps of at most the cap, balanced, each a
    multiple of ``HALO`` rows."""
    fit = VMEM_BUDGET // (LIVE_PLANES * 4 * lanes(w)) - HALO
    cap = max(HALO, min(MAX_TILE, fit // HALO * HALO))
    steps = -(-h // cap)
    return -(-h // (steps * HALO)) * HALO


def plane_rows(h: int, w: int) -> int:
    """Rows the kernel's planes have for an ``h``-row output: whole tiles
    and one halo block below the last."""
    t = row_tile(h, w)
    return -(-h // t) * t + HALO


def _box_lanes(x, n):
    """``y[:, j] = x[:, j] + ... + x[:, j + n - 1]`` by log steps (lanes
    within ``n - 1`` of the end wrap around and are not used)."""
    wp = x.shape[1]
    total, span, off, k = None, x, 0, 1
    while True:
        if n & k:
            part = span if off == 0 else pltpu.roll(span, wp - off, 1)
            total = part if total is None else total + part
            off += k
        if 2 * k > n:
            return total
        span = span + pltpu.roll(span, wp - k, 1)
        k *= 2


def _sad_kernel(l_tile, l_halo, r_tile, r_halo, o_ref, *, nd, bh, bw,
                unroll):
    t, w_out = o_ref.shape
    lf = jnp.concatenate([l_tile[...], l_halo[...]], axis=0)
    rf = jnp.concatenate([r_tile[...], r_halo[...]], axis=0)
    lf, rf = lf.astype(jnp.float32), rf.astype(jnp.float32)
    rows, wp = lf.shape
    wc = min(wp, lanes(w_out + bw - 1))         # lanes that reach an output
    lw = (pltpu.roll(lf, wp - (nd - 1), 1) if nd > 1 else lf)[:, :wc]
    i = jax.lax.broadcasted_iota(jnp.int32, (t, rows), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (t, rows), 1)
    band = ((k >= i) & (k < i + bh)).astype(jnp.bfloat16)

    def step(d, carry):
        rw, best, best_d = carry
        diff = jnp.abs(lw - rw[:, :wc]).astype(jnp.bfloat16)
        acc = _box_lanes(jnp.dot(band, diff, preferred_element_type=jnp.float32),
                         bw)[:, :w_out]
        take = acc < best
        return (pltpu.roll(rw, wp - 1, 1), jnp.where(take, acc, best),
                jnp.where(take, d, best_d))

    def trip(n, carry):
        for j in range(unroll):
            carry = step(n * unroll + j, carry)
        return carry

    init = (rf, jnp.full((t, w_out), jnp.inf, jnp.float32),
            jnp.zeros((t, w_out), jnp.int32))
    o_ref[...] = jax.lax.fori_loop(0, nd // unroll, trip, init)[2]


@functools.partial(jax.jit, static_argnames=("nd", "bh", "bw", "w_out",
                                             "interpret"))
def sad_strips(l, r, *, nd, bh, bw, w_out, interpret: bool = True):
    """Disparities of the ``(rows - HALO, w_out)`` output from int32 planes
    of ``plane_rows`` rows whose values lie in [0, 256)."""
    if bh - 1 > HALO:
        raise ValueError(f"a {bh}-row block reaches past the {HALO}-row halo")
    w = l.shape[1]
    # a lane rotate needs whole 128-lane registers: zero-extend the lanes
    # (no cost read for a real output column reaches the extension's end)
    ext = lanes(w) - w
    l = jnp.pad(l, ((0, 0), (0, ext)))
    r = jnp.pad(r, ((0, 0), (0, ext)))
    hp, wp = l.shape
    h = hp - HALO
    t = row_tile(h, w)
    assert h % t == 0, (h, t)
    tile = pl.BlockSpec((t, wp), lambda i: (i, 0))
    halo = pl.BlockSpec((HALO, wp), lambda i: ((i + 1) * (t // HALO), 0))
    with jax.enable_x64(False):         # 32-bit kernel, int32 index maps
        return pl.pallas_call(
            functools.partial(_sad_kernel, nd=nd, bh=bh, bw=bw,
                              unroll=math.gcd(nd, MAX_UNROLL)),
            grid=(h // t,),
            in_specs=[tile, halo, tile, halo],
            out_specs=pl.BlockSpec((t, w_out), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((h, w_out), jnp.int32),
            interpret=interpret,
        )(l, l, r, r)
