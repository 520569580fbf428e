"""Shared row-streaming plumbing for the Pallas kernels (the conv2d/sad
strip kernels and the megakernel emitter).

A streaming kernel walks the frame in row blocks: the grid iterates output
row blocks, inputs arrive as row strips through ``BlockSpec`` index maps
(strip i plus its halo neighbours), and every value inside the kernel is a
2-D ``(rows, width)`` array of a 32-bit dtype — the shape a TPU vector unit
works in.  The helpers here are the in-kernel window operations, written
only with forms the TPU's kernel compiler accepts: static slices,
concatenation, lane gathers within one 128-lane register, and ``repeat``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """The one interpret-mode decision for every Pallas kernel: kernels are
    interpreted exactly when the default backend is the CPU (tests, the
    sandbox); on an accelerator they always compile."""
    return jax.default_backend() == "cpu"


# rows per grid step for megakernel emission: deep enough to amortize the
# per-block overhead, shallow enough that stencil halos stay small
# multiples of it.  SUBLANES is the row tiling of a 32-bit TPU register: a
# row strip handed to a kernel through a BlockSpec must be a multiple.
MK_BLOCK_ROWS = 8
SUBLANES = 8
LANES = 128


def shift_lanes(x, k: int, fill=0):
    """``out[:, j] = x[:, j + k]``, ``fill`` where ``j + k`` leaves
    ``[0, width)`` — a stencil tap's column offset with the executor's
    zero fill outside the frame."""
    s, w = x.shape
    if k == 0:
        return x
    if abs(k) >= w:
        return jnp.full((s, w), fill, x.dtype)
    pad = jnp.full((s, abs(k)), fill, x.dtype)
    if k > 0:
        return jnp.concatenate([x[:, k:], pad], axis=1)
    return jnp.concatenate([pad, x[:, :w + k]], axis=1)


def pad_lanes(x, left: int, right: int, fill=0):
    s = x.shape[0]
    parts = ([jnp.full((s, left), fill, x.dtype)] if left else []) + [x] \
        + ([jnp.full((s, right), fill, x.dtype)] if right else [])
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def pick_rows(x, rows):
    """Rows ``x[rows[0]], x[rows[1]], ...`` (static indices) stacked in
    order: runs of one repeated source row become one broadcast."""
    parts, j = [], 0
    while j < len(rows):
        k = j
        while k + 1 < len(rows) and rows[k + 1] == rows[j]:
            k += 1
        r = rows[j]
        if k > j:
            parts.append(jnp.broadcast_to(x[r:r + 1], (k - j + 1,)
                                          + x.shape[1:]))
        else:
            parts.append(x[r:r + 1])
        j = k + 1
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def decimate_lanes(x, sx: int):
    """``x[:, ::sx]`` for a stride dividing the 128-lane register: output
    register k gathers every sx-th lane of input registers k*sx .. k*sx+sx-1
    (a per-register lane gather, which the TPU has; a strided lane slice it
    has not)."""
    if sx == 1:
        return x
    s, w = x.shape
    w_out = -(-w // sx)
    nv = -(-w // LANES)
    x = pad_lanes(x, 0, nv * LANES - w)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s, LANES), 1)
    idx = (lane * sx) % LANES
    outs = []
    for k in range(-(-w_out // LANES)):
        acc = None
        for q in range(sx):
            src = k * sx + q
            if src >= nv:
                break
            g = jnp.take_along_axis(x[:, src * LANES:(src + 1) * LANES],
                                    idx, axis=1)
            acc = g if acc is None else jnp.where(lane // (LANES // sx) == q,
                                                  g, acc)
        outs.append(acc)
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out[:, :w_out]


def nbytes(shape: Tuple[int, ...], dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * jnp.dtype(dtype).itemsize
