"""Frame-axis sharding for the serving layer.

A stacked batch carries frames on axis 0; with more than one device the
axis is laid out across a 1-d ``jax.sharding.Mesh`` ("frames") and the
engine runs every vmapped pipeline program once per device on its local
frames (``shard_map``: a Pallas kernel is not partitioned by XLA's SPMD
partitioner, so the split is made explicit).  With a single device (the
common CPU CI case) everything degrades transparently to a plain committed
``device_put`` — callers never branch on device count.

Transfers run under the x64 context so int64 frame buffers keep the
executor's integer carrier width instead of being canonicalized to int32.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.lowering.engine import FRAME_AXIS


def frame_sharding(devices=None) -> Optional[NamedSharding]:
    """NamedSharding that splits axis 0 ("frames") across ``devices``
    (default: all local devices), or None for the single-device fallback."""
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) <= 1:
        return None
    mesh = Mesh(np.array(devs), (FRAME_AXIS,))
    return NamedSharding(mesh, PartitionSpec(FRAME_AXIS))


def pad_frames(batch: Dict[str, Any], multiple: int
               ) -> Tuple[Dict[str, Any], int]:
    """Pad the frame axis up to a multiple of ``multiple`` by repeating the
    last frame (rows are independent under vmap); returns (batch, n_real)."""
    def n_of(v):
        return (v[0] if isinstance(v, tuple) else v).shape[0]

    n = n_of(next(iter(batch.values())))
    pad = (-n) % multiple
    if pad == 0:
        return batch, n

    def ext(v):
        if isinstance(v, tuple):
            return tuple(ext(e) for e in v)
        a = np.asarray(v)
        return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])

    return {k: ext(v) for k, v in batch.items()}, n


def device_put_batch(batch: Dict[str, Any],
                     sharding: Optional[NamedSharding]
                     ) -> Tuple[Dict[str, Any], int]:
    """Start the (asynchronous) host→device transfer of a stacked batch,
    sharded on the frame axis when a multi-device sharding is given.
    Returns ``(device_batch, n_real)`` — the frame axis may have been
    padded to a multiple of the device count for an even layout."""
    n_dev = len(sharding.mesh.devices.flat) if sharding is not None else 1
    if n_dev > 1:
        batch, n = pad_frames(batch, n_dev)
    else:
        v = next(iter(batch.values()))
        n = (v[0] if isinstance(v, tuple) else v).shape[0]

    def put(v):
        if isinstance(v, tuple):
            return tuple(put(e) for e in v)
        if sharding is not None:
            return jax.device_put(v, sharding)
        return jax.device_put(v)

    with jax.enable_x64(True):
        return {k: put(v) for k, v in batch.items()}, n
