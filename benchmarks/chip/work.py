"""Work of one frame at declared shapes and widths, and the least time the
chip could take for it.

Operations follow the configuration's HWImg DAG (``dag()`` in its
reference module): a Map costs 1 operation per output scalar, a Reduce or
ReducePatch (and the arg-min, a reduction) 1 per input scalar; geometry
nodes (Stencil, Pad, Replicate, Concat, ...) cost nothing and are not
listed.  Bytes are the pipeline's inputs and outputs at their declared
widths, once per frame, each scalar rounded up to whole bytes: a u8 is 1
byte, a u6 is 1 byte, an f32 is 4.  Neither depends on how the program
lowers the pipeline or on the carrier it uses.
"""
from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def ops_per_frame(dag) -> int:
    total = 0
    for op, shape in dag:
        if op not in ("Map", "Reduce"):
            raise ValueError(f"unknown node kind {op!r}")
        total += math.prod(shape)
    return total


def bytes_per_frame(io) -> int:
    return sum(-(-bits // 8) * n for bits, n in io["in"] + io["out"])


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (peaks.json); a device that
    is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return table[kind]


def least_time_s(ops: int, nbytes: int, peak: dict) -> tuple:
    """(seconds, bound): the larger of ops at peak op/s and bytes at peak
    HBM bytes/s, and which of the two it is."""
    t_ops = ops / peak["ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
