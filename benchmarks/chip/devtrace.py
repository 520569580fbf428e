"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The window is the host span the run opens over its measured window
(``run.WINDOW_SPAN``); everything is clipped to it.  On each device plane
(``/device:TPU:<n>``) the operations are the events of the ``XLA Ops``
line.  From them:

- ``busy_s``: the union of the operation intervals, averaged over chips;
- ``pallas_s`` / ``xla_s``: summed durations of Pallas kernels (XLA
  custom calls to ``tpu_custom_call``) and of every other operation, over
  all chips;
- ``device_ops``: total time per operation name, largest first;
- ``idle_gaps``: the gaps between busy intervals on the first chip,
  longest first, each named by the host event that overlaps it most
  (the runtime's own TraceMe events: dispatch, transfer, compile), or
  ``host: untraced`` where no host event covers it.
"""
from __future__ import annotations

import collections
import glob
import os

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
UNTRACED = "host: untraced"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


PALLAS = 'custom_call_target="tpu_custom_call"'


def is_pallas(name: str) -> bool:
    """A Pallas kernel: the op is a custom call to ``tpu_custom_call``
    (the op's event name is its HLO text)."""
    return PALLAS in name


def short_name(name: str) -> str:
    """The HLO op name of an event, ``%vmap_mk0_.1 = ...`` -> ``vmap_mk0_.1``."""
    return name.split(" = ", 1)[0].lstrip("%")


def window_span(planes, name: str):
    """(start_ns, end_ns) of the longest host event called ``name``."""
    best = None
    for pl in planes:
        if not pl.name.startswith(HOST_PREFIX):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name == name and (best is None
                                        or ev.duration_ns > best[1] - best[0]):
                    best = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if best is None:
        raise ValueError(f"no host span {name!r} in the trace")
    return best


def device_ops(planes, t0, t1):
    """{plane name: [(start, end, name, pallas)]} clipped to [t0, t1]."""
    out = {}
    for pl in planes:
        if not pl.name.startswith(DEVICE_PREFIX) or not pl.name[
                len(DEVICE_PREFIX):].isdigit():
            continue
        ops = []
        for ln in pl.lines:
            if ln.name != OPS_LINE:
                continue
            for ev in ln.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                s, e = max(s, t0), min(e, t1)
                if e > s:
                    ops.append((s, e, short_name(ev.name), is_pallas(ev.name)))
        out[pl.name] = ops
    return out


def host_events(planes, t0, t1, skip_prefix: str):
    evs = []
    for pl in planes:
        if not pl.name.startswith(HOST_PREFIX):
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.duration_ns <= 0 or ev.name.startswith(skip_prefix):
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > t0 and s < t1:
                    evs.append((s, e, ev.name))
    return evs


def label_gap(gs, ge, hosts) -> str:
    """The host event overlapping [gs, ge] most; the shorter on a tie."""
    best, key = UNTRACED, None
    for s, e, name in hosts:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        k = (ov, -(e - s))
        if key is None or k > key:
            best, key = name, k
    return best


def reduce_planes(planes, span_name: str) -> dict:
    planes = list(planes)
    t0, t1 = window_span(planes, span_name)
    per_dev = device_ops(planes, t0, t1)
    if not per_dev:
        raise ValueError("no TPU device plane in the trace")
    busy, pallas, xla = [], 0, 0
    by_name = collections.Counter()
    for ops in per_dev.values():
        busy.append(sum(e - s for s, e in merge((s, e) for s, e, _, _ in ops)))
        for s, e, name, pk in ops:
            by_name[name] += e - s
            if pk:
                pallas += e - s
            else:
                xla += e - s
    first = per_dev[sorted(per_dev)[0]]
    merged = merge((s, e) for s, e, _, _ in first)
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    hosts = host_events(planes, t0, t1, "bench.")
    idle = [[label_gap(s, e, hosts), (e - s) / 1e9] for s, e in gaps[:TOP]]
    return {
        "window_s": (t1 - t0) / 1e9,
        "chips": len(per_dev),
        "busy_s": sum(busy) / len(busy) / 1e9,
        "pallas_s": pallas / 1e9,
        "xla_s": xla / 1e9,
        "ops": sum(len(v) for v in per_dev.values()),
        "breakdown": {
            "device_ops": [[n, v / 1e9] for n, v in by_name.most_common(TOP)],
            "idle_gaps": idle,
        },
    }


def reduce_file(path: str, span_name: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, span_name)


def reduce_dir(trace_dir: str, span_name: str) -> dict:
    return reduce_file(find_xplane(trace_dir), span_name)
