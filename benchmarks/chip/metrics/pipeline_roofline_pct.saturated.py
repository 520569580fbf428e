"""Least time of a frame's work on the chip over the device's busy time
per frame, in percent.

The least time is the larger of the frame's DAG operations at the peak
op rate and its declared-width input and output bytes at the peak HBM
rate (work.py, peaks.json).  Busy time counts every device operation,
Pallas kernels and XLA ops alike, over the frames completed in the
traced window, so work moved between a kernel and XLA neither inflates
the share nor drives it to infinity."""

import work


def read(ctx):
    t = ctx.get("trace")
    frames = ctx.get("frames_completed", 0)
    if not t or t["busy_s"] <= 0 or frames <= 0:
        return None
    least, _ = work.least_time_s(ctx["work"]["ops"], ctx["work"]["bytes"],
                                 work.peaks(ctx["kind"]))
    return 100.0 * least / (t["busy_s"] * t["chips"] / frames)
