"""Median host time of one batch's host-to-device transfer, in
milliseconds: the program's ``serve.h2d`` spans (stacking the frames and
putting the batch on the device)."""

from spanread import median_ms


def read(ctx):
    return median_ms("serve.h2d")
