"""Median host time of one batch's host-to-device transfer under full
batches, in milliseconds: the program's ``serve.h2d`` spans (stacking the
frames and putting the batch on the cell's chips), one of the host
thread's stages in a saturated server."""

from spanread import median_ms


def read(ctx):
    return median_ms("serve.h2d")
