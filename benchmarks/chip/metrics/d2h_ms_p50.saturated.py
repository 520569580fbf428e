"""Median host time of one batch's device-to-host readback, in
milliseconds: the program's ``serve.d2h`` spans (the wait for the device
to finish the batch, the conversion of the batched output to host arrays
and the per-frame copies)."""

from spanread import median_ms


def read(ctx):
    return median_ms("serve.d2h")
