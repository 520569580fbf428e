"""Median time a frame waits before its batch is dispatched, in
milliseconds: the program's ``serve.queue`` intervals, from ``submit`` to
the start of the dispatch (admission, the request queue, the batcher's
hold)."""

from spanread import median_ms


def read(ctx):
    return median_ms("serve.queue")
