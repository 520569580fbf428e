"""Bytes read back from the device per real frame: the program's
``serve.d2h_bytes`` counter (every leaf of every batched output, padding
rows included) over the frames of the ``serve.d2h`` spans (their ``n``).
A ratio over both is not moved by the pre-roll."""

from spanread import store


def read(ctx):
    spans = store()
    if spans is None:
        return None
    frames = sum(r.ids["n"] for r in spans.records("serve.d2h"))
    nbytes = spans.counters().get("serve.d2h_bytes")
    if not frames or nbytes is None:
        return None
    return nbytes / frames
