"""Seconds of set-up spent in the program's compiler: the totals of its
``compile.pipeline`` (the HWTool mapping: interface, rates, modules, FIFO
solve) and ``compile.lower`` (IR, rewrite, partition, megakernel
emission) spans.  The totals are kept with the profiler on or off, so
the whole set-up is counted."""

from spanread import store


def read(ctx):
    spans = store()
    if spans is None:
        return None
    tot = spans.totals()
    parts = [tot[k][1] for k in ("compile.pipeline", "compile.lower")
             if k in tot]
    return sum(parts) if parts else None
