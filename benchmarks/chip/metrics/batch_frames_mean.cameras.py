"""Mean frames per dispatched batch over the window, from the server's
own counters (ServeStats.batch_frames / ServeStats.batches)."""


def read(ctx):
    s = ctx["stats"]
    if s["batches"] <= 0:
        return None
    return s["batch_frames"] / s["batches"]
