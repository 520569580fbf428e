"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 100 * (1 - busy / window)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
