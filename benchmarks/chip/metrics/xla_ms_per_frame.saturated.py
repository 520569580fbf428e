"""Device time of operations that are not Pallas kernels, per frame
completed in the traced window, in milliseconds: the carrier casts, pads,
slices and stacks the engine puts around kernels."""


def read(ctx):
    t = ctx.get("trace")
    frames = ctx.get("frames_completed", 0)
    if not t or t["busy_s"] <= 0 or frames <= 0:
        return None
    return 1e3 * t["xla_s"] / frames
