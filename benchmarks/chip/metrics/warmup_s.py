"""Seconds the server spent warming its batch programs before it took
traffic (ServeStats.warmup_s): compile or cache load, and one run of
every batch size it warms."""


def read(ctx):
    return ctx["stats"]["warmup_s"]
