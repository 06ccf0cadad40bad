"""A number the load generator's own clock gave (latency percentiles from due
time, how late it sent). args: ``key``."""


def read(ctx, key):
    return ctx["gen"].get(key)
