"""Device idle share of the traced window, in %: 1 - (union of the
intervals in which an operation ran) / (window length), read on the
busiest chip."""
from bench import trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    t0, t1 = trace_reduce.window(trace)
    busy = max(trace_reduce.busy_s(trace).values())
    return 100.0 * (1.0 - busy / (t1 - t0))
