"""Device time of the routing programs per request routed, in us.

A routing program is one execution of the router's jitted entry,
recognised by its module name (``jit__route_batch`` on one chip,
``jit__sharded_route`` across chips); each routes one window of the
traffic file's ``window_requests``. Read over the executions that start
in the traced window, on the chip where the ratio is highest."""
from bench import trace_reduce

MODULES = ("jit__route_batch(", "jit__sharded_route(")


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    t0, t1 = trace_reduce.window(trace)
    per = ctx["run"].traced["requests_per_module"]
    best = None
    for dev in trace["devices"].values():
        durs = [d for s, d, name in trace_reduce.clipped(dev["modules"], t0, t1)
                if name.startswith(MODULES)]
        if durs:
            v = 1e6 * sum(durs) / (len(durs) * per)
            best = v if best is None else max(best, v)
    return best
