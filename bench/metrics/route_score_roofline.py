"""Share of its roofline that the route-score kernel reached, in %.

The kernel's events are the device's Pallas custom calls
(``tpu_custom_call``) whose result is the ``(chunk, N)`` score panel,
padded to the kernel's 128-lane tiles, or whose name says
``route_score``. Their summed device time in the traced window is set
against the least time the chip needs for the same number of panels
(``bench.roofline``: bytes bound on a TPU v5e)."""
from bench import roofline, trace_reduce


def _is_kernel(name: str, c: int, n_pad: int) -> bool:
    if "tpu_custom_call" not in name:
        return False
    result = name.split(" = ", 1)[-1]
    return "route_score" in name or result.startswith(f"f32[{c},{n_pad}]")


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    t0, t1 = trace_reduce.window(trace)
    c = ctx["run"].chunk
    n = len(ctx["table"]["flops"])
    n_pad = -(-n // 128) * 128
    durs = [d for dev in trace["devices"].values()
            for s, d, name in trace_reduce.clipped(dev["ops"], t0, t1)
            if _is_kernel(name, c, n_pad)]
    if not durs or sum(durs) <= 0:
        return None
    least, _ = roofline.least_time_s(roofline.route_score_work(c, n),
                                     roofline.peaks(ctx["device_kind"]))
    return 100.0 * least * len(durs) / sum(durs)
