"""How late the open-loop driver ran, in ms: the 95th percentile, over
the windows dispatched in the traced window, of the time from the due
time of a window's last request to the window's dispatch."""
import numpy as np


def read(ctx):
    traced = ctx["run"].traced or {}
    lag = traced.get("lag_s")
    if lag is None or len(lag) == 0:
        return None
    return float(np.percentile(lag, 95) * 1e3)
