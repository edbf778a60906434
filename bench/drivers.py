"""The two drivers a traffic file can name: ``online`` and ``replay``.

Both build the fleet and the stream from the configuration, the traffic
file and the seed, warm up every shape the window uses, then drive the
program for ``seconds`` of host clock. They return a ``Run``: the host
measurements by metric name, the program's outputs for the reference,
and what the trace readers need. Nothing here is specific to one cell;
the program's knobs (``chunk``, ``backend``, the window sizes) come from
the traffic file.

* ``online`` is the open-loop front end the program does not have yet:
  requests are due on a Poisson schedule of wall-clock time; a window is
  dispatched once its last request is due, its columns go to the device,
  ``route_batch`` routes it against the state the previous window left,
  and its ``choice`` column comes back to the host. A request's decision
  latency runs from its due time to that return.
* ``replay`` compiles the stream at set-up and keeps its columns on the
  device, then feeds it back to back through ``workloads.simulate``,
  several router windows a call, carrying the state from call to call.
  Its rate counts every request of every call and all the time of every
  call, the call that ends after ``seconds`` included.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import math
import os
import threading
import time
from typing import Optional

import numpy as np

from bench import fleet, streams


@dataclasses.dataclass
class Run:
    host: dict                     # host-clock measurements by metric name
    attempted: int
    failed: int
    cols: dict                     # the stream as fed, decided prefix and beyond
    choice: np.ndarray             # program outputs for the decided prefix
    latency: np.ndarray
    hit: np.ndarray
    chunk: int
    traced: Optional[dict] = None  # what the trace readers need
    memory_peak_bytes: int = 0
    describe: dict = dataclasses.field(default_factory=dict)


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def percentile_ms(values_s: np.ndarray, q: float) -> float:
    """``q``-th percentile in ms over ALL values; a missing value is
    ``inf`` and counts as the slowest."""
    if values_s.size == 0:
        return math.inf
    big = 1e300  # stands for inf so the interpolation stays defined
    v = float(np.percentile(np.where(np.isinf(values_s), big, values_s), q))
    return math.inf if v >= big * 1e-3 else v * 1e3


def decision_latencies(due: np.ndarray, done: np.ndarray,
                       seconds: float) -> np.ndarray:
    """Latency from due time to decision for every request due inside
    the window ``[0, seconds)``; undecided requests (``done`` NaN) read
    ``inf``."""
    inside = due < seconds
    lat = done[inside] - due[inside]
    return np.where(np.isnan(lat), np.inf, lat)


def lag_share(lags_s: np.ndarray, window: int, due: np.ndarray,
              done: np.ndarray) -> float:
    """Share of the summed decision latency of the decided requests that
    the driver itself added by dispatching late: each request of window
    ``k`` carries that window's lag. It keeps the host's jitter apart from
    the router's own part of the tail."""
    decided = lags_s.size * window
    if decided == 0:
        return 0.0
    total = float((done[:decided] - due[:decided]).sum())
    return float(np.repeat(lags_s, window).sum()) / total


def window_rate(requests: int, ends) -> float:
    """Requests over the seconds from the window's open to the end of the
    last call: all the work and all the time, the call that ends after
    the nominal window included."""
    return requests / float(ends[-1])


def describe(hit: np.ndarray, choice: np.ndarray, cloud, chunk: int) -> dict:
    """Traffic description: residency hits and misses, cloud share, and
    the share of requests at or after the first miss of their chunk (the
    suffix the speculative commit replays serially)."""
    n = hit.size - hit.size % chunk
    miss = ~hit[:n].reshape(-1, chunk)
    first = np.where(miss.any(axis=1), miss.argmax(axis=1), chunk)
    return {
        "requests": int(hit.size),
        "hit_rate": float(hit.mean()) if hit.size else 0.0,
        "misses": int((~hit).sum()),
        "cloud_share": (float((choice == cloud).mean())
                        if cloud is not None and choice.size else 0.0),
        "after_first_miss_share": (float((chunk - first).sum() / n)
                                   if n else 0.0),
    }


class CompileCounter:
    """Counts JAX traces and backend compiles while it is open; the
    window should hold none."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.count += 1

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


@contextlib.contextmanager
def gc_paused():
    """The cyclic garbage collector off for the window: its pauses are the
    driver's own, not the router's, and they fall on random windows."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def _batch(cols: dict, sl, device=None, arrival=None):
    """The program's ``RequestBatch`` for the stream slice ``sl``, on
    ``device`` (``arrival`` replaces the slice's stamps)."""
    import jax
    from repro.core.batch_router import RequestBatch

    put = (lambda x: jax.device_put(x, device)) if device else jax.device_put
    return RequestBatch(
        model=put(cols["model"][sl]), prompt_bits=put(cols["prompt_bits"][sl]),
        gen_tokens=put(cols["gen_tokens"][sl]), cell=put(cols["cell"][sl]),
        arrival_s=put(cols["arrival_s"][sl] if arrival is None else arrival))


class Tracer:
    """The profiler over the first ``seconds`` of the window, with the
    host spans the idle-gap breakdown reads. A timer thread ends the
    trace, so the traced span need not end at a call boundary; its ends
    are the markers ``bench.trace_open`` and ``bench.trace_close``. Off,
    every span is a no-op."""

    def __init__(self, on: bool, log_dir=None, seconds: float = 1.0):
        self.on = on
        self.log_dir = log_dir
        self.seconds = seconds
        self.active = False
        self.stopped_at = None
        self._lock = threading.Lock()
        self._timer = None

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        if not self.on:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.active = True
        with self.span("bench.trace_open"):
            pass
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self):
        with self._lock:
            if not self.active:
                return
            import jax
            with self.span("bench.trace_close"):
                pass
            self.stopped_at = time.perf_counter()
            self.active = False
            jax.profiler.stop_trace()

    def close(self):
        """Stop the trace if the timer has not, and wait for the timer."""
        if self._timer is not None:
            self._timer.cancel()
            self.stop()
            self._timer.join()


def online(cfg: dict, traffic: dict, *, seed: int, seconds: float,
           tracer: Tracer, devices) -> Run:
    import jax
    from repro.core import batch_router as br

    spec = streams.scenario(traffic["scenario"])
    w = int(traffic["window_requests"])
    rate = float(spec["rate"])
    expect = rate * seconds
    n = w * (math.ceil((expect + 8.0 * math.sqrt(expect)) / w) + 2)
    cols = streams.generate(spec, seed=seed, n=n, num_models=len(cfg["models"]),
                            num_cells=cfg["num_cells"])
    due = cols["arrival_f64"]
    dev = devices[0]
    params, state0 = jax.device_put(fleet.program_fleet(cfg), dev)
    route = functools.partial(br.route_batch, chunk=traffic["chunk"],
                              backend=traffic["backend"])
    n_win = n // w
    wins = [slice(k * w, (k + 1) * w) for k in range(n_win)]
    for _ in range(2):  # the one window shape, compiled or loaded, then warm
        _, out = route(params, state0, _batch(cols, wins[0], dev))
        np.asarray(out.choice)

    done = np.full(n, np.nan)
    lags, disp_t, outs = [], [], []
    state = state0
    tracer.start()
    t_open = time.perf_counter()
    setup_s = process_age_s()
    k = 0
    with CompileCounter() as compiles, gc_paused():
        while k < n_win and due[k * w] < seconds:
            last_due = t_open + due[(k + 1) * w - 1]
            with tracer.span("bench.wait_due"):
                now = time.perf_counter()
                if now < last_due:
                    time.sleep(last_due - now)
            t_disp = time.perf_counter()
            lags.append(t_disp - last_due)
            disp_t.append(t_disp - t_open)
            with tracer.span("bench.put"):
                batch = _batch(cols, wins[k], dev)
            with tracer.span("bench.route"):
                state, out = route(params, state, batch)
            with tracer.span("bench.fetch"):
                choice = np.asarray(out.choice)
            done[wins[k]] = time.perf_counter() - t_open
            outs.append((choice, out.latency, out.hit))
            k += 1
    tracer.close()
    jax.block_until_ready(state)
    peak = _peak_bytes([dev])

    lat = decision_latencies(due, done, seconds)
    decided = k * w
    choice = np.concatenate([o[0] for o in outs]) if outs else np.zeros(0, int)
    latency = np.concatenate([np.asarray(o[1]) for o in outs]) if outs else choice
    hit = np.concatenate([np.asarray(o[2]) for o in outs]) if outs else choice
    lag = np.asarray(lags)
    traced = None
    if tracer.on:
        stop = tracer.stopped_at - t_open
        traced = {"requests_per_module": w,
                  "lag_s": lag[np.asarray(disp_t) < stop]}
    return Run(
        host={"decide_p95_ms": percentile_ms(lat, 95),
              "decide_p50_ms": percentile_ms(lat, 50),
              "setup_s": setup_s},
        attempted=int(lat.size),
        failed=int(np.isinf(lat).sum()),
        cols={k_: v[:decided] for k_, v in cols.items()},
        choice=choice.astype(np.int64), latency=latency, hit=hit.astype(bool),
        chunk=int(traffic["chunk"]), traced=traced,
        memory_peak_bytes=peak,
        describe={**describe(hit.astype(bool), choice, fleet.table(cfg)["cloud"],
                             int(traffic["chunk"])),
                  "windows": k, "offered_rps": rate,
                  "compiles_in_window": compiles.count,
                  "dispatch_lag_p95_ms": percentile_ms(lag, 95),
                  "dispatch_lag_share": lag_share(lag, w, due, done),
                  "dispatch_lag_last_ms": float(lag[-1] * 1e3) if lag.size
                  else math.nan},
    )


def replay(cfg: dict, traffic: dict, *, seed: int, seconds: float,
           tracer: Tracer, devices) -> Run:
    import jax
    from repro.workloads import simulate

    spec = streams.scenario(traffic["scenario"])
    w = int(traffic["window_requests"])
    per_call = w * int(traffic["windows_per_call"])
    n_calls = int(traffic["stream_calls"])
    n = per_call * n_calls
    cols = streams.generate(spec, seed=seed, n=n, num_models=len(cfg["models"]),
                            num_cells=cfg["num_cells"])
    cloud = fleet.table(cfg)["cloud"]
    dev = devices[0]
    params, state0 = jax.device_put(fleet.program_fleet(cfg), dev)
    calls = [slice(j * per_call, (j + 1) * per_call) for j in range(n_calls)]
    staged = [_batch(cols, sl, dev) for sl in calls]
    span = float(cols["arrival_f64"][-1]) * (1.0 + 1.0 / n)

    def stamps(j, wrap):
        """Call ``j``'s arrival column, shifted past the stream ``wrap``
        times when the window outruns it."""
        if not wrap:
            return cols["arrival_s"][calls[j]]
        return (cols["arrival_f64"][calls[j]] + wrap * span).astype(np.float32)

    def sim(state, batch):
        return simulate(params, state, batch, window_requests=w,
                        chunk=traffic["chunk"], backend=traffic["backend"],
                        cloud_index=cloud)

    # every call has the same shapes: one call compiles or loads them all
    _, out, _ = sim(state0, staged[0])
    jax.block_until_ready(out.choice)

    fed = []        # (call index, wrap) in the order fed
    outs, ends = [], []
    state = state0
    tracer.start()
    t_open = time.perf_counter()
    setup_s = process_age_s()
    j = 0
    with CompileCounter() as compiles, gc_paused():
        while True:
            wrap, jj = divmod(j, n_calls)
            batch = staged[jj]
            if wrap:
                with tracer.span("bench.wrap"):
                    batch = _batch(cols, calls[jj], dev,
                                   arrival=stamps(jj, wrap))
            with tracer.span("bench.simulate"):
                state, out, _ = sim(state, batch)
                jax.block_until_ready(out.choice)
            ends.append(time.perf_counter() - t_open)
            outs.append(out)
            fed.append((jj, wrap))
            j += 1
            if ends[-1] >= seconds:
                break
    tracer.close()
    jax.block_until_ready(state)
    peak = _peak_bytes([dev])

    choice = np.concatenate([np.asarray(o.choice) for o in outs])
    latency = np.concatenate([np.asarray(o.latency) for o in outs])
    hit = np.concatenate([np.asarray(o.hit) for o in outs]).astype(bool)
    used = {}
    for key in ("model", "prompt_bits", "gen_tokens", "cell"):
        used[key] = np.concatenate([cols[key][calls[c]] for c, _ in fed])
    used["arrival_s"] = np.concatenate([stamps(c, wr) for c, wr in fed])
    routed = len(fed) * per_call
    traced = None
    if tracer.on:
        traced = {"requests_per_module": w}
    return Run(
        host={"routed_rps": window_rate(routed, ends), "setup_s": setup_s},
        attempted=routed, failed=0, cols=used,
        choice=choice.astype(np.int64), latency=latency, hit=hit,
        chunk=int(traffic["chunk"]),
        traced=traced, memory_peak_bytes=peak,
        describe={**describe(hit, choice, cloud, int(traffic["chunk"])),
                  "calls": len(fed), "wraps": fed[-1][1],
                  "compiles_in_window": compiles.count,
                  "fleet_rate_rps": float(spec["rate"])},
    )


DRIVERS = {"online": online, "replay": replay}
