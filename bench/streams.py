"""Request streams for the benchmark, generated from a traffic file and a seed.

A frozen copy of the repository's scenario generator
(``repro.workloads.generators`` and ``scenario.compile_scenario``), kept
here so that a later change to the program cannot move the yardstick.
Every component draws from its own ``SeedSequence`` child, so the same
``(scenario, seed, num_models, num_cells)`` gives the same stream in any
process, bit for bit, and matches what the program's own
``compile_scenario`` builds for the same spec.

The columns come back as numpy arrays in the dtypes the router is fed
(``model``/``cell`` int32, ``prompt_bits``/``gen_tokens``/``arrival_s``
float32), plus ``arrival_f64``, the stamps before the float32 cast, which
the online driver uses as due times.
"""
from __future__ import annotations

import numpy as np

#: Scenario keys a traffic file may set, with the generator's defaults.
SCENARIO_DEFAULTS = {
    "arrival": "poisson",
    "rate": 200.0,
    "burst": 64,
    "burst_gap_s": 0.5,
    "jitter_s": 1e-3,
    "rate_hi": 2000.0,
    "dwell_lo_s": 2.0,
    "dwell_hi_s": 0.25,
    "period_s": 5.0,
    "depth": 0.9,
    "spike_start_s": 3.0,
    "spike_dur_s": 1.0,
    "spike_mult": 20.0,
    "zipf_s": 0.0,
    "drift_period_s": None,
    "hotspot_cell": None,
    "hotspot_weight": 0.7,
    "prompt_bits": (1e5, 1e6),
    "gen_tokens": (8, 128),
}


def scenario(spec: dict) -> dict:
    """Complete a traffic file's ``scenario`` object with the defaults;
    an unknown key is an error, not a silent no-op."""
    unknown = set(spec) - set(SCENARIO_DEFAULTS)
    if unknown:
        raise ValueError(f"unknown scenario keys {sorted(unknown)}")
    return {**SCENARIO_DEFAULTS, **spec}


def component_rngs(seed: int, n: int) -> list:
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(n)]


def _warp_piecewise_rate(mass, starts, rates):
    starts = np.asarray(starts, float)
    rates = np.asarray(rates, float)
    cum = np.concatenate([[0.0], np.cumsum(rates[:-1] * np.diff(starts))])
    seg = np.clip(np.searchsorted(cum, mass, side="right") - 1,
                  0, len(rates) - 1)
    return starts[seg] + (mass - cum[seg]) / rates[seg]


def _arrivals(spec: dict, rng, n: int) -> np.ndarray:
    kind = spec["arrival"]
    if kind == "poisson":
        return np.cumsum(rng.exponential(1.0 / spec["rate"], n))
    if kind == "bursts":
        a = ((np.arange(n) // spec["burst"]) * spec["burst_gap_s"]
             + rng.uniform(0.0, spec["jitter_s"], n))
        return np.sort(a)
    mass = np.cumsum(rng.exponential(1.0, n))
    if kind == "mmpp":
        starts, rates = [0.0], []
        t, covered, lo = 0.0, 0.0, True
        while covered < mass[-1]:
            dwell, rate = ((spec["dwell_lo_s"], spec["rate"]) if lo
                           else (spec["dwell_hi_s"], spec["rate_hi"]))
            d = rng.exponential(dwell)
            t += d
            covered += rate * d
            starts.append(t)
            rates.append(rate)
            lo = not lo
        rates.append(spec["rate"])
        return _warp_piecewise_rate(mass, starts, rates)
    if kind == "diurnal":
        rate, period, depth = spec["rate"], spec["period_s"], spec["depth"]
        horizon = mass[-1] / rate + 2.0 * period
        grid = np.linspace(0.0, horizon, max(2048, int(256 * horizon / period)))
        w = 2.0 * np.pi / period
        cum = rate * (grid + depth / w * (1.0 - np.cos(w * grid)))
        return np.interp(mass, cum, grid)
    if kind == "flash":
        s0, dur = spec["spike_start_s"], spec["spike_dur_s"]
        return _warp_piecewise_rate(
            mass, [0.0, s0, s0 + dur],
            [spec["rate"], spec["rate"] * spec["spike_mult"], spec["rate"]])
    raise ValueError(f"unknown arrival process {kind!r}")


def zipf_popularity(num_models: int, s: float) -> np.ndarray:
    w = np.arange(1, num_models + 1, dtype=float) ** -float(s)
    return w / w.sum()


def _categorical(rng, n: int, probs, rows=None) -> np.ndarray:
    p = np.asarray(probs, float)
    u = rng.random(n)
    if p.ndim == 1:
        cdf = np.cumsum(p)
        return np.searchsorted(cdf, u * cdf[-1], side="right").astype(np.int64)
    cdf = np.cumsum(p, axis=1)[rows]
    return (cdf < u[:, None] * cdf[:, -1:]).sum(axis=1)


def generate(spec: dict, *, seed: int, n: int, num_models: int,
             num_cells: int) -> dict:
    """``n`` requests of the scenario ``spec`` (a completed ``scenario``
    dict) for a fleet of ``num_cells`` cells and ``num_models`` models."""
    (rng_arr, rng_drift, rng_model, rng_prompt, rng_gen, rng_cell,
     _rng_deadline) = component_rngs(seed, 7)
    arrivals = _arrivals(spec, rng_arr, n)

    probs = rows = None
    if spec["drift_period_s"] is not None:
        period = spec["drift_period_s"]
        windows = int(arrivals[-1] // period) + 1
        base = zipf_popularity(num_models, spec["zipf_s"])
        perms = np.argsort(rng_drift.random((windows, num_models)), axis=1)
        probs = np.zeros((windows, num_models))
        np.put_along_axis(probs, perms,
                          np.broadcast_to(base, perms.shape), axis=1)
        rows = np.minimum((arrivals // period).astype(np.int64), windows - 1)
    elif spec["zipf_s"]:
        probs = zipf_popularity(num_models, spec["zipf_s"])
    model = (rng_model.integers(0, num_models, n) if probs is None
             else _categorical(rng_model, n, probs, rows))

    lo, hi = spec["prompt_bits"]
    prompt = rng_prompt.uniform(lo, hi, n)
    glo, ghi = spec["gen_tokens"]
    gen = np.full(n, glo) if ghi <= glo else rng_gen.integers(glo, ghi, n)

    if num_cells > 1:
        if spec["hotspot_cell"] is None:
            cell = rng_cell.integers(0, num_cells, n)
        else:
            p = np.full(num_cells,
                        (1.0 - spec["hotspot_weight"]) / (num_cells - 1))
            p[spec["hotspot_cell"]] = spec["hotspot_weight"]
            cell = _categorical(rng_cell, n, p)
    else:
        cell = np.zeros(n, np.int64)
    return {
        "model": model.astype(np.int32),
        "prompt_bits": prompt.astype(np.float32),
        "gen_tokens": gen.astype(np.float32),
        "cell": cell.astype(np.int32),
        "arrival_s": arrivals.astype(np.float32),
        "arrival_f64": arrivals,
    }
