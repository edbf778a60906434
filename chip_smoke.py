"""On-chip smoke test of the routing path.

Drives the serving entry points (``repro.launch.serve.make_window`` and
``route_window``, the two halves of ``serve``) once per phase on the
accelerator, and checks every routed window against the same jitted
call on the host CPU device of the same process — the path the tier-1
oracle lattice pins:

1. ``single-cell``: 4096 requests over 64 servers, ``steady``, chunk 256
   (the README's ``--requests 4096 --servers 64 --chunk 256``).
2. ``metro``: 64 cells x 16 servers plus the cloud column (N = 1025),
   262,144 requests, ``popularity-drift`` seed 7, a 20,000 tok/s time
   drain, chunk 256, speculative commit.
3. ``actor``: a seed-initialised MADDPG actor serving 4 cells x 4
   servers plus cloud, 4096 requests, a 50 tok/s drain, chunk 256 — the
   actor MLP runs inside the routing scan.

Each greedy phase runs with ``backend="pallas"`` (the compiled kernel)
and ``backend="xla"``. ``choice``/``hit`` and the returned residency and
LRU clocks must match the CPU reference exactly; latencies and queues to
``rtol=1e-5``; and the two backends must choose alike. A differing
choice is accepted only as a near tie: at the first differing request,
the eq. 11 scores of the two servers chosen, on the reference state, lie
at most ``NEAR_TIE_ULPS`` float32 ulps apart. The outputs after a near tie
follow different histories and are not compared.

``--chips 4`` runs only the sharded metro window
(``mesh_router.route_batch_sharded``) at 4 devices and at 1, and a
cloud-free, drain-free variant at 4 devices against plain
``route_batch``, and prints where the outputs live and each device's
memory. Each pair must agree bit for bit in every output and state
field (``docs/sharding.md``, exactness tiers).

Timings printed here are smoke timings of one warm call, not a
benchmark. The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``; any failed check exits 1 without it.
With no TPU the script exits 2 before running anything.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

NEAR_TIE_ULPS = 4
RTOL = 1e-5
CHIP_BACKENDS = ("pallas", "xla")  # the reference always runs "xla"
CHUNK = 256

SINGLE_CELL = dict(num_requests=4096, n_servers=64, n_cells=1,
                   scenario="steady", seed=0)
METRO = dict(num_requests=262_144, n_servers=16, n_cells=64,
             scenario="popularity-drift", seed=7, drain_rate=20000.0)
ACTOR = dict(num_requests=4096, n_servers=4, n_cells=4, scenario="steady",
             seed=0, drain_rate=50.0, arrival_rate=100.0)


def require_tpu():
    """The first JAX device, which must be a TPU: no CPU fallback."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        raise SystemExit(2)
    return dev


def _on(device, tree):
    import jax

    return jax.device_put(tree, device)


def _timed_route(window, policy, backend, device, *, mesh=None):
    """Route ``window`` on ``device`` (or over a ``mesh`` of the first
    devices) twice: the first call compiles or reads the persistent
    cache, the second is one warm smoke timing."""
    import jax
    from repro.launch.serve import route_window

    w = window  # the mesh router places its own uncommitted inputs
    if mesh is None:
        w = window._replace(params=_on(device, window.params),
                            state=_on(device, window.state),
                            reqs=_on(device, window.reqs))
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        with jax.default_device(device):
            state, out = route_window(w, policy, chunk=CHUNK,
                                      backend=backend, mesh=mesh)
        jax.block_until_ready((state, out))
        times.append(time.perf_counter() - t0)
    return state, out, times


def _host(tree):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, tree)


def _score_gap_ulps(window, i, picks, policy, cpu):
    """Gap, in float32 ulps, between the eq. 11 scores of the two
    servers ``picks`` for request ``i``, against the reference state just
    before it: a near tie when the picks are (nearly) the two best."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import batch_router as br

    params = _on(cpu, window.params)
    reqs = _on(cpu, window.reqs)
    with jax.default_device(cpu):
        state = _on(cpu, window.state)
        if i:
            head = jax.tree.map(lambda x: x[:i], reqs)
            state, _ = br.route_batch(params, state, head,
                                      window.drain_tokens, policy=policy,
                                      backend="xla")
        if reqs.arrival_s is not None and state.time_s is not None:
            dt = jnp.maximum(reqs.arrival_s[i] - state.time_s, 0.0)
            state = state._replace(queue_tokens=jnp.maximum(
                state.queue_tokens - params.drain_rate * dt, 0.0))
        row = jax.tree.map(lambda x: x[i:i + 1], reqs)
        scores = np.asarray(br.score_matrix(params, state, row,
                                            backend="xla"))[0]
    if min(picks) < 0:  # one side rejected the request
        return float("inf")
    a, b = sorted(scores[list(picks)].astype(np.float32))
    return float((b - a) / np.spacing(a))


def _compare(label, window, got, ref, *, policy, cpu, near_ties,
             check_state=True):
    """Check one routed window against another; returns failure lines."""
    import numpy as np

    (g_state, g_out), (r_state, r_out) = _host(got), _host(ref)
    fails = []
    diff = np.nonzero(g_out.choice != r_out.choice)[0]
    upto = len(r_out.choice)
    print(f"  {label}: {diff.size} of {upto} choices differ")
    if diff.size:
        i = int(diff[0])
        picks = (int(g_out.choice[i]), int(r_out.choice[i]))
        gap = _score_gap_ulps(window, i, picks, policy, cpu)
        print(f"  {label}: first at request {i} (servers {picks[0]} vs "
              f"{picks[1]}); their eq. 11 scores there are {gap} ulp apart")
        if policy == "greedy" and gap <= NEAR_TIE_ULPS:
            near_ties.append(f"{label} @ request {i}: {gap} ulp")
            upto = i
        else:
            fails.append(f"{label}: choice differs at request {i} and it "
                         f"is not a near tie ({gap} ulp)")
    hits = int(np.sum(g_out.hit[:upto] != r_out.hit[:upto]))
    if hits:
        fails.append(f"{label}: {hits} hits differ")
    if not np.allclose(g_out.latency[:upto], r_out.latency[:upto],
                       rtol=RTOL, atol=0.0, equal_nan=True):
        fails.append(f"{label}: latency beyond rtol {RTOL}")
    if check_state and upto == len(r_out.choice):
        for name in ("resident", "last_use", "clock"):
            if not np.array_equal(getattr(g_state, name),
                                  getattr(r_state, name)):
                fails.append(f"{label}: state.{name} differs")
        if not np.allclose(g_state.queue_tokens, r_state.queue_tokens,
                           rtol=RTOL, atol=0.0):
            fails.append(f"{label}: queue_tokens beyond rtol {RTOL}")
        bitwise = (np.array_equal(g_out.latency, r_out.latency)
                   and np.array_equal(g_state.queue_tokens,
                                      r_state.queue_tokens))
        print(f"  {label}: latency and queues bitwise equal: {bitwise}")
    return fails


def _print_stats(label, window, out, times):
    from repro.core import batch_router as br

    s = br.stats(out, cloud_index=window.cloud_index)
    line = {
        "run": label,
        "first_call_s": times[0],
        "warm_call_s (smoke timing, not a benchmark)": times[1],
        "compile_s (first - warm)": times[0] - times[1],
        "completion_rate": s["completion_rate"],
        "residency_hit_rate": s["residency_hit_rate"],
    }
    if "cloud_fallback_rate" in s:
        line["cloud_fallback_rate"] = s["cloud_fallback_rate"]
    print("  " + json.dumps(line))


def _describe(name, window):
    n = window.params.flops_per_s.shape[0]
    b = window.reqs.model.shape[0]
    print(f"phase {name}: B={b} requests, N={n} servers, K="
          f"{window.state.resident.shape[1]} models, chunk={CHUNK}, "
          f"drain_tokens={window.drain_tokens}, scenario "
          f"{window.spec.name}")


def _run_phase(name, window, policy, backends, chip, cpu, near_ties):
    _describe(name, window)
    ref = _timed_route(window, policy, "xla", cpu)
    _print_stats(f"{name}/cpu-xla (reference)", window, ref[1], ref[2])
    fails, first = [], None
    for backend in backends:
        got = _timed_route(window, policy, backend, chip)
        _print_stats(f"{name}/{chip.platform}-{backend}", window, got[1],
                     got[2])
        fails += _compare(f"{name} {backend} vs cpu", window, got[:2],
                          ref[:2], policy=policy, cpu=cpu,
                          near_ties=near_ties)
        if first is None:
            first = (backend, got)
        else:
            fails += _compare(f"{name} {backend} vs {first[0]} on chip",
                              window, got[:2], first[1][:2], policy=policy,
                              cpu=cpu, near_ties=near_ties,
                              check_state=False)
    return fails


def _actor_policy(window):
    import jax
    from repro.core import maddpg, policies
    from repro.core.catalog import build_catalog, env_params_from_catalog
    from repro.launch.serve import EDGE_ARCHS

    p = env_params_from_catalog(build_catalog(EDGE_ARCHS), num_eds=4,
                                num_ess=ACTOR["n_servers"])
    ts = maddpg.init_state(jax.random.key(0), p, maddpg.AlgoConfig())
    return policies.make_actor_policy(ts.actor, policies.spec_from_env(p),
                                      window.params)


def one_chip(chip, cpu):
    import jax
    from repro.launch.serve import make_window

    fails, near_ties = [], []
    for name, cfg in (("single-cell", SINGLE_CELL), ("metro", METRO)):
        fails += _run_phase(name, make_window(**cfg), "greedy",
                            CHIP_BACKENDS, chip, cpu, near_ties)
    window = make_window(**ACTOR)
    with jax.default_device(cpu):  # the reference's weights live there
        ref_policy = _actor_policy(window)
    chip_policy = _actor_policy(window)
    _describe("actor", window)
    ref = _timed_route(window, ref_policy, "xla", cpu)
    _print_stats("actor/cpu-xla (reference)", window, ref[1], ref[2])
    got = _timed_route(window, chip_policy, CHIP_BACKENDS[0], chip)
    _print_stats(f"actor/{chip.platform}-{CHIP_BACKENDS[0]}", window,
                 got[1], got[2])
    fails += _compare(f"actor {CHIP_BACKENDS[0]} vs cpu", window, got[:2],
                      ref[:2], policy=ref_policy, cpu=cpu,
                      near_ties=near_ties)
    return fails, near_ties


def _bitwise_report(label, got, ref):
    """Compare two routed windows field by field, bit for bit; returns
    one failure line per field that differs."""
    import numpy as np

    (g_state, g_out), (r_state, r_out) = _host(got), _host(ref)
    differ = [f for f in ("choice", "hit", "latency")
              if not np.array_equal(getattr(g_out, f), getattr(r_out, f))]
    differ += [f for f in ("resident", "last_use", "queue_tokens", "clock")
               if not np.array_equal(getattr(g_state, f),
                                     getattr(r_state, f))]
    print(f"  {label}: " + (f"differs in {', '.join(differ)}" if differ
                            else "bitwise equal"))
    return [f"{label}: {f} differs" for f in differ]


def four_chips(chip, cpu):
    import jax
    from repro.launch.serve import make_window

    if jax.device_count() < 4:
        return [f"--chips 4 needs 4 devices, found {jax.device_count()}"], []
    fails, near_ties = [], []
    window = make_window(**METRO)
    _describe("sharded-metro", window)
    backend = CHIP_BACKENDS[0]
    runs = {}
    for d in (4, 1):
        runs[d] = _timed_route(window, "greedy", backend, chip, mesh=d)
        _print_stats(f"sharded-metro/D={d}-{backend}", window, runs[d][1],
                     runs[d][2])
    out4 = runs[4][1]
    placed = sorted(d.id for d in out4.choice.sharding.device_set)
    print(f"  D=4 choice sharding: {out4.choice.sharding}; devices {placed}")
    if placed != sorted(d.id for d in jax.devices()[:4]):
        fails.append(f"sharded-metro: D=4 output lives on devices {placed}")
    for dev in jax.devices()[:4]:
        m = dev.memory_stats()  # None where the backend keeps no stats
        print(f"  device {dev.id}: memory_stats "
              f"{m and {k: m.get(k) for k in ('bytes_in_use', 'peak_bytes_in_use')}}")
        if m is not None and not m.get("peak_bytes_in_use"):
            fails.append(f"device {dev.id} shows no memory in use")
    fails += _bitwise_report("device-count invariance, D=4 vs D=1",
                             runs[4][:2], runs[1][:2])

    # cloud-free, drain-free fleet: the sharded window is exactly the
    # plain scan (docs/sharding.md, exactness tiers)
    free = make_window(**{**METRO, "drain_rate": 0.0})
    edge = free.cloud_index
    free = free._replace(
        drain_tokens=None, cloud_index=None,
        params=jax.tree.map(lambda x: x[:edge] if x.ndim and
                            x.shape[0] == edge + 1 else x, free.params),
        state=jax.tree.map(lambda x: x[:edge] if x.ndim and
                           x.shape[0] == edge + 1 else x, free.state))
    _describe("cloud-free", free)
    sharded = _timed_route(free, "greedy", backend, chip, mesh=4)
    plain = _timed_route(free, "greedy", backend, chip)
    _print_stats(f"cloud-free/D=4-{backend}", free, sharded[1], sharded[2])
    _print_stats(f"cloud-free/plain-{backend}", free, plain[1], plain[2])
    fails += _bitwise_report("cloud-free, D=4 vs plain route_batch",
                             sharded[:2], plain[:2])
    return fails, near_ties


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded metro window on four chips")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    chip = require_tpu()
    print(f"compile cache: {enable_compile_cache()}")
    cpu = jax.devices("cpu")[0]
    print(f"device: {chip.platform} {chip.device_kind} x "
          f"{jax.device_count()}; reference: {cpu.platform}; jax "
          f"{jax.__version__}")
    run = four_chips if args.chips == 4 else one_chip
    fails, near_ties = run(chip, cpu)
    print(f"near ties: {len(near_ties)}" +
          "".join(f"\n  {t}" for t in near_ties))
    if fails:
        print("FAILED:\n  " + "\n  ".join(fails))
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": chip.platform, "kind": chip.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
