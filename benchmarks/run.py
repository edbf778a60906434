"""Benchmark entry point: one section per paper table/figure + the
framework's own microbenchmarks + the roofline summary.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --list     # registered sections
    PYTHONPATH=src python -m benchmarks.run --only router_throughput,scenarios
    PYTHONPATH=src python -m benchmarks.run --only router_throughput --smoke

CSV convention per scaffold: ``name,us_per_call,derived``.
Paper-figure sections read the cached training results in
``benchmarks/results/`` (populate with ``python -m benchmarks.populate``).
Every section is registered in ``SECTIONS`` — CI smoke-checks the
registration via ``--list`` so new benchmarks can't silently drop out.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache


def _timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def bench_env_step():
    """IIoT environment throughput (vectorised, jitted)."""
    from repro.core import baselines, env as env_lib

    p = env_lib.default_params(num_eds=10, num_models=3)
    state = env_lib.reset(jax.random.key(0), p)
    obs = env_lib.observe(state, p)

    @jax.jit
    def step(state, key):
        act = baselines.random_policy(key, env_lib.observe(state, p), p)
        nxt, _, out, _ = env_lib.step(state, act, p)
        return nxt, out.reward.sum()

    us = _timeit(lambda: step(state, jax.random.key(1))[1])
    print(f"env_step_10ed,{us:.1f},agent_steps_per_s={10e6 / us:.0f}")


def bench_maddpg_update():
    from repro.core import env as env_lib, maddpg, replay

    p = env_lib.default_params(num_eds=10, num_models=3)
    cfg = maddpg.AlgoConfig(batch_size=512)
    ts = maddpg.init_state(jax.random.key(0), p, cfg)
    ex = maddpg.make_transition_example(p, cfg)
    buf = replay.init(2048, ex)
    buf = replay.add_batch(
        buf, jax.tree.map(lambda x: jnp.ones((2048,) + x.shape, x.dtype), ex), 2048
    )
    batch = replay.sample(buf, jax.random.key(1), cfg.batch_size)
    upd = jax.jit(lambda t: maddpg.update(t, batch, jax.random.key(2), p, cfg))
    us = _timeit(upd, ts)
    print(f"maddpg_update_b512,{us:.1f},updates_per_s={1e6 / us:.2f}")


def bench_kernels():
    from repro.kernels import ref

    q = jax.random.normal(jax.random.key(0), (4, 1024, 8, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (4, 1024, 2, 64), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (4, 1024, 2, 64), jnp.float32)
    att = jax.jit(lambda a, b, c: ref.attention_xla(a, b, c, causal=True))
    us = _timeit(att, q, k, v)
    flops = 4 * 4 * 8 * 1024 * 1024 * 64 / 2  # causal
    print(f"attention_xla_4x1024x8x64,{us:.1f},gflops_per_s={flops / us / 1e3:.1f}")

    x = jax.random.normal(jax.random.key(3), (2, 2048, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(4), (2, 2048, 8)))
    a_log = jax.random.normal(jax.random.key(5), (8,)) * 0.5
    b = jax.random.normal(jax.random.key(6), (2, 2048, 64))
    c = jax.random.normal(jax.random.key(7), (2, 2048, 64))
    d = jnp.ones((8,))
    ssd = jax.jit(lambda *a: ref.ssd_chunked_xla(*a, chunk=256)[0])
    us = _timeit(ssd, x, dt, a_log, b, c, d)
    print(f"ssd_xla_2x2048x8x64,{us:.1f},tokens_per_s={2 * 2048 * 1e6 / us:.0f}")

    xr = jax.random.normal(jax.random.key(8), (4096, 2048), jnp.bfloat16)
    sc = jnp.ones((2048,), jnp.bfloat16)
    rms = jax.jit(lambda a, s: ref.rmsnorm_naive(a, s))
    us = _timeit(rms, xr, sc)
    gb = 2 * xr.size * 2 / 1e9
    print(f"rmsnorm_4096x2048,{us:.1f},gb_per_s={gb * 1e6 / us:.1f}")


def bench_router_throughput(smoke=False):
    """Fleet-scale routing: scalar oracle vs scan vs chunked vs the
    speculative parallel commit (incl. the N=64 B=4096 acceptance cell,
    which refreshes benchmarks/BENCH_router.json). With --smoke, a
    tiny-shape pass that exercises every path (no timing, no JSON)."""
    from benchmarks import router_throughput

    if smoke:
        router_throughput.main(header=False, smoke=True)
        return
    # one representative cell per size regime; the full sweep is
    # ``python -m benchmarks.router_throughput``
    router_throughput.main(fleet_sizes=(16, 64), batch_sizes=(1024, 4096),
                           header=False)


def bench_score_kernel():
    """Fused (B, N) eq. 11 score contraction (chunked phase 1)."""
    from benchmarks import score_kernel

    score_kernel.main(shapes=((4096, 64),), header=False)


def bench_multicell():
    """Multi-cell fleets + time-based drain, one jitted call per batch."""
    from benchmarks import multicell_throughput

    # the acceptance cell (C=4, N=64, B=1024); the full sweep is
    # ``python -m benchmarks.multicell_throughput``
    multicell_throughput.main(cell_counts=(4,), servers_per_cell=(16,),
                              batch_sizes=(1024,), header=False)


def bench_fleet_scale(smoke=False):
    """Mesh-sharded fleet routing (core.mesh_router): req/s vs device
    count at C=64 cells, N=1024 edge + cloud, B=256k requests/window on
    a forced-8-device host; refreshes benchmarks/BENCH_fleet.json. With
    --smoke, tiny shapes + a bitwise parity assert vs the plain scan
    (no timing, no JSON)."""
    from benchmarks import fleet_scale

    fleet_scale.main(header=False, smoke=smoke)


def bench_policy_serving(smoke=False):
    """Policy QUALITY (not req/s): greedy vs drain-aware vs a trained
    MADDPG-MATO actor checkpoint — target-only AND the full eq. 16
    action (eta/beta head columns) — on the same bursty multi-cell
    stream; refreshes benchmarks/BENCH_policy.json. Trains a
    short-budget checkpoint on first run (cached under
    benchmarks/results/). With --smoke, a toy untrained actor asserts
    the eta/beta columns are honoured end to end (bitwise no-op for
    all-ones knobs, refusal zeroes download_rate); no training, no
    timing, no BENCH JSON."""
    from benchmarks import policy_serving

    if smoke:
        policy_serving.smoke()
        return
    policy_serving.main(header=False)


def bench_scenarios():
    """Policies x scenarios matrix through the long-horizon workload
    simulator (repro.workloads); refreshes benchmarks/
    BENCH_scenarios.json."""
    from benchmarks import scenario_suite

    scenario_suite.main(header=False)


def bench_degraded(smoke=False):
    """Degraded-service scenarios (slo-mix / flash-crowd-outage /
    drain-outage) with per-cause rejection rates + the SLO queue-bound
    acceptance check; refreshes benchmarks/BENCH_degraded.json. With
    --smoke, one tiny episode asserting admission AND outage rejections
    end to end (no timing, no JSON)."""
    from benchmarks import degraded_suite

    degraded_suite.main(header=False, smoke=smoke)


def bench_train_step():
    from repro.configs import get_arch, reduced
    from repro.data import pipeline
    from repro.models import lm
    from repro.models.train import make_train_step

    cfg = reduced(get_arch("smollm_135m"))
    params = lm.init_params(jax.random.key(0), cfg)
    dc = pipeline.DataConfig(seq_len=128, global_batch=4, vocab=cfg.vocab)
    batch = pipeline.synthetic_batch(cfg, dc, 0)
    opt_init, step = make_train_step(cfg)
    opt = opt_init(params)
    jit_step = jax.jit(step)
    us = _timeit(lambda: jit_step(params, opt, batch)[2]["loss"], n=3, warmup=1)
    print(f"lm_train_step_reduced,{us:.1f},tokens_per_s={4 * 128 * 1e6 / us:.0f}")


def paper_tables():
    from benchmarks import convergence, ed_sweep, model_sweep

    print("\n=== paper Fig.2 (convergence) ===")
    try:
        convergence.main()
    except Exception as e:  # cache missing
        print(f"(skipped: {e})")
    print("\n=== paper Fig.3 (model sweep) ===")
    try:
        model_sweep.main()
    except Exception as e:
        print(f"(skipped: {e})")
    print("\n=== paper Fig.4 (ED sweep) ===")
    try:
        ed_sweep.main()
    except Exception as e:
        print(f"(skipped: {e})")


def roofline_table():
    from benchmarks import roofline

    print("\n=== roofline (from dry-run artifacts) ===")
    try:
        roofline.main()
        print()
        roofline.main_multipod()
    except Exception as e:
        print(f"(skipped: {e})")


def faithful_table():
    from benchmarks import faithful_ablation

    print("\n=== faithful-vs-corrected cost model (DESIGN.md §3) ===")
    try:
        faithful_ablation.main()
    except Exception as e:
        print(f"(skipped: {e})")


#: Registered sections, run order. CI pins this registry via ``--list``.
SECTIONS = [
    ("env_step", bench_env_step),
    ("maddpg_update", bench_maddpg_update),
    ("kernels", bench_kernels),
    ("score_kernel", bench_score_kernel),
    ("router_throughput", bench_router_throughput),
    ("multicell", bench_multicell),
    ("fleet_scale", bench_fleet_scale),
    ("policy_serving", bench_policy_serving),
    ("scenarios", bench_scenarios),
    ("degraded_suite", bench_degraded),
    ("train_step", bench_train_step),
    ("paper_tables", paper_tables),
    ("faithful", faithful_table),
    ("roofline", roofline_table),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--list", action="store_true",
                    help="print registered sections and exit (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections to run")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape pass for sections that support it "
                         "(exercised, not timed; no BENCH files rewritten)")
    args = ap.parse_args(argv)
    if args.list:
        for name, fn in SECTIONS:
            doc = (fn.__doc__ or "").strip().splitlines() or [""]
            print(f"{name}: {doc[0]}")
        return
    selected = dict(SECTIONS)
    if args.only is not None:
        missing = [n for n in args.only.split(",") if n not in selected]
        if missing:
            raise SystemExit(
                f"unknown sections {missing}; see --list"
            )
        keep = set(args.only.split(","))
        sections = [(n, f) for n, f in SECTIONS if n in keep]
    else:
        sections = SECTIONS
    enable_compile_cache()
    print("name,us_per_call,derived")
    for _, fn in sections:
        if args.smoke and "smoke" in fn.__code__.co_varnames:
            fn(smoke=True)
        else:
            fn()


if __name__ == "__main__":
    main()
