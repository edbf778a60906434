"""Serving driver: model-aware edge serving of the AIGC model zoo.

Wires the paper's scheduling layer to the real model plane:
  * a fleet of ``EdgeServer``s (device groups), each caching a subset of
    the catalogue (the 10 assigned architectures);
  * the WHOLE request batch routed in one jitted ``core.batch_router``
    call pricing the paper's eq. 5/7/9 cost terms (transmission, model
    switch, FIFO-shared compute) with sequential-commit semantics;
  * actual prefill+decode of the routed batch through ``models.lm`` on
    the local device (reduced configs on CPU).

Workload (the scenario subsystem, ``repro.workloads``):
  * ``--scenario NAME`` picks a registered traffic shape (``steady``,
    ``bursty``, ``diurnal``, ``flash-crowd``, ``popularity-drift``,
    ``hotspot-cell`` — see ``docs/scenarios.md``); the whole stream
    (arrival stamps, model popularity, cells, prompt sizes) is compiled
    from ``(ScenarioSpec, --seed)`` by ``workloads.compile_scenario``,
    so serve runs are reproducible end to end.
  * ``--arrival-rate R`` overrides the scenario's base rate (req/s
    fleet-wide); ``--seed`` reseeds the stream.

Cell / drain knobs (the multi-cell + time-based-drain serving path):
  * ``--cells C`` partitions the fleet into C edge cells of
    ``--servers`` servers each, plus ONE cloud-fallback server
    (``make_cloud_server``) in the reserved ``CLOUD_CELL`` that every
    request can reach at backhaul-folded uplink pricing. Requests carry
    the scenario's cell column and the whole C-cell fleet is still
    routed in a single jitted call (block-diagonal score mask).
  * ``--drain-rate R`` gives every edge server R tokens/sec of
    continuous queue drain; queue decay then tracks the scenario's
    wall-clock arrival stamps inside the scan carry rather than request
    count. ``--drain-rate 0`` (default) keeps the legacy synchronous
    drain.

Policies (``--policy``, dispatched through ``core.batch_router``'s
policy contract — a traceable callable evaluated once per request inside
the routing scan; see that module's docstring for what a policy callable
receives and returns):
  * ``greedy`` (default) — argmin of the eq. 11 latency;
  * ``load``   — least-loaded server (switch-blind baseline);
  * ``drain``  — drain-aware greedy: queue backlog discounted by each
    server's ``drain_rate`` before the eq. 9 pricing, so fast-draining
    servers keep winning under bursty arrivals;
  * ``actor:<ckpt_dir>`` — a trained MADDPG-MATO actor restored from a
    ``core.policies.save_actor_checkpoint`` directory. The policy
    rebuilds the env's eq. 16 observation from live fleet state per
    request (``core.policies``); an actor trained at ``num_cells=1``
    with N servers serves every cell of a ``--cells C --servers N``
    fleet unchanged. ``benchmarks/policy_serving.py`` trains and saves
    such a checkpoint under ``benchmarks/results/actor_ckpt``.

Performance knobs (the chunked two-phase commit, see
``core.batch_router``): ``--chunk C`` scores C requests per fused
kernel call and runs the slimmed correction scan between calls
(identical routing decisions, ~2x req/s at fleet scale); ``--backend``
picks the scoring backend (``xla`` | ``pallas`` | ``pallas-interpret``,
default from ``$REPRO_ROUTER_BACKEND``).

    python -m repro.launch.serve --requests 64 --servers 3
    python -m repro.launch.serve --requests 256 --servers 4 --cells 4 \
        --drain-rate 50 --arrival-rate 100 --no-execute
    python -m repro.launch.serve --requests 1024 --servers 3 --cells 2 \
        --scenario popularity-drift --seed 7 --drain-rate 20000 --no-execute
    python -m repro.launch.serve --requests 256 --servers 3 --cells 2 \
        --drain-rate 20000 --policy drain --no-execute
    python -m repro.launch.serve --requests 256 --servers 3 --cells 2 \
        --policy actor:benchmarks/results/actor_ckpt --no-execute
    python -m repro.launch.serve --requests 4096 --servers 64 \
        --chunk 256 --no-execute
"""
from __future__ import annotations

import argparse
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs, reduced
from repro.core import batch_router, policies
from repro.core.catalog import build_catalog
from repro.core.router import CLOUD_CELL, EdgeServer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.workloads import compile_scenario, get_scenario, list_scenarios

EDGE_ARCHS = ["smollm_135m", "starcoder2_3b", "mamba2_2p7b", "musicgen_medium"]


def make_fleet(n_servers: int, catalog, flops=197e12, slots=2, cell=0,
               drain_rate=0.0):
    """One cell of ``n_servers`` edge servers with staggered residencies."""
    return [
        EdgeServer(
            name=f"c{cell}-es{i}", flops_per_s=flops, cache_slots=slots,
            uplink_bps=100e6, backhaul_bps=1e9,
            resident=[(2 * i + j) % len(catalog) for j in range(slots)],
            cell=cell, drain_rate=drain_rate,
        )
        for i in range(n_servers)
    ]


def make_cloud_server(catalog, flops=2e15, uplink_bps=100e6,
                      backhaul_bps=1e9, drain_rate=0.0):
    """Cloud-fallback column: every model resident, visible fleet-wide.

    The cloud sits behind the backhaul, so its effective uplink folds the
    extra hop: 1/u_eff = 1/uplink + 1/backhaul (prompt bits traverse
    both links in series). With all models resident it never pays the
    eq. 7 switch, but the slower path + shared queue keep it a fallback
    rather than a free lunch."""
    u_eff = 1.0 / (1.0 / uplink_bps + 1.0 / backhaul_bps)
    return EdgeServer(
        name="cloud", flops_per_s=flops, cache_slots=len(catalog),
        uplink_bps=u_eff, backhaul_bps=backhaul_bps,
        resident=list(range(len(catalog))),
        cell=CLOUD_CELL, drain_rate=drain_rate,
    )


def make_multicell_fleet(n_cells: int, servers_per_cell: int, catalog,
                         flops=197e12, slots=2, drain_rate=0.0,
                         cloud=True):
    """C cells x N servers (+ one cloud fallback), one flat server list."""
    fleet = []
    for c in range(n_cells):
        fleet.extend(
            make_fleet(servers_per_cell, catalog, flops=flops, slots=slots,
                       cell=c, drain_rate=drain_rate)
        )
    if cloud:
        fleet.append(make_cloud_server(catalog, drain_rate=drain_rate))
    return fleet


def resolve_policy_flag(policy, fleet_params, *, sharded=False):
    """CLI policy flag -> ``route_batch`` policy. ``actor:<ckpt_dir>``
    restores a trained MADDPG-MATO actor through ``core.policies``;
    everything else passes through (builtin name or callable).

    ``sharded=True`` builds the actor against the cell-block-local
    geometry (``policies.actor_policy_for_cell_blocks``) so the one
    closure serves every shard of ``route_batch_sharded``.

    Checkpoint problems surface as a clean ``SystemExit`` (missing dir,
    no committed step, corrupt manifest/arrays, wrong checkpoint kind)
    instead of a traceback from deep inside the restore path."""
    if isinstance(policy, str) and policy.startswith("actor:"):
        ckpt = policy.split(":", 1)[1]
        if not ckpt:
            raise SystemExit(
                "serve: --policy actor: needs a checkpoint directory, e.g. "
                "--policy actor:benchmarks/results/actor_ckpt"
            )
        try:
            if not sharded:
                return policies.load_actor_policy(ckpt, fleet_params)
            params, spec, extra = policies.load_actor_checkpoint(ckpt)
            return policies.actor_policy_for_cell_blocks(
                params, spec, fleet_params,
                model_aware=extra.get("model_aware", True),
            )
        except (FileNotFoundError, NotADirectoryError) as e:
            raise SystemExit(
                f"serve: no actor checkpoint at {ckpt!r}: {e}\n"
                "train one with benchmarks/policy_serving.py (it saves "
                "under benchmarks/results/actor_ckpt)"
            ) from e
        except (ValueError, KeyError, OSError, TypeError) as e:
            raise SystemExit(
                f"serve: could not restore actor checkpoint {ckpt!r}: "
                f"{type(e).__name__}: {e}\n"
                "the directory exists but is not a readable "
                "core.policies.save_actor_checkpoint layout "
                "(step_<N>/manifest.json + committed arrays)"
            ) from e
    return policy


def validate_mesh_flag(mesh):
    """Fail fast — BEFORE any tracing — when ``--mesh D`` asks for more
    devices than this process can see. ``jax.Mesh`` would reject the
    device array anyway, but only after the fleet/stream setup work, and
    with a shape error that doesn't mention the XLA_FLAGS escape hatch."""
    if mesh is None:
        return
    avail = jax.local_device_count()
    if mesh < 1 or mesh > avail:
        raise SystemExit(
            f"serve: --mesh {mesh} needs {mesh} local devices but only "
            f"{avail} are available; on CPU hosts expose more via "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )


class Window(NamedTuple):
    """One routing window as ``serve`` builds it: the fleet, its array
    snapshot and the request stream compiled from ``(scenario, seed)``."""

    params: batch_router.FleetParams
    state: batch_router.FleetState
    reqs: batch_router.RequestBatch
    #: legacy per-request synchronous drain; ``None`` under a drain rate
    drain_tokens: Optional[float]
    cloud_index: Optional[int]  # the last server, on multi-cell fleets
    spec: object                # the ScenarioSpec the stream came from


def make_window(num_requests=32, n_servers=3, *, seed=0, gen_tokens=8,
                n_cells=1, drain_rate=0.0, arrival_rate=None,
                scenario="steady") -> Window:
    """Build the fleet and compile the request stream of one window."""
    # serve the edge-suitable (small) members of the catalogue
    catalog = build_catalog(EDGE_ARCHS)
    multicell = n_cells > 1
    if multicell:
        fleet = make_multicell_fleet(n_cells, n_servers, catalog,
                                     drain_rate=drain_rate)
    else:
        fleet = make_fleet(n_servers, catalog, drain_rate=drain_rate)
    fleet_params, fleet_state = batch_router.fleet_from_servers(fleet, catalog)

    # the whole stream — arrival stamps, model popularity, cells, prompt
    # sizes — compiles from (ScenarioSpec, seed): reproducible end to end
    spec = get_scenario(scenario, num_requests=num_requests)
    if arrival_rate is not None:
        spec = spec._replace(rate=arrival_rate)
    if gen_tokens is not None:  # None: keep the scenario's length range
        spec = spec._replace(gen_tokens=(gen_tokens, gen_tokens))
    reqs = compile_scenario(spec, seed=seed, num_models=len(catalog),
                            num_cells=n_cells)
    # with drain_rate > 0 the queues decay by drain_rate * dt between
    # arrivals; otherwise each routed request drains the fleet like the
    # old per-request loop
    drain_tokens = (
        None if drain_rate > 0.0
        else float(np.mean(np.asarray(reqs.gen_tokens))) * len(fleet)
        / max(num_requests, 1)
    )
    return Window(fleet_params, fleet_state, reqs, drain_tokens,
                  len(fleet) - 1 if multicell else None, spec)


def route_window(window: Window, policy="greedy", *, chunk=None,
                 backend=None, mesh=None):
    """Route the WHOLE window (all cells) in one jitted call; returns
    ``(state, outcome)``. It runs where the window's arrays live.

    Under ``mesh`` the window is ONE reconciliation window of the
    sharded router, which takes no per-request drain_tokens
    (docs/sharding.md) — drain only through drain_rate there."""
    if mesh is not None:
        from repro.core import mesh_router

        return mesh_router.route_batch_sharded(
            window.params, window.state, window.reqs, num_devices=mesh,
            policy=policy, chunk=chunk, backend=backend,
        )
    return batch_router.route_batch(
        window.params, window.state, window.reqs, window.drain_tokens,
        policy=policy, chunk=chunk, backend=backend,
    )


def serve(num_requests=32, n_servers=3, policy="greedy", execute=True, seed=0,
          gen_tokens=8, n_cells=1, drain_rate=0.0, arrival_rate=None,
          chunk=None, backend=None, scenario="steady", mesh=None):
    validate_mesh_flag(mesh)
    window = make_window(num_requests, n_servers, seed=seed,
                         gen_tokens=gen_tokens, n_cells=n_cells,
                         drain_rate=drain_rate, arrival_rate=arrival_rate,
                         scenario=scenario)
    policy = resolve_policy_flag(policy, window.params,
                                 sharded=mesh is not None)
    reqs = window.reqs

    # local reduced models actually generate tokens for routed requests
    models = {}
    if execute:
        for e in build_catalog(EDGE_ARCHS):
            cfg = reduced(get_arch(e.name))
            models[e.index] = (cfg, lm.init_params(jax.random.key(e.index), cfg))

    t0 = time.time()
    fleet_state, out = route_window(window, policy, chunk=chunk,
                                    backend=backend, mesh=mesh)
    jax.block_until_ready(out.choice)
    route_s = time.time() - t0

    if execute:
        gen_counts = np.asarray(reqs.gen_tokens).astype(int)
        for model_idx, n_gen in zip(np.asarray(reqs.model), gen_counts):
            cfg, params = models[int(model_idx)]
            n_gen = int(n_gen)
            B, P = 1, 8
            if cfg.modality == "audio":
                prompt = jnp.zeros((B, P, cfg.num_codebooks), jnp.int32)
            else:
                prompt = jnp.zeros((B, P), jnp.int32)
            ids, _, cache = lm.prefill(params, prompt, cfg)
            # token-by-token generation against a fresh full cache
            full = lm.init_cache(cfg, B, P + n_gen)

            def seat(dst, src):
                if src.shape == dst.shape:
                    return src.astype(dst.dtype)
                pad = [(0, d - s) for d, s in zip(dst.shape, src.shape)]
                return jnp.pad(src, pad).astype(dst.dtype)

            cache = jax.tree.map(seat, full, cache)
            tok = ids[:, -1:]
            for t in range(n_gen):
                tok, _, cache = lm.decode_step(
                    params, cache, tok, jnp.int32(P + t), cfg
                )

    stats = batch_router.stats(out, cloud_index=window.cloud_index)
    stats["route_s"] = route_s
    stats["wall_s"] = time.time() - t0
    stats["requests"] = num_requests
    stats["cells"] = n_cells
    stats["servers"] = int(window.params.flops_per_s.shape[0])
    stats["scenario"] = window.spec.name
    stats["seed"] = seed
    return stats


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--servers", type=int, default=3,
                    help="edge servers per cell")
    ap.add_argument("--cells", type=int, default=1,
                    help=">1 adds a block-diagonal cell mask + cloud column")
    ap.add_argument("--drain-rate", type=float, default=0.0,
                    help="tokens/sec continuous queue drain (0 = legacy "
                         "synchronous per-request drain)")
    ap.add_argument("--scenario", default="steady", choices=list_scenarios(),
                    help="registered workload shape compiled into the "
                         "request stream (see docs/scenarios.md)")
    ap.add_argument("--seed", type=int, default=0,
                    help="stream seed: the same (scenario, seed) "
                         "regenerates the stream bit-identically")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="override the scenario's base arrival rate "
                         "(req/s fleet-wide)")
    ap.add_argument("--gen-tokens", type=int, default=8,
                    help="constant generation length (default 8, matching "
                         "the Python API); pass 0 to serve the scenario's "
                         "[lo, hi) length range instead (execute time "
                         "scales with the token count)")
    ap.add_argument("--policy", default="greedy",
                    help="greedy | load | drain | actor:<ckpt_dir> (a "
                         "core.policies actor checkpoint, e.g. the one "
                         "benchmarks/policy_serving.py trains)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="two-phase commit chunk size (None = single-scan "
                         "path; 256 is a good default at fleet scale)")
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="scoring backend (default: $REPRO_ROUTER_BACKEND "
                         "or xla)")
    ap.add_argument("--no-execute", action="store_true",
                    help="route only (no local generation)")
    ap.add_argument("--mesh", type=int, default=None, metavar="D",
                    help="shard routing over D local devices "
                         "(core.mesh_router; the batch is one "
                         "reconciliation window — see docs/sharding.md). "
                         "CPU hosts expose extra devices via "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    args = ap.parse_args()
    stats = serve(args.requests, args.servers, args.policy,
                  execute=not args.no_execute, seed=args.seed,
                  gen_tokens=args.gen_tokens if args.gen_tokens > 0 else None,
                  n_cells=args.cells,
                  drain_rate=args.drain_rate,
                  arrival_rate=args.arrival_rate, chunk=args.chunk,
                  backend=args.backend, scenario=args.scenario,
                  mesh=args.mesh)
    for k, v in stats.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
