"""Serving launcher: continuous-batching engine over a synthetic request mix.

    PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b \\
        --requests 12 --max-batch 4 --cache paged --block-size 16 \\
        --shared-prefix 32 --prefill-budget 16

    # tensor-parallel: one model instance over 2 devices (on CPU, force
    # host devices first)
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --tp 2

Runs the paper's inference QoS class end-to-end: online requests admitted
ahead of offline backfill, per-request TTFT, paged-pool block accounting and
engine utilization stats.  ``--shared-prefix N`` prepends a common N-token
system prompt to every request so the prefix cache's hit rate / saved
prefill tokens show up in the stats; ``--prefill-budget`` bounds prompt
tokens processed per engine step (chunked prefill interleaved with decode).
``--cache dense`` selects the slot-granular baseline; ``--quantize-kv
[int8|fp8]`` stores paged pools quantized (KIVI scales / e4m3);
``--fused`` lowers each scheduler tick to one jitted dispatch (plan →
unified batch → in-graph sample/accept); ``--spill-bytes N`` adds the tiered
KV cache — evicted prefix blocks spill to an N-byte host-RAM pool
(``--spill-dtype cache|int8|fp8`` picks the at-rest encoding) and swap back
on a prefix hit at ``--restore-budget`` blocks per step; ``--attn-impl
pallas`` routes prefill chunks (and speculative verify) through the paged
chunk kernel (decode attention is the Pallas kernel on a TPU either way); ``--spec-decode
ngram|draft`` turns on speculative decoding with ``--spec-k`` drafted tokens
per verify pass; ``--tp N`` shards params and the paged K/V pools over a
``(data=1, model=N)`` mesh — the paper's 4-way Grace-Hopper node is
``--tp 4`` (see docs/serving.md for the tuning guide and the
sharded-vs-replicated state matrix).  ``--metrics-json`` / ``--trace-out``
dump the observability layer's registry snapshot and Chrome trace after the
drain, and ``--profile DIR`` writes a ``jax.profiler`` trace of the serve to
DIR: the device's named programs and the engine's ``engine.*`` /
``async.*`` phase spans on one clock (see docs/observability.md).

``--http`` switches from the synthetic closed-loop drive to the always-on
service: an asyncio stepping loop (``serving.async_engine``) plus a
stdlib HTTP/SSE front-end (``serving.http``) on ``--host``/``--port`` —
``POST /v1/generate`` streams tokens as Server-Sent Events, ``GET /metrics``
exposes the Prometheus registry, ``GET /stats`` / ``GET /healthz`` serve
JSON.  ``--policy slo|fcfs`` selects the scheduler: ``slo`` (default) orders
by per-request ``priority``/``deadline_s`` and preempts lower-priority work
under pool pressure; ``fcfs`` ignores SLO knobs.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --http \\
        --port 8731 --prefill-budget 16

``--replicas N`` runs N independent engines behind the prefix-affinity
``serving.router.Router`` — each replica on its own ``(data=1, model=tp)``
device slice (``launch.mesh.make_replica_meshes``); it fails when fewer
than ``N*tp`` devices are visible.
The router health-checks replicas, fails over in-flight requests and
supports graceful drain; combine with ``--http`` for an always-on
multi-replica service.  SIGTERM/SIGINT on the ``--http`` path triggers a
graceful drain (stop admission, finish active requests) before the
``--metrics-json`` / ``--trace-out`` flush.

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --replicas 2 \\
        --shared-prefix 32 --prefill-budget 16

Without ``--full`` the model is the ``reduce_for_smoke`` shrink with float32
weights and ``max_seq`` 256 — the CPU smoke size.  ``--full`` serves the
published widths: bf16 weights built under ``jax.jit`` straight into their
target sharding (never float32, never gathered on one device first), with
``--max-seq`` and ``--num-blocks`` sizing the paged pool:

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --full \\
        --max-seq 2048 --num-blocks 2049 --max-batch 8 --prefill-budget 256
"""

from __future__ import annotations

import argparse
import contextlib
import random

import jax
import jax.numpy as jnp

from repro.config.model import reduce_for_smoke
from repro.configs import ASSIGNED, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import abstract_params, init_params
from repro.serving import InferenceEngine
from repro.serving.engine import serving_rules

DTYPES = {"bf16": jnp.bfloat16, "fp32": jnp.float32}


def serving_config(arch: str, *, full: bool):
    """The arch's config at published widths (``full``) or the smoke shrink."""
    cfg = get_config(arch)
    return cfg if full else reduce_for_smoke(cfg)


def init_serving_params(cfg, *, seed: int, dtype, mesh=None):
    """Random weights from ``seed``, built by one jitted program whose
    ``out_shardings`` are the serving placement: each device materializes
    only its own shard, in ``dtype``.  ``mesh=None`` places on one device."""
    if mesh is None:
        out = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    else:
        out = serving_rules().param_shardings(cfg, mesh, abstract_params(cfg, dtype))
    build = jax.jit(lambda key: init_params(cfg, key, dtype), out_shardings=out)
    return build(jax.random.PRNGKey(seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral-nemo-12b", choices=ASSIGNED)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--full", action="store_true",
        help="published widths with bf16 weights (default: the reduced smoke "
        "config with float32 weights)",
    )
    ap.add_argument("--max-seq", type=int, default=256, help="longest prompt + generation")
    ap.add_argument("--cache", default="paged", choices=("paged", "dense"))
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--cache-dtype", default="bf16", choices=sorted(DTYPES))
    ap.add_argument(
        "--quantize-kv", nargs="?", const="int8", default=False,
        choices=("int8", "fp8"),
        help="quantized paged block pools (bare flag = int8)",
    )
    ap.add_argument("--attn-impl", default="xla", choices=("xla", "pallas"))
    ap.add_argument(
        "--fused", action="store_true",
        help="fused one-dispatch step: one jitted dispatch + one host sync "
        "per scheduler tick (chunked paged families only)",
    )
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--shared-prefix", type=int, default=0,
        help="prepend a common N-token system prompt to every request",
    )
    ap.add_argument(
        "--no-prefix-cache", action="store_true",
        help="disable prefix caching (measure the re-prefill baseline)",
    )
    ap.add_argument(
        "--prefill-budget", type=int, default=0,
        help="max prompt tokens prefilled per step (0 = unbounded)",
    )
    ap.add_argument(
        "--spill-bytes", type=int, default=0,
        help="host-RAM budget for the spill tier: evicted prefix blocks park "
        "in pinned host memory instead of being dropped (0 = drop on evict)",
    )
    ap.add_argument(
        "--spill-dtype", default="cache", choices=("cache", "int8", "fp8"),
        help="at-rest encoding for spilled blocks: 'cache' stores pool-native "
        "rows (bit-exact), 'int8'/'fp8' compress on the way out",
    )
    ap.add_argument(
        "--restore-budget", type=int, default=4,
        help="max spilled blocks swapped back per scheduler step (bounds "
        "host->device traffic interleaved with decode)",
    )
    ap.add_argument(
        "--spec-decode", default="off", choices=("off", "ngram", "draft"),
        help="speculative decoding: n-gram prompt lookup or a reduced-depth "
        "draft model (verify pass through the chunked-prefill kernel)",
    )
    ap.add_argument(
        "--spec-k", type=int, default=4,
        help="drafted tokens scored per verify pass (reserves spec-k "
        "positions of per-request block headroom)",
    )
    ap.add_argument(
        "--replicas", type=int, default=1,
        help="independent engine replicas behind the prefix-affinity router "
        "(each on its own (1, tp) device slice when replicas*tp devices are "
        "visible; health checks + failover + graceful drain)",
    )
    ap.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel degree: shard params + paged KV pools over a "
        "(data=1, model=tp) mesh (CPU: set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N first)",
    )
    ap.add_argument(
        "--metrics-json", default=None, metavar="PATH",
        help="write the metrics registry snapshot (counters/gauges/histogram "
        "percentiles) as JSON after the drain",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="write the request-lifecycle trace as Chrome-trace JSON "
        "(open in chrome://tracing or ui.perfetto.dev)",
    )
    ap.add_argument(
        "--profile", default=None, metavar="DIR",
        help="write a jax.profiler trace of the serve to DIR: device programs "
        "and the engine's phase spans on one clock (adds no host syncs)",
    )
    ap.add_argument(
        "--policy", default="slo", choices=("slo", "fcfs"),
        help="scheduler policy: 'slo' honors priority/deadline_s and "
        "preempts under pressure; 'fcfs' is strict arrival order",
    )
    ap.add_argument(
        "--http", action="store_true",
        help="serve an always-on HTTP/SSE front-end instead of draining a "
        "synthetic batch (POST /v1/generate streams tokens; GET /metrics, "
        "/stats, /healthz)",
    )
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = serving_config(args.arch, full=args.full)
    if cfg.is_encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only; no decode serving")
    if args.replicas < 1:
        raise SystemExit(f"--replicas {args.replicas} (need >= 1)")

    meshes: list = [None] * args.replicas
    if args.replicas * args.tp > 1:
        from repro.launch.mesh import make_replica_meshes

        try:
            meshes = make_replica_meshes(args.replicas, args.tp)
        except ValueError as e:
            raise SystemExit(f"[serve] {e}") from e
        print(f"[serve] {args.replicas} replica(s) x tp={args.tp}: disjoint device slices")

    param_dtype = jnp.bfloat16 if args.full else jnp.float32

    def build_engine(mesh):
        params = init_serving_params(cfg, seed=args.seed, dtype=param_dtype, mesh=mesh)
        return InferenceEngine(
            cfg,
            params,
            mesh=mesh,
            max_batch=args.max_batch,
            max_seq=args.max_seq,
            seed=args.seed,
            cache_kind=args.cache,
            block_size=args.block_size,
            num_blocks=args.num_blocks,
            cache_dtype=DTYPES[args.cache_dtype],
            quantize_kv=args.quantize_kv,
            attn_impl=args.attn_impl,
            fused=args.fused,
            prefix_cache=False if args.no_prefix_cache else None,
            prefill_budget=args.prefill_budget,
            spill_bytes=args.spill_bytes,
            spill_dtype=args.spill_dtype,
            restore_budget=args.restore_budget,
            policy=args.policy,
            spec_decode=args.spec_decode,
            spec_k=args.spec_k,
            trace_capacity=65536 if args.trace_out else 4096,
        )

    if args.replicas > 1:
        from repro.serving import Replica, Router

        replicas = [Replica(i, build_engine(meshes[i])) for i in range(args.replicas)]
        eng = Router(replicas, trace_capacity=65536 if args.trace_out else 4096)
    else:
        eng = build_engine(meshes[0])

    with jax.profiler.trace(args.profile) if args.profile else contextlib.nullcontext():
        serve(args, cfg, eng)
    if args.profile:
        print(f"[serve] profiler trace -> {args.profile}")


def serve(args, cfg, eng) -> None:
    """Serve over HTTP, or drain the synthetic request mix and report."""
    if args.http:
        import asyncio

        from repro.serving.http import serve_http

        try:
            asyncio.run(
                serve_http(
                    eng,
                    host=args.host,
                    port=args.port,
                    metrics_json=args.metrics_json,
                    trace_out=args.trace_out,
                )
            )
        except KeyboardInterrupt:
            print("[serve] shutting down")
        return

    rng = random.Random(args.seed)
    system = [rng.randrange(2, cfg.vocab_size) for _ in range(args.shared_prefix)]
    reqs = []
    for i in range(args.requests):
        prompt = system + [rng.randrange(2, cfg.vocab_size) for _ in range(rng.randint(2, 8))]
        reqs.append(
            eng.submit(
                prompt,
                max_new_tokens=args.max_new,
                online=(i % 3 != 0),
                temperature=args.temperature,
                top_k=args.top_k,
            )
        )
    eng.run_until_drained()
    for r in reqs:
        online = r.online if hasattr(r, "online") else r.kwargs.get("online", True)
        kind = "online " if online else "offline"
        ttft = f"{r.ttft*1e3:8.1f}ms" if r.ttft is not None else "   never admitted"
        hit_toks = getattr(r, "prefix_hit_tokens", 0)
        hit = f" prefix_hit={hit_toks:3d}" if hit_toks else ""
        rep = f" replica={r.replica_id}" if hasattr(r, "replica_id") else ""
        print(f"req {r.req_id:3d} [{kind}] ttft={ttft} len={len(r.generated)}{hit}{rep} head={r.generated[:6]}")
    print("[serve] stats:", eng.stats())
    for name in ("engine_ttft_seconds", "engine_tpot_seconds", "engine_step_seconds"):
        p = eng.metrics.percentiles(name)
        if p[50] is not None:
            pretty = "  ".join(f"p{int(k)}={v*1e3:.2f}ms" for k, v in p.items())
            print(f"[serve] {name}: {pretty}")
    if args.metrics_json:
        eng.metrics.write_json(args.metrics_json)
        print(f"[serve] metrics snapshot -> {args.metrics_json}")
    if args.trace_out:
        eng.tracer.write(args.trace_out)
        print(
            f"[serve] chrome trace -> {args.trace_out} "
            f"({len(eng.tracer.events)} events, {eng.tracer.dropped} dropped)"
        )


if __name__ == "__main__":
    main()
