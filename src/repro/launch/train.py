"""Training launcher: any assigned arch, real training on the local devices.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-235b-a22b \\
        --steps 5 --reduced --microbatches 2

On this CPU image ``--reduced`` (default) shrinks the config to the smoke
size; on a pod the same launcher takes ``--full`` and builds the production
mesh + sharding trees from ``repro.launch.specs``.  Fault tolerance
(heartbeats + checkpoint/restart) and DCIM energy accounting run in-line,
exactly as the paper's flex-start class requires.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.config import ParallelConfig, PrecisionConfig, RunConfig, TrainConfig
from repro.config.model import reduce_for_smoke
from repro.configs import ASSIGNED, get_config
from repro.core import Cluster, ClusterSpec, EnergyLedger, FaultTolerantRunner
from repro.data import make_batch_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.train.step import init_train_state, make_train_step


def build_training(
    arch: str,
    *,
    full: bool,
    batch: int,
    seq: int,
    steps: int,
    microbatches: int = 1,
    fp8: bool = False,
    seed: int = 0,
):
    """The launcher's training set-up: ``(cfg, run, state, jitted step,
    batch_fn)`` at published widths (``full``) or the smoke shrink."""
    cfg = get_config(arch)
    if not full:
        cfg = reduce_for_smoke(cfg)
    run = RunConfig(
        arch=arch,
        train=TrainConfig(global_batch=batch, seq_len=seq, warmup_steps=5, total_steps=steps),
        parallel=ParallelConfig(num_microbatches=microbatches, remat="full"),
        precision=PrecisionConfig(fp8=fp8),
    )
    state = init_train_state(cfg, run, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(cfg, run))
    batch_fn = make_batch_fn(cfg, global_batch=batch, seq_len=seq, seed=seed)
    return cfg, run, state, step, batch_fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=ASSIGNED + ["bert-large"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", dest="reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=0, help="inject a node failure at this step (chaos test)")
    ap.add_argument("--fp8", action="store_true", help="FP8 quantized training (repro.fp8 delayed scaling)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg, run, state, step, batch_fn = build_training(
        args.arch,
        full=not args.reduced,
        batch=args.batch,
        seq=args.seq,
        steps=args.steps,
        microbatches=args.microbatches,
        fp8=args.fp8,
        seed=args.seed,
    )
    print(f"[train] {cfg.name} family={cfg.family} params={cfg.param_count()/1e6:.1f}M "
          f"(reduced={args.reduced})")
    if args.fp8:
        # 2 scale keys (x + w operand) per quantized GEMM site
        n_sites = 0 if state.fp8 is None else len(state.fp8.scale) // 2
        print(f"[train] fp8: {'ON' if state.fp8 is not None else 'unsupported family, bf16 fallback'}"
              f" ({n_sites} gemm sites, window={run.precision.fp8_amax_history})")

    cluster = Cluster(ClusterSpec("local", nodes_per_pod=2, num_pods=1))
    cluster.allocate([0, 1], "train")
    for n in cluster.nodes.values():
        cluster.heartbeat(n.node_id, 0.0)
    runner = FaultTolerantRunner(
        step_fn=step,
        init_state=state,
        batch_fn=batch_fn,
        cluster=cluster,
        ckpt=CheckpointManager(f"{args.ckpt_dir}/{cfg.name}", keep=2, async_save=False),
        job_id="train",
        checkpoint_every=args.ckpt_every,
        ledger=EnergyLedger(),
    )
    schedule = {args.fail_at: 1} if args.fail_at else None
    t0 = time.time()
    report = runner.run(args.steps, failure_schedule=schedule)
    dt = time.time() - t0
    last = max(report.losses)
    print(f"[train] {report.steps_run} steps in {dt:.1f}s  "
          f"loss {report.losses[min(report.losses)]:.4f} -> {report.losses[last]:.4f}  "
          f"failures={report.failures} restores={report.restores}")
    print(f"[train] energy: {runner.ledger.report()}")


if __name__ == "__main__":
    main()
