"""Paged-attention decode microbenchmark (Pallas kernel vs jnp gather oracle).

Sweeps decode-batch / context-length points, checks the Pallas kernel
against the oracle at every point, and times both paths plus the dense
(contiguous-cache) attention equivalent.  Wall-clock columns are
CPU/interpret measured; the ``derived`` column carries the modeled HBM
traffic per decode step (the quantity the paged layout exists to bound —
decode attention is memory-bound, so bytes-touched is the roofline term).
Results land in ``benchmarks/results/paged_attention.json`` so the perf
trajectory picks the sweep up.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention
from repro.kernels.paged_attention_ref import paged_attention_ref

RESULTS_DIR = Path(__file__).resolve().parent / "results"

# (B, nb, block_size, H, KV, hd)
CASES = [
    (4, 4, 16, 8, 2, 64),
    (8, 8, 16, 8, 2, 64),
    (4, 4, 32, 16, 4, 128),
]
HBM_GBPS = 819e9  # v5e per-chip HBM bandwidth


def _time(fn, *args, iters: int = 3) -> float:
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


def run() -> list[dict]:
    rows = []
    for B, nb, bs, H, KV, hd in CASES:
        N = 1 + B * nb
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
        kp = jax.random.normal(ks[1], (N, bs, KV, hd), jnp.float32)
        vp = jax.random.normal(ks[2], (N, bs, KV, hd), jnp.float32)
        tbl = jnp.arange(1, 1 + B * nb, dtype=jnp.int32).reshape(B, nb)
        lens = jnp.full((B,), nb * bs, jnp.int32)

        ref = paged_attention_ref(q, kp, vp, tbl, lens)
        out = paged_attention(q, kp, vp, tbl, lens)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 2e-5, f"paged attention diverged from oracle: {err}"

        ref_us = _time(jax.jit(paged_attention_ref), q, kp, vp, tbl, lens) * 1e6
        pal_us = _time(paged_attention, q, kp, vp, tbl, lens, iters=2) * 1e6
        # decode reads each sequence's K+V once per step (2 bytes bf16 on HW)
        hbm_bytes = 2 * B * nb * bs * KV * hd * 2
        modeled_us = hbm_bytes / HBM_GBPS * 1e6
        name = f"paged_attn_b{B}_ctx{nb * bs}_kv{KV}x{hd}"
        rows.append(
            {
                "name": f"{name}_oracle",
                "us_per_call": ref_us,
                "derived": f"modeled_v5e_hbm_us={modeled_us:.3f} maxerr_vs_pallas={err:.1e}",
            }
        )
        rows.append(
            {
                "name": f"{name}_pallas_interp",
                "us_per_call": pal_us,
                "derived": f"modeled_v5e_hbm_us={modeled_us:.3f} kv_bytes={hbm_bytes}",
            }
        )

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    (RESULTS_DIR / "paged_attention.json").write_text(json.dumps(rows, indent=1))
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description="paged-attention microbenchmark")
    ap.add_argument(
        "--autotune", action="store_true",
        help="sweep kernel layout knobs over the benchmark CASES on a TPU and "
        "write winners to the committed table (see repro.kernels.autotune)",
    )
    ap.add_argument("--iters", type=int, default=5, help="timing reps per candidate")
    ap.add_argument("--dtype", default="bfloat16", help="pool dtype for the sweep")
    ap.add_argument("--out", default=None, help="autotune table path override")
    args = ap.parse_args()
    if args.autotune:
        from repro.kernels.autotune import autotune

        autotune(CASES, dtype=args.dtype, iters=args.iters, out_path=args.out)
        return
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")


if __name__ == "__main__":
    main()
