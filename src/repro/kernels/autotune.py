"""Pallas paged-attention autotuner: sweep layouts, cache the winners.

The paged kernels expose two layout knobs whose best setting depends on the
problem shape, not the code:

* ``prefill_rows_per_tile`` — how many of the C*qpk query rows each grid
  step streams against a K/V page (``paged_prefill_attention_bhd``).  Small
  tiles shrink VMEM scratch but re-DMA every page once per tile; one big
  tile amortizes page reads but can blow the ~16 MB VMEM budget at long
  chunks.
* ``decode_kernel`` — single-token rows can run the dedicated decode kernel
  (``"paged"``, qpk-row tiles) or the multi-query prefill kernel at C=1
  (``"prefill1"``) whose masks degenerate to the decode masks exactly; on
  some shapes one layout pipelines better than the other.

``autotune()`` times every candidate per case with the same
block-until-ready loop as ``benchmarks/paged_attention.py`` (which exposes
the sweep as ``--autotune``) and records the winner under a key derived
from ``(head_dim, block_size, page_count, dtype)`` in the committed
``src/repro/kernels/autotune_defaults.json``.  Lookup reads that file
alone — an exact key, then the any-page-count key, then ``"default"`` — so
what gets compiled depends only on the checkout, never on files elsewhere
on the host.  The sweep refuses to run off-TPU: interpret-mode timings say
nothing about the chip.

``get_config`` is pure given the file (no timing at lookup), so a compiled
graph's layout is deterministic — the CI ``fused-step`` lane asserts that
two lookups and a table round-trip agree byte-for-byte.
"""

from __future__ import annotations

import json
import time
from functools import lru_cache
from pathlib import Path

DEFAULTS_PATH = Path(__file__).with_name("autotune_defaults.json")

DECODE_KERNELS = ("paged", "prefill1")
ROW_TILE_CANDIDATES = (0, 8, 16, 32)  # 0 = one tile holding every row

_DEFAULT_CONFIG = {"prefill_rows_per_tile": 0, "decode_kernel": "paged"}


def cache_key(head_dim: int, block_size: int, page_count: int, dtype) -> str:
    return f"hd{head_dim}_bs{block_size}_pages{page_count}_{str(dtype)}"


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return {}
    return table if isinstance(table, dict) else {}


@lru_cache(maxsize=None)
def _load_table(path: str) -> dict:
    return _read_json(Path(path))


def load_table(refresh: bool = False) -> dict:
    """The committed tuning table (``autotune_defaults.json``)."""
    if refresh:
        _load_table.cache_clear()
    return _load_table(str(DEFAULTS_PATH))


def _sanitize(entry) -> dict:
    cfg = dict(_DEFAULT_CONFIG)
    if isinstance(entry, dict):
        rt = entry.get("prefill_rows_per_tile", 0)
        if isinstance(rt, int) and rt >= 0:
            cfg["prefill_rows_per_tile"] = rt
        dk = entry.get("decode_kernel", "paged")
        if dk in DECODE_KERNELS:
            cfg["decode_kernel"] = dk
    return cfg


def get_config(head_dim: int, block_size: int, page_count: int, dtype) -> dict:
    """Tuned kernel config for one problem shape (falls back to defaults).

    Called at trace time by ``kernels.paged_attention_ops`` — shapes are
    static there, so the choice bakes into the compiled graph.
    """
    table = load_table()
    entry = table.get(cache_key(head_dim, block_size, page_count, dtype))
    if entry is None:
        entry = table.get(cache_key(head_dim, block_size, 0, dtype))  # any page count
    if entry is None:
        entry = table.get("default")
    return _sanitize(entry)


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------


def _time(fn, *args, iters: int = 5) -> float:
    import jax

    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _build_case(B: int, nb: int, bs: int, H: int, KV: int, hd: int, dtype):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(B * 131 + nb * 17 + hd)
    N = 1 + B * nb
    ks = jax.random.split(key, 4)
    q1 = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dtype)
    qc = jax.random.normal(ks[1], (B, 8, H, hd), jnp.float32).astype(dtype)
    k_pool = jax.random.normal(ks[2], (N, bs, KV, hd), jnp.float32).astype(dtype)
    v_pool = jax.random.normal(ks[3], (N, bs, KV, hd), jnp.float32).astype(dtype)
    tbl = jnp.arange(1, 1 + B * nb, dtype=jnp.int32).reshape(B, nb)
    lens = jnp.full((B,), nb * bs, jnp.int32)
    return q1, qc, k_pool, v_pool, tbl, lens


def tune_case(B: int, nb: int, bs: int, H: int, KV: int, hd: int, dtype="bfloat16", iters: int = 5) -> dict:
    """Time every candidate for one shape; return the winning config."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention_bhd, paged_prefill_attention_bhd

    dt = jnp.dtype(dtype)
    q1, qc, k_pool, v_pool, tbl, lens = _build_case(B, nb, bs, H, KV, hd, dt)

    best_rt, best_rt_t = 0, float("inf")
    rows = qc.shape[1] * (H // KV)
    for rt in ROW_TILE_CANDIDATES:
        if rt and (rt >= rows or rows % rt):
            continue
        fn = jax.jit(
            lambda q, k, v, t, s, _rt=rt: paged_prefill_attention_bhd(
                q, k, v, t, s, rows_per_tile=_rt
            )
        )
        dt_s = _time(fn, qc, k_pool, v_pool, tbl, jnp.zeros((B,), jnp.int32), iters=iters)
        if dt_s < best_rt_t:
            best_rt, best_rt_t = rt, dt_s

    decode_fns = {
        "paged": jax.jit(
            lambda q, k, v, t, sl: paged_attention_bhd(q, k, v, t, sl)
        ),
        "prefill1": jax.jit(
            lambda q, k, v, t, sl: paged_prefill_attention_bhd(q[:, None], k, v, t, sl - 1)[:, 0]
        ),
    }
    best_dk, best_dk_t = "paged", float("inf")
    for name, fn in decode_fns.items():
        dt_s = _time(fn, q1, k_pool, v_pool, tbl, lens, iters=iters)
        if dt_s < best_dk_t:
            best_dk, best_dk_t = name, dt_s

    return {
        "prefill_rows_per_tile": best_rt,
        "decode_kernel": best_dk,
        "prefill_s": best_rt_t,
        "decode_s": best_dk_t,
    }


def autotune(cases, dtype="bfloat16", iters: int = 5, out_path: Path | None = None) -> dict:
    """Sweep ``cases`` (tuples of (B, nb, block_size, H, KV, hd)) on the TPU
    and write the winners to ``out_path`` (default: the committed table)."""
    import jax

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"autotune sweeps only on a TPU (backend is {jax.default_backend()!r}): "
            "interpret-mode timings must not become kernel layouts"
        )
    out_path = Path(out_path) if out_path else DEFAULTS_PATH
    table = _read_json(out_path)
    for B, nb, bs, H, KV, hd in cases:
        won = tune_case(B, nb, bs, H, KV, hd, dtype=dtype, iters=iters)
        key = cache_key(hd, bs, nb, dtype)
        table[key] = {
            "prefill_rows_per_tile": won["prefill_rows_per_tile"],
            "decode_kernel": won["decode_kernel"],
        }
        print(f"{key}: {table[key]}  (prefill {won['prefill_s']*1e3:.3f} ms, decode {won['decode_s']*1e3:.3f} ms)")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    load_table(refresh=True)
    return table


def check_determinism() -> None:
    """CI guard: lookups are pure and the cache round-trips byte-stably."""
    table = load_table(refresh=True)
    assert isinstance(table, dict) and "default" in table, "defaults file must define 'default'"
    for key, entry in table.items():
        cfg = _sanitize(entry)
        assert cfg["decode_kernel"] in DECODE_KERNELS, (key, cfg)
        assert cfg["prefill_rows_per_tile"] >= 0, (key, cfg)
    a = get_config(64, 16, 8, "bfloat16")
    b = get_config(64, 16, 8, "bfloat16")
    assert a == b, "get_config must be deterministic"
    dumped = json.dumps(table, indent=2, sort_keys=True)
    assert json.dumps(json.loads(dumped), indent=2, sort_keys=True) == dumped
    print("autotune cache deterministic:", len(table), "entries")


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="Pallas paged-attention autotuner")
    ap.add_argument("--check", action="store_true", help="verify cache determinism, no sweep")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--out", default=None, help="table path (default: the committed defaults)")
    args = ap.parse_args(argv)
    if args.check:
        check_determinism()
        return
    try:  # canonical sweep shapes live with the benchmark harness
        from benchmarks.paged_attention import CASES
    except ImportError:
        CASES = [(4, 4, 16, 8, 2, 64), (8, 8, 16, 8, 2, 64), (4, 4, 32, 16, 4, 128)]
    autotune(CASES, dtype=args.dtype, iters=args.iters, out_path=args.out)


if __name__ == "__main__":
    main()
