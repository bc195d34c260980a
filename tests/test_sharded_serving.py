"""Tensor-parallel paged serving: TP=n must be invisible to the tokens.

Runs only on a multi-device jax (the CI lane forces a 2-device CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=2``; a plain tier-1 run
skips cleanly).  Three invariants:

* **Token equivalence** — greedy decode under TP=2 is token-identical to the
  single-device engine across dense / moe / sliding-window archs, with
  prefix caching, chunked prefill and ngram speculative decoding enabled,
  on both attention backends (Pallas runs per-shard under ``shard_map``).
* **Sharding layout** — paged K/V pool leaves carry a NamedSharding
  partitioned on the kv-head axis; block tables stay replicated, and both
  survive engine steps (explicit jit out-specs, not propagation luck).
* **Host state is mesh-invariant** — allocator / prefix-index counters and
  the global ``cache_bytes()`` don't depend on mesh size; only
  ``cache_bytes(per_device=True)`` shrinks with TP.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import InferenceEngine

if jax.device_count() < 2:
    pytest.skip(
        "needs >= 2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=2)",
        allow_module_level=True,
    )

from repro.launch.mesh import make_serving_mesh  # noqa: E402  (after the skip guard)

# prompts with repetitive suffixes (the ngram drafter proposes real windows)
# and a shared leading prefix (the prefix cache registers and re-serves it)
SHARED = [11, 12, 13, 14, 15, 16, 17, 18]
PROMPTS = [
    SHARED + [7, 3, 9, 4] * 3 + [5],
    SHARED + [5, 9, 12, 5, 9, 12, 2],
    SHARED + [21, 22, 23, 24],
    SHARED + [7, 3, 9, 4] * 3 + [5],  # repeat: exercises a full prefix hit
]


def _make(arch, window=0):
    cfg = reduce_for_smoke(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if window:
        cfg = cfg.replace(sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _run(cfg, params, mesh=None, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        eng = InferenceEngine(
            cfg,
            params,
            max_batch=2,
            max_seq=64,
            block_size=8,
            cache_dtype=jnp.float32,
            mesh=mesh,
            **kw,
        )
        reqs = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
        eng.run_until_drained()
    return [r.generated for r in reqs], eng


# dense / moe / sliding-window x {plain, prefix+chunked+ngram-spec} x backend
TP_CASES = [
    ("olmo-1b", 0, "xla", {}),
    ("olmo-1b", 0, "pallas", {}),
    ("olmo-1b", 0, "xla", dict(spec_decode="ngram", spec_k=3, prefill_budget=8)),
    ("olmo-1b", 0, "pallas", dict(spec_decode="ngram", spec_k=3, prefill_budget=8)),
    ("qwen3-moe-235b-a22b", 0, "xla", dict(spec_decode="ngram", spec_k=3, prefill_budget=8)),
    ("olmo-1b", 8, "xla", dict(spec_decode="ngram", spec_k=3, prefill_budget=8)),
    ("olmo-1b", 8, "pallas", dict(spec_decode="ngram", spec_k=3)),
    # hybrid: blocking prefill+graft admission under the mesh (its odd head
    # count also exercises the replicated-pool divisibility fallback)
    ("hymba-1.5b", 0, "xla", {}),
]


@pytest.mark.parametrize("arch,window,impl,kw", TP_CASES)
def test_tp2_token_identical_to_tp1(arch, window, impl, kw, steer_decode):
    steer_decode(impl)
    cfg, params = _make(arch, window)
    base, eng = _run(cfg, params, attn_impl=impl, **kw)
    tp, tp_eng = _run(cfg, params, mesh=make_serving_mesh(2), attn_impl=impl, **kw)
    for e in (eng, tp_eng):
        assert e.metrics.get("engine_decode_attention_kernel").value == (impl == "pallas")
    assert base == tp, f"{arch}/w{window}/{impl}/{kw}: TP=2 changed greedy tokens"


def test_mqa_pallas_falls_back_and_matches():
    """num_kv_heads=1 can't shard over model=2: the engine refuses the Pallas
    kernels (no silent switch to the reference), and the XLA path serves the
    same tokens as one device from a replicated pool."""
    cfg, _ = _make("olmo-1b")
    cfg = cfg.replace(num_kv_heads=1)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    base, _ = _run(cfg, params, attn_impl="pallas")
    mesh = make_serving_mesh(2)
    with pytest.raises(ValueError, match="head counts"):
        InferenceEngine(cfg, params, max_batch=2, max_seq=64, mesh=mesh, attn_impl="pallas")
    tp, eng = _run(cfg, params, mesh=mesh, attn_impl="xla")
    assert tp == base
    # indivisible head count -> divisibility fallback replicates the pool
    assert eng.cache["k"].sharding.spec == jax.sharding.PartitionSpec(None, None, None, None, None)


def test_kv_pools_head_sharded_tables_replicated():
    cfg, params = _make("olmo-1b")
    mesh = make_serving_mesh(2)
    _, eng = _run(cfg, params, mesh=mesh)
    P = jax.sharding.PartitionSpec
    for name in ("k", "v"):
        sh = eng.cache[name].sharding
        assert isinstance(sh, jax.sharding.NamedSharding)
        # (L, num_blocks, block_size, kv_heads, head_dim): kv_heads partitioned
        assert sh.spec == P(None, None, None, "model", None), (name, sh.spec)
    assert eng.cache["tbl"].sharding.spec == P(None, None, None)
    # params: attention head projections shard over the model axis
    wq = eng.params["blocks"]["attn"]["wq"]
    flat = [a for e in wq.sharding.spec if e for a in (e if isinstance(e, tuple) else (e,))]
    assert "model" in flat, wq.sharding.spec


def test_cache_bytes_global_vs_per_device():
    cfg, params = _make("olmo-1b")
    _, base = _run(cfg, params)
    _, tp = _run(cfg, params, mesh=make_serving_mesh(2))
    # global (logical) bytes are mesh-invariant; per-device bytes shrink by
    # the pool shard and the two are consistent leaf-by-leaf
    assert tp.cache_bytes() == base.cache_bytes()
    assert tp.cache_bytes(per_device=True) < tp.cache_bytes()
    for name in ("k", "v"):
        leaf = tp.cache[name]
        import numpy as np

        shard = int(np.prod(leaf.sharding.shard_shape(leaf.shape))) * leaf.dtype.itemsize
        assert shard * 2 == leaf.size * leaf.dtype.itemsize
    s = tp.stats()
    assert s["tp"] == 2
    assert s["cache_bytes_per_device"] == tp.cache_bytes(per_device=True)


def test_allocator_and_prefix_counters_mesh_invariant():
    cfg, params = _make("olmo-1b")
    _, base = _run(cfg, params, spec_decode="ngram", spec_k=3, prefill_budget=8)
    _, tp = _run(cfg, params, mesh=make_serving_mesh(2), spec_decode="ngram", spec_k=3, prefill_budget=8)
    sb, st = base.stats(), tp.stats()
    keys = [k for k in sb if k.startswith(("alloc_", "prefix_"))]
    keys += ["prefill_tokens", "prefill_chunks", "evictions", "verify_tokens", "tokens_out"]
    for k in keys:
        assert sb[k] == st[k], f"{k}: {sb[k]} != {st[k]} under TP=2"
