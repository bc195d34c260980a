"""Prefix-cached paged serving: refcounted allocator + LRU eviction
invariants, prefix-index matching (full blocks, partial-tail copy-on-write),
chunked-prefill kernel vs oracle, token equivalence of the cached + chunked
engine against the uncached/unchunked baselines, and the scheduling
satellites (batched sampling, auto-defrag, queue discipline)."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.kernels import paged_prefill_attention
from repro.kernels.paged_attention_ref import paged_prefill_attention_ref
from repro.models import forward, init_params
from repro.serving import (
    BlockAllocator,
    InferenceEngine,
    PrefixIndex,
    RequestState,
    binary_chunks,
    sample_token,
    sample_tokens,
)


# ---------------------------------------------------------------------------
# allocator: refcounts, cached pool, eviction
# ---------------------------------------------------------------------------


def test_refcount_shared_free():
    a = BlockAllocator(9)
    blocks = a.alloc(3)
    for b in blocks:
        a.incref(b)  # second sharer
    a.free(blocks)  # first sharer drops out
    assert a.blocks_in_use == 3, "shared blocks must survive one sharer's free"
    assert a.num_free == 5
    a.free(blocks)  # last sharer
    assert a.blocks_in_use == 0 and a.num_free == 8
    with pytest.raises(ValueError):
        a.free(blocks)  # double free of dead blocks
    with pytest.raises(ValueError):
        a.incref(blocks[0])  # incref on a dead block


def test_cached_pool_counts_as_free_and_reuses():
    a = BlockAllocator(5)
    blocks = a.alloc(4)
    a.free_cached(blocks)
    assert a.blocks_in_use == 0
    assert a.num_cached == 4
    assert a.num_free == 4, "cached blocks are evictable, hence free for gating"
    a.reuse_cached(blocks[1])  # prefix hit revives without eviction
    assert a.refcount(blocks[1]) == 1 and a.num_cached == 3
    with pytest.raises(ValueError):
        a.reuse_cached(blocks[1])  # no longer cached


def test_eviction_is_lru_and_notifies():
    evicted = []
    a = BlockAllocator(5, on_evict=evicted.append)
    blocks = a.alloc(4)
    a.free_cached(blocks[:2])  # oldest
    a.free_cached(blocks[2:])  # newest
    got = a.alloc(3)  # free list is empty -> evicts 3 oldest cached blocks
    assert evicted == blocks[:3], "eviction must be oldest-first"
    assert set(got) == set(blocks[:3])
    assert a.evictions == 3 and a.num_cached == 1


def test_fragmentation_defrag_boundary_cases():
    a = BlockAllocator(5)
    assert a.fragmentation() == 0.0  # pristine free list
    blocks = a.alloc(4)
    assert a.fragmentation() == 0.0 and a.defrag() == 0.0  # empty free list
    a.free(blocks[:1])
    assert a.fragmentation() == 0.0  # single free block is trivially contiguous
    a.free(blocks[2:3])
    assert a.fragmentation() > 0.0  # {b0, b2}: a hole
    a.free(blocks[1:2])
    a.defrag()
    assert a.fragmentation() == 0.0
    # cached blocks never enter the free-list fragmentation accounting
    a2 = BlockAllocator(5)
    bs = a2.alloc(4)
    a2.free_cached(bs)
    assert a2.fragmentation() == 0.0


def test_eviction_under_pressure_keeps_live_blocks():
    a = BlockAllocator(6, on_evict=lambda b: None)
    live = a.alloc(3)
    cached = a.alloc(2)
    a.free_cached(cached)
    got = a.alloc(2)  # must evict the cached pair, never touch live
    assert set(got) == set(cached)
    assert all(a.refcount(b) == 1 for b in live)


# ---------------------------------------------------------------------------
# prefix index
# ---------------------------------------------------------------------------


def _index(num_blocks=17, bs=4):
    return PrefixIndex(BlockAllocator(num_blocks), bs)


def test_prefix_match_full_blocks_and_cap():
    idx = _index()
    prompt = list(range(10, 26))  # 16 tokens = 4 full blocks @ bs 4
    blocks = idx.allocator.alloc(4)
    idx.register(prompt, blocks, upto=16)
    assert len(idx) == 4
    # identical prompt: the cap must leave >= 1 token to prefill -> 3 blocks
    full, partial = idx.match(prompt)
    assert full == blocks[:3]
    assert partial is None or partial.block == blocks[3]
    # longer prompt with the same prefix: all 4 blocks match
    full, partial = idx.match(prompt + [99, 98])
    assert full == blocks
    assert partial is None
    # diverging second block: only the first matches
    other = prompt[:4] + [77, 77, 77, 77] + prompt[8:] + [1]
    full, _ = idx.match(other)
    assert full == blocks[:1]


def test_prefix_partial_tail_match():
    idx = _index()
    prompt = list(range(10, 22))  # 3 full blocks
    blocks = idx.allocator.alloc(3)
    idx.register(prompt, blocks, upto=12)
    probe = prompt[:8] + [prompt[8], prompt[9], 555, 556, 557]
    full, partial = idx.match(probe)
    assert full == blocks[:2]
    assert partial is not None and partial.block == blocks[2] and partial.tokens == 2


def test_prefix_eviction_unmaps():
    idx = _index(num_blocks=5, bs=4)
    prompt = list(range(8))
    blocks = idx.allocator.alloc(2)
    idx.register(prompt, blocks, upto=8)
    idx.release(blocks)  # refcount 0 -> LRU cached pool, still matchable
    assert idx.match(prompt + [9])[0] == blocks
    idx.allocator.alloc(4)  # forces eviction of both cached blocks
    assert idx.match(prompt + [9]) == ([], None), "evicted blocks must unmap"
    assert len(idx) == 0


def test_prefix_release_routes_indexed_blocks_to_cache():
    idx = _index()
    prompt = list(range(8))
    blocks = idx.allocator.alloc(3)  # 2 full prompt blocks + 1 generation block
    idx.register(prompt, blocks[:2], upto=8)
    idx.release(blocks)
    assert idx.allocator.num_cached == 2, "indexed blocks park in the LRU pool"
    assert idx.allocator.blocks_in_use == 0  # unindexed block freed eagerly


def test_binary_chunks():
    assert binary_chunks(52) == [32, 16, 4]
    assert binary_chunks(1) == [1]
    assert binary_chunks(8) == [8]
    for n in range(1, 200):
        parts = binary_chunks(n)
        assert sum(parts) == n
        assert parts == sorted(parts, reverse=True)
        assert all(p & (p - 1) == 0 for p in parts)


# ---------------------------------------------------------------------------
# chunked-prefill Pallas kernel vs jnp oracle
# ---------------------------------------------------------------------------

CHUNK_KERNEL_CASES = [
    # B, nb, bs, C, H, KV, hd, window, softcap, dtype
    (2, 4, 8, 5, 4, 2, 16, 0, 0.0, jnp.float32),
    (1, 3, 16, 8, 8, 2, 32, 0, 0.0, jnp.float32),
    (2, 4, 8, 6, 4, 4, 16, 10, 0.0, jnp.float32),  # sliding window
    (1, 2, 8, 3, 2, 1, 64, 0, 30.0, jnp.float32),  # MQA + softcap
    (2, 4, 8, 4, 4, 2, 16, 0, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", CHUNK_KERNEL_CASES)
def test_chunked_prefill_kernel_matches_oracle(case):
    B, nb, bs, C, H, KV, hd, win, cap, dt = case
    N = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, C, H, hd), dt)
    kp = jax.random.normal(ks[1], (N, bs, KV, hd), dt)
    vp = jax.random.normal(ks[2], (N, bs, KV, hd), dt)
    perm = jax.random.permutation(jax.random.PRNGKey(7), N - 1) + 1
    tbl = perm[: B * nb].reshape(B, nb).astype(jnp.int32)
    start = jnp.array([(5 * b + 2) % (nb * bs - C) for b in range(B)], jnp.int32)
    out = paged_prefill_attention(q, kp, vp, tbl, start, softcap=cap, window=win)
    ref = paged_prefill_attention_ref(q, kp, vp, tbl, start, softcap=cap, window=win)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < tol, f"{case}: err={err}"


def test_chunked_prefill_row_tiles_match_oracle(monkeypatch):
    """Rows that overflow the VMEM budget are split into row tiles by the
    kernel itself; the tiled result still equals the oracle."""
    import importlib

    # the module, not the same-named wrapper ``repro.kernels`` re-exports
    pa = importlib.import_module("repro.kernels.paged_attention")

    B, nb, bs, C, H, KV, hd = 2, 4, 8, 8, 8, 2, 32  # 32 query rows per kv head
    per_row = KV * (4 * hd * 4 + 4 * hd + 2 * 4 * 128)
    monkeypatch.setattr(pa, "ROW_TILE_VMEM_BYTES", 8 * per_row)
    assert pa._row_tile(C * H // KV, KV, hd, 4, 0) == 8
    N = 1 + B * nb
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, C, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, KV, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, KV, hd), jnp.float32)
    tbl = (jax.random.permutation(jax.random.PRNGKey(7), N - 1) + 1).reshape(B, nb).astype(jnp.int32)
    start = jnp.array([3, 17], jnp.int32)
    out = pa.paged_prefill_attention_bhd(q, kp, vp, tbl, start)
    ref = paged_prefill_attention_ref(q, kp, vp, tbl, start)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


# ---------------------------------------------------------------------------
# engine: cached + chunked == uncached/unchunked (greedy token equivalence)
# ---------------------------------------------------------------------------


def _make(arch, window=0):
    cfg = reduce_for_smoke(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if window:
        cfg = cfg.replace(sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


EQUIV_CASES = [
    ("olmo-1b", 0, "xla"),
    ("olmo-1b", 0, "pallas"),
    ("olmo-1b", 8, "xla"),  # sliding-window arch
    ("qwen3-moe-235b-a22b", 0, "xla"),
    ("hymba-1.5b", 0, "xla"),  # hybrid: feature safely disabled internally
]


@pytest.mark.parametrize("arch,window,impl", EQUIV_CASES)
def test_cached_chunked_engine_matches_baselines(arch, window, impl, steer_decode):
    """Prefix caching + chunked prefill must reproduce the dense-cache
    engine (fully independent prefill/decode path) and the uncached paged
    engine token-for-token under greedy sampling, with real sharing (the
    requests run back-to-back, so later prompts hit the registered prefix).
    """
    steer_decode(impl)
    cfg, params = _make(arch, window)
    sys_prompt = [7, 3, 9, 4, 11, 2, 6, 8, 13, 5, 10, 12, 14, 15, 16, 17]
    prompts = [sys_prompt + [30 + i] for i in range(3)] + [[5, 9, 12]]
    outs, stats = {}, {}
    variants = {
        "dense": dict(cache_kind="dense"),
        "uncached": dict(prefix_cache=False),
        "cached": dict(prefix_cache=True),
        "cached_budget": dict(prefix_cache=True, prefill_budget=4),
    }
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for label, kw in variants.items():
            eng = InferenceEngine(
                cfg, params, max_batch=2, max_seq=64, block_size=8,
                cache_dtype=jnp.float32, attn_impl=impl, **kw,
            )
            gen = []
            for p in prompts:  # sequential: sharing kicks in from request 2
                r = eng.submit(p, max_new_tokens=5)
                eng.run_until_drained()
                gen.append(r.generated)
            outs[label] = gen
            stats[label] = eng.stats()
    assert outs["cached"] == outs["dense"], f"{arch}: cached diverged from dense"
    assert outs["cached_budget"] == outs["dense"]
    assert outs["uncached"] == outs["dense"]
    if arch != "hymba-1.5b":  # hybrid can't share (blocking prefill path)
        assert stats["cached"]["prefix_hit_tokens"] >= 2 * 16, stats["cached"]
        saved = stats["uncached"]["prefill_tokens"] - stats["cached"]["prefill_tokens"]
        assert saved == stats["cached"]["prefix_hit_tokens"]


def test_partial_tail_copy_on_write_engine():
    cfg, params = _make("olmo-1b")
    sys24 = list(range(2, 26))  # 3 full blocks @ bs 8
    p1 = sys24 + [30]
    p2 = sys24[:20] + [99, 98, 97, 96]  # full blocks 0-1 + 4 tokens of block 2
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, block_size=8,
                          cache_dtype=jnp.float32, prefix_cache=True)
    eng.submit(p1, max_new_tokens=4)
    eng.run_until_drained()
    r2 = eng.submit(p2, max_new_tokens=4)
    eng.run_until_drained()
    s = eng.stats()
    assert s["prefix_partial_hits"] == 1
    assert r2.prefix_hit_tokens == 20  # 16 full + 4 copied-on-write
    ref = InferenceEngine(cfg, params, max_batch=1, max_seq=64, block_size=8,
                          cache_dtype=jnp.float32, prefix_cache=False)
    q2 = ref.submit(p2, max_new_tokens=4)
    ref.run_until_drained()
    assert r2.generated == q2.generated, "COW hit changed greedy tokens"


def test_engine_eviction_under_pressure():
    """A pool too small to cache every finished prompt must evict LRU
    entries on demand — and keep serving correctly."""
    cfg, params = _make("olmo-1b")
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, block_size=8,
                          num_blocks=7, cache_dtype=jnp.float32, prefix_cache=True)
    for i in range(4):
        eng.submit([50 + i] + list(range(2, 18)) + [60 + i] * 7, max_new_tokens=4)
        eng.run_until_drained()
    s = eng.stats()
    assert s["requests_done"] == 4
    assert s["evictions"] > 0
    assert s["alloc_blocks_in_use"] == 0
    assert s["alloc_num_cached"] + len(eng.allocator._free) == eng.allocator.capacity


def test_prefill_budget_bounds_chunk_sizes():
    """With prefill_budget=B, no single step may process more than B prompt
    tokens, and the jitted chunk trace count stays O(log)."""
    cfg, params = _make("olmo-1b")
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=128, block_size=8,
                          cache_dtype=jnp.float32, prefill_budget=8)
    r = eng.submit(list(range(2, 55)), max_new_tokens=2)  # 53-token prompt
    seen = []
    while r.state != RequestState.DONE:
        before = eng.prefill_tokens
        eng.step()
        seen.append(eng.prefill_tokens - before)
    assert max(seen) <= 8, seen
    assert eng._chunk_step._cache_size() <= 4  # chunks of 8, 4, 2, 1 at most
    assert len(r.generated) == 2


def test_hybrid_prefix_cache_warns_and_disables():
    cfg, params = _make("hymba-1.5b")
    with pytest.warns(RuntimeWarning, match="prefix_cache"):
        eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64,
                              prefix_cache=True)
    assert eng.prefix is None
    with pytest.warns(RuntimeWarning, match="prefill_budget"):
        InferenceEngine(cfg, params, max_batch=1, max_seq=64, prefill_budget=8)
    r = eng.submit([5, 9, 12], max_new_tokens=3)
    eng.run_until_drained()
    assert len(r.generated) == 3


# ---------------------------------------------------------------------------
# satellites: batched sampling, queue discipline, auto-defrag
# ---------------------------------------------------------------------------


def test_batched_sampler_greedy_matches_scalar():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (5, 64))
    out = sample_tokens(logits, jnp.zeros(5), jnp.zeros(5, jnp.int32), key)
    assert out.shape == (5,)
    for b in range(5):
        assert int(out[b]) == int(sample_token(logits[b], 0.0, key))


def test_batched_sampler_top_k_one_is_greedy():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (4, 32))
    out = sample_tokens(logits, jnp.full(4, 0.9), jnp.ones(4, jnp.int32), key)
    assert np.array_equal(np.asarray(out), np.asarray(jnp.argmax(logits, -1)))


def test_batched_sampler_respects_top_k_support():
    key = jax.random.PRNGKey(4)
    logits = jax.random.normal(key, (3, 32))
    ks = jnp.array([2, 4, 0], jnp.int32)
    for seed in range(8):
        out = np.asarray(sample_tokens(logits, jnp.ones(3), ks, jax.random.PRNGKey(seed)))
        for b, k in enumerate([2, 4, 32]):
            topk = set(np.argsort(np.asarray(logits[b]))[-k:].tolist())
            assert out[b] in topk


def test_queue_admission_order_unchanged():
    """Priority-aware insert must reproduce the old sort-by-(offline,
    submit_t) admission order exactly."""
    cfg, params = _make("olmo-1b")
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, cache_dtype=jnp.float32)
    pattern = [False, True, False, True, True, False]
    reqs = [eng.submit([10 + i, 2], max_new_tokens=1, online=on)
            for i, on in enumerate(pattern)]
    expected = [r.req_id for r in sorted(reqs, key=lambda r: (not r.online, r.submit_t))]
    assert [r.req_id for r in eng.queue] == expected
    eng.run_until_drained()
    admitted = [r.req_id for r in sorted(eng.done, key=lambda r: r.first_token_t)]
    assert admitted == expected, "admission order drifted from the sort baseline"


def test_auto_defrag_triggers_and_counts():
    cfg, params = _make("olmo-1b")
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, block_size=8,
                          num_blocks=17, cache_dtype=jnp.float32,
                          prefix_cache=False, defrag_threshold=0.5)
    blocks = eng.allocator.alloc(16)
    eng.allocator.free([b for b in blocks if b % 2 == 0])  # scattered frees
    assert eng.allocator.fragmentation() > 0.5
    eng.step()  # no work, but the post-step check must fire
    assert eng.stats()["defrag_triggers"] == 1
    # defrag sorts the free list: the next allocations come out id-contiguous
    freed = sorted(b for b in blocks if b % 2 == 0)
    assert eng.allocator.alloc(3) == freed[:3]
    eng.step()  # no new frees -> no re-trigger
    assert eng.stats()["defrag_triggers"] == 1


def test_shared_prefix_halves_prefill_tokens():
    """Acceptance: a shared-system-prompt mix must compute >= 2x fewer
    prefill tokens with the cache on (sequential arrivals)."""
    cfg, params = _make("olmo-1b")
    system = list(range(2, 34))  # 32 tokens = 4 full blocks @ bs 8
    prompts = [system + [40 + i, 50 + i] for i in range(6)]
    toks = {}
    for label, on in (("uncached", False), ("cached", True)):
        eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64, block_size=8,
                              cache_dtype=jnp.float32, prefix_cache=on)
        for p in prompts:
            eng.submit(p, max_new_tokens=3)
            eng.run_until_drained()
        toks[label] = eng.stats()["prefill_tokens"]
        if on:
            assert eng.stats()["prefix_hit_rate"] > 0.5
    assert toks["cached"] * 2 <= toks["uncached"], toks


# ---------------------------------------------------------------------------
# stranding hazard: an evicted parent leaves its cached children unreachable
# ---------------------------------------------------------------------------


def _spill_fetch(block):
    """Stand-in for the engine's device gather: 8 recognizable bytes."""
    return {"k": np.full((2,), float(block), np.float32)}


def test_evicted_parent_reclaims_stranded_children_drop_tier():
    """Matching always walks from the root, so dropping a chain's first
    block makes every descendant unmatchable.  The eviction cascade must
    unmap the whole subtree AND return still-cached descendants to the
    free list (``uncache``) — without it they sit in the LRU pool as
    unreachable-but-resident capacity until eviction churn gets to them."""
    a = BlockAllocator(17)  # 16 usable
    idx = PrefixIndex(a, 4)
    toks = list(range(40, 52))  # 12 tokens = 3 chained blocks
    blocks = a.alloc(3)
    idx.register(toks, blocks, 12)
    idx.release(blocks)  # whole chain parks in the LRU, oldest = blocks[0]
    assert all(a.is_cached(b) for b in blocks) and len(idx) == 3
    a.alloc(13)  # drain the free list; only the cached chain remains
    got = a.alloc(1)  # forces eviction of the LRU entry: the chain's ROOT
    assert got == [blocks[0]]
    assert a.evictions_dropped == 1 and a.evictions_spilled == 0
    # the cascade unmapped the children and repaired the stranding
    assert len(idx) == 0 and idx.stranded_dropped == 2
    assert a.stranded_reclaims == 2
    assert not a.is_cached(blocks[1]) and not a.is_cached(blocks[2])
    assert idx.match(toks + [99]) == ([], None)
    # the reclaimed blocks are allocatable immediately
    assert set(a.alloc(2)) == {blocks[1], blocks[2]}
    assert a.num_free == 0


def test_spilled_parent_keeps_children_matchable():
    """Under the spill tier the same eviction DEMOTES instead: the parent
    re-keys to a host-pool handle, descendants stay reachable through the
    mixed-tier chain walk, and nothing is stranded."""
    from repro.serving import SpillPool, is_spilled

    a = BlockAllocator(17)
    idx = PrefixIndex(a, 4)
    idx.attach_spill(SpillPool(1 << 10, mode="cache"), _spill_fetch)
    toks = list(range(60, 72))
    blocks = a.alloc(3)
    idx.register(toks, blocks, 12)
    idx.release(blocks)
    a.alloc(13)
    a.alloc(1)  # evicts the root -> spilled, not dropped
    assert a.evictions_spilled == 1 and a.evictions_dropped == 0
    assert a.stranded_reclaims == 0 and len(idx) == 3
    full, partial = idx.match(toks + [99])
    assert len(full) == 3 and partial is None
    assert is_spilled(full[0]) and full[1:] == blocks[1:]
    assert idx.stats()["spilled_entries"] == 1
    # the spilled payload is the evicted block's rows, bit-exact
    got = idx.spill.pop(full[0])
    assert float(np.asarray(got["k"])[0]) == float(blocks[0])


def test_spill_pool_budget_drop_cascades_through_index():
    """When the host pool's own byte budget forces a spilled parent out,
    the drop must cascade exactly like a device-tier drop: spilled
    descendants leave the pool, cached device descendants return to the
    free list, and a spill racing its ancestor's drop discards cleanly
    (the mid-``put`` reentrancy path)."""
    from repro.serving import SpillPool

    a = BlockAllocator(17)
    idx = PrefixIndex(a, 4)
    pool = SpillPool(16, mode="cache", staging_depth=0)  # room for TWO entries
    idx.attach_spill(pool, _spill_fetch)
    toks = list(range(80, 92))
    blocks = a.alloc(3)
    idx.register(toks, blocks, 12)
    idx.release(blocks)
    a.alloc(13)
    a.alloc(3)  # evict the whole chain, oldest first
    # b0 and b1 spilled; b2's put overflowed the pool, dropping b0 — whose
    # cascade discarded b1 from the pool and unmapped b2 mid-spill, so the
    # b2 spill was discarded rather than stranded in the pool
    assert a.evictions_spilled == 2 and a.evictions_dropped == 1
    assert len(idx) == 0 and len(pool) == 0 and pool.bytes_used == 0
    assert idx.stranded_dropped == 2
    assert idx.match(toks + [99]) == ([], None)
