"""Tiered KV cache: host-RAM spill for evicted prefix blocks.

Token-identity of spill-hit decode against the drop-on-evict paged baseline,
the uncached re-prefill engine and the dense-cache oracle across
dense/window/moe x xla/pallas x at-rest compression; mid-restore preemption
and mid-restore abort leave zero residue on both pools; knob validation."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import InferenceEngine, RequestState


def _make(arch, window=0):
    cfg = reduce_for_smoke(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if window:
        cfg = cfg.replace(sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _group_prompts(groups=4, pre_len=24):
    """One prompt per group: a distinct ``pre_len``-token prefix (3 full
    blocks at block_size 8) plus a unique tail token.  Submitted over two
    rounds against a pool too small for all chains, round 2 finds round 1's
    chains evicted — dropped or spilled depending on the tier."""
    return [
        [10 + g * 40 + i for i in range(pre_len)] + [200 + g] for g in range(groups)
    ]


def _drive(eng, rounds=2, max_new=5):
    outs = []
    for _ in range(rounds):
        for p in _group_prompts():
            r = eng.submit(p, max_new_tokens=max_new)
            eng.run_until_drained()
            outs.append(list(r.generated))
    return outs


def _engine(cfg, params, impl="xla", **kw):
    base = dict(
        max_batch=2, max_seq=64, block_size=8, cache_dtype=jnp.float32, attn_impl=impl
    )
    base.update(kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return InferenceEngine(cfg, params, **base)


def _force_spill(eng):
    """Churn the pool so every cached chain is evicted (spilled when a pool
    is attached): allocate the whole free budget, then return it."""
    blks = eng.allocator.alloc(eng.allocator.num_free)
    eng.allocator.free(blks)


# ---------------------------------------------------------------------------
# token identity: spill == drop == uncached re-prefill == dense oracle
# ---------------------------------------------------------------------------

# arch, sliding window, attention impl, extra engine knobs for the paged arms
TIERED_CASES = [
    ("olmo-1b", 0, "xla", {}),
    ("olmo-1b", 0, "pallas", {}),
    ("olmo-1b", 8, "xla", {}),  # sliding-window arch
    ("qwen3-moe-235b-a22b", 0, "xla", {}),
    ("olmo-1b", 0, "xla", {"spill_dtype": "int8"}),  # lossy at-rest compression
    ("olmo-1b", 0, "xla", {"quantize_kv": True}),  # int8 pool: spill is pool-native
]


@pytest.mark.parametrize("arch,window,impl,extra", TIERED_CASES)
def test_spill_engine_token_identical_to_baselines(arch, window, impl, extra, steer_decode):
    """The spill tier is a pure capacity extension: greedy outputs must be
    token-identical whether an evicted chain restores from host RAM (spill),
    re-prefills from scratch (drop / uncached), or was never paged at all
    (dense oracle) — including int8-at-rest and int8-pool configurations."""
    steer_decode(impl)
    cfg, params = _make(arch, window)
    paged = dict(num_blocks=10, prefill_budget=8, **extra)  # 9 usable blocks
    if "quantize_kv" in extra:
        # an int8 pool has no dense counterpart — the oracle is an ample
        # paged pool of the same dtype that never needs to evict
        oracle = dict(num_blocks=64, **extra)
    else:
        oracle = dict(cache_kind="dense")
    outs, stats = {}, {}
    variants = {
        "oracle": oracle,
        "uncached": dict(prefix_cache=False, **paged),
        "drop": dict(**paged),
        "spill": dict(spill_bytes=8 << 20, **paged),
    }
    for label, kw in variants.items():
        eng = _engine(cfg, params, impl=impl, **kw)
        outs[label] = _drive(eng)
        stats[label] = eng.stats()
        assert eng.allocator is None or eng.allocator.blocks_in_use == 0
    assert outs["spill"] == outs["oracle"], f"{arch}/{impl}: spill diverged from oracle"
    assert outs["spill"] == outs["drop"], f"{arch}/{impl}: spill diverged from drop"
    assert outs["spill"] == outs["uncached"]
    drop_s, spill_s = stats["drop"], stats["spill"]
    assert drop_s["alloc_evictions_dropped"] > 0, "scenario failed to overflow the pool"
    assert spill_s["alloc_evictions_spilled"] > 0
    assert spill_s["restores"] > 0 and spill_s["spill_hit_tokens"] > 0
    assert spill_s["restores_pending"] == 0 and spill_s["spill_staged"] >= 0
    # round 2 hits the host tier instead of re-prefilling: strictly better
    assert spill_s["prefix_hit_rate"] > drop_s["prefix_hit_rate"]
    assert spill_s["prefill_tokens"] < drop_s["prefill_tokens"]


# ---------------------------------------------------------------------------
# mid-restore preemption / abort (PR-7 / PR-8 interactions)
# ---------------------------------------------------------------------------


def _restore_setup():
    """An engine + a spilled 3-block chain + a request admitted against it
    with restore_budget=1, stepped once: exactly one block restored, two
    swap-ins still pending."""
    cfg, params = _make("olmo-1b")
    eng = _engine(
        cfg, params, max_batch=1, num_blocks=12, prefill_budget=8,
        restore_budget=1, spill_bytes=1 << 20,
    )
    pre = list(range(2, 26))  # 24 tokens = 3 full blocks @ bs 8
    p_low = pre + [30]
    r0 = eng.submit(p_low, max_new_tokens=4)
    eng.run_until_drained()
    _force_spill(eng)
    assert len(eng.spill) >= 3, "chain must be fully spilled"
    r1 = eng.submit(p_low, max_new_tokens=4)
    eng.step()  # admit: 3 swap-ins queued, budget executes 1
    assert r1.pending_restores and len(eng._restore_q) == 2
    return eng, p_low, r0, r1


def test_mid_restore_preemption_token_identical():
    """A higher-priority arrival preempts a victim whose spill swap-ins are
    still in flight: the un-copied payloads demote back to the pool, the
    victim resumes through a mixed device/spilled chain, and every output
    matches the unconstrained reference."""
    eng, p_low, r0, r1 = _restore_setup()
    rh = eng.submit([40, 41, 42], max_new_tokens=4, priority=5)
    eng.step()  # SLO preemption evicts the mid-restore victim
    assert r1.state == RequestState.WAITING and r1.preemptions == 1
    assert not eng._restore_q and not eng._restoring, "cancel left tasks queued"
    assert eng.stats()["restores_cancelled"] >= 1
    assert eng.stats()["prefix_demoted"] >= 1, "payloads must re-park in the pool"
    eng.run_until_drained()
    assert r1.state == RequestState.DONE and rh.state == RequestState.DONE
    assert r1.generated == r0.generated, "resumed spill-hit decode diverged"
    # the high-priority request must match a clean single-request engine
    cfg, params = _make("olmo-1b")
    ref = _engine(cfg, params, max_batch=1)
    rr = ref.submit([40, 41, 42], max_new_tokens=4)
    ref.run_until_drained()
    assert rh.generated == rr.generated
    # zero residue on both pools
    assert eng.allocator.blocks_in_use == 0
    assert not eng._restore_q and not eng._restoring
    assert all(not r.pending_restores for r in eng.done)
    assert eng.spill.bytes_used == sum(eng.spill._nbytes.values())


def test_mid_restore_abort_zero_residue():
    """abort() of a request with pending swap-ins cancels the queue,
    demotes the un-copied entries back to the pool, frees every block, and
    the chain stays matchable for the next identical prompt."""
    eng, p_low, r0, r1 = _restore_setup()
    assert len(eng.spill) == 0  # admission popped every spilled payload
    assert eng.abort(r1)
    assert r1.state == RequestState.DONE and r1.finish_reason == "aborted"
    assert not eng._restore_q and not eng._restoring
    assert not r1.pending_restores
    assert eng.allocator.blocks_in_use == 0, "abort leaked blocks mid-restore"
    assert eng.stats()["restores_cancelled"] == 2
    assert len(eng.spill) == 2  # the two un-copied payloads demoted back
    # the demoted entries (and the one restored device block) still serve
    # the next identical prompt, token-identically
    r2 = eng.submit(p_low, max_new_tokens=4)
    eng.run_until_drained()
    assert r2.generated == r0.generated
    assert eng.allocator.blocks_in_use == 0
    assert eng.stats()["spill_hit_tokens"] > 0


# ---------------------------------------------------------------------------
# knob validation
# ---------------------------------------------------------------------------


def test_spill_knob_validation():
    cfg, params = _make("olmo-1b")
    with pytest.raises(ValueError, match="spill_dtype"):
        _engine(cfg, params, spill_dtype="fp4")
    with pytest.raises(ValueError, match="restore_budget"):
        _engine(cfg, params, restore_budget=0)
    with pytest.warns(RuntimeWarning, match="spill_bytes only applies"):
        dense = InferenceEngine(
            cfg, params, max_batch=2, max_seq=64, cache_kind="dense", spill_bytes=1 << 20
        )
    assert dense.spill is None
    with pytest.warns(RuntimeWarning, match="spill_bytes needs the prefix cache"):
        off = InferenceEngine(
            cfg, params, max_batch=2, max_seq=64, block_size=8,
            prefix_cache=False, spill_bytes=1 << 20,
        )
    assert off.spill is None
    hybrid_cfg, hybrid_params = _make("hymba-1.5b")
    with pytest.warns(RuntimeWarning):
        hyb = InferenceEngine(
            hybrid_cfg, hybrid_params, max_batch=2, max_seq=64, block_size=8,
            spill_bytes=1 << 20,
        )
    assert hyb.spill is None  # hybrid: no prefix cache, tier disabled
    with pytest.warns(RuntimeWarning, match="re-quantize"):
        q8 = InferenceEngine(
            cfg, params, max_batch=2, max_seq=64, block_size=8,
            cache_dtype=jnp.float32, quantize_kv=True,
            spill_bytes=1 << 20, spill_dtype="fp8",
        )
    assert q8.spill is not None and q8.spill.mode == "cache"
