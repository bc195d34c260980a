"""Fused one-dispatch step: ``fused=True`` must be invisible to the tokens.

The fused engine lowers each scheduler tick into ONE jitted dispatch (unified
decode / prefill-chunk / spec-verify row batch with in-graph sampling, accept
and rollback) instead of the legacy per-phase walk.  Invariants:

* **Token equivalence** — greedy decode is token-identical to the legacy
  engine across dense / moe / sliding-window archs, both attention backends,
  prefix caching, and both speculative modes (ngram + draft model), with
  identical ``prefix_hit_rate`` / ``acceptance_rate``.
* **Mixed batches** — staggered submits make prefill chunks and decodes share
  one dispatch; outputs still match the legacy interleave.
* **Preemption + spill restore** — SLO preemption mid-flight and the
  host-RAM restore queue compose with the fused path without token drift.
* **Fewer dispatches** — the point of the refactor: the fused engine reports
  strictly fewer ``dispatches_per_step`` and ``host_syncs_per_step``.
* **TP=2** — under a 2-device mesh (CI forces host devices) the fused engine
  still matches the single-device legacy engine.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import InferenceEngine, RequestState

# shared leading prefix (prefix-cache hits) + repetitive tails (real ngram
# drafts) + one short prompt (admission churn)
SHARED = [11, 12, 13, 14, 15, 16, 17, 18]
PROMPTS = [
    SHARED + [7, 3, 9, 4] * 3 + [5],
    SHARED + [5, 9, 12, 5, 9, 12, 2],
    SHARED + [21, 22, 23, 24],
    [30, 31],
]


def _make(arch, window=0):
    cfg = reduce_for_smoke(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if window:
        cfg = cfg.replace(sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _engine(cfg, params, *, fused, **kw):
    base = dict(
        max_batch=2, max_seq=64, block_size=8, cache_dtype=jnp.float32,
        prefill_budget=8, fused=fused,
    )
    base.update(kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return InferenceEngine(cfg, params, **base)


def _drain(eng, prompts=PROMPTS, max_new=6):
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_drained()
    return [list(r.generated) for r in reqs]


# ---------------------------------------------------------------------------
# equivalence matrix: family x backend x prefix x spec mode
# ---------------------------------------------------------------------------

# arch, sliding window, attention impl, extra engine knobs
FUSED_CASES = [
    ("olmo-1b", 0, "xla", {}),
    ("olmo-1b", 0, "pallas", {}),
    ("olmo-1b", 0, "xla", dict(prefix_cache=True)),
    ("olmo-1b", 0, "xla", dict(prefix_cache=True, spec_decode="ngram", spec_k=3)),
    ("olmo-1b", 0, "pallas", dict(spec_decode="ngram", spec_k=3)),
    ("olmo-1b", 0, "xla", dict(spec_decode="draft", spec_k=3)),
    ("olmo-1b", 8, "xla", dict(spec_decode="ngram", spec_k=3)),  # window+rollback
    ("qwen3-moe-235b-a22b", 0, "xla", {}),
    ("qwen3-moe-235b-a22b", 0, "xla", dict(spec_decode="ngram", spec_k=3)),
]


@pytest.mark.parametrize("arch,window,impl,kw", FUSED_CASES)
def test_fused_token_identical_to_legacy(arch, window, impl, kw, steer_decode):
    steer_decode(impl)
    cfg, params = _make(arch, window)
    if kw.get("spec_decode") == "draft":
        # self-drafting: maximal acceptance, commit/rollback runs hot
        kw = dict(kw, draft_cfg=cfg, draft_params=params)
    runs = {}
    for fused in (False, True):
        eng = _engine(cfg, params, fused=fused, attn_impl=impl, **kw)
        assert eng.metrics.get("engine_decode_attention_kernel").value == (impl == "pallas")
        runs[fused] = (_drain(eng), eng.stats())
        assert eng.allocator is None or eng.allocator.blocks_in_use == 0
    (base, bs), (out, fs) = runs[False], runs[True]
    assert out == base, f"{arch}/w{window}/{impl}/{kw}: fused changed greedy tokens"
    for rate in ("prefix_hit_rate", "acceptance_rate"):
        if rate in bs:
            assert fs[rate] == bs[rate], f"{rate} drifted under fusion"
    assert fs["fused"] and not bs["fused"]


def test_fused_fewer_dispatches_and_syncs():
    """The refactor's contract: one dispatch and one host sync per tick."""
    cfg, params = _make("olmo-1b")
    stats = {}
    for fused in (False, True):
        # EOS unreachable: every request decodes its full budget, so the
        # per-step means cover decode ticks, not just admission ticks
        eng = _engine(cfg, params, fused=fused, eos_token=-1)
        _drain(eng)
        stats[fused] = eng.stats()
    assert stats[True]["dispatches_per_step"] < stats[False]["dispatches_per_step"]
    assert stats[True]["host_syncs_per_step"] <= stats[False]["host_syncs_per_step"]
    # fused mixed/decode ticks each dispatch exactly once; the budget walk's
    # per-chunk dispatches are gone, so the mean sits at ~1 per decode step
    assert stats[True]["dispatches_per_step"] <= 1.5


def test_fused_requires_chunked_prefill():
    """The unified row batch is built from chunked-prefill machinery: a
    dense (non-paged) cache can't chunk, so ``fused=True`` must refuse."""
    cfg, params = _make("olmo-1b")
    with pytest.raises(ValueError, match="fused"):
        InferenceEngine(cfg, params, max_batch=2, max_seq=64, fused=True,
                        cache_kind="dense")


# ---------------------------------------------------------------------------
# mixed batches: chunks + decodes (+ verify windows) share one dispatch
# ---------------------------------------------------------------------------


def _staggered(eng):
    """Admit one request, decode it a few ticks, then pile on the rest: with
    prefill_budget=4 the later prompts chunk across several ticks while the
    first request keeps decoding — every mixed row-kind combination shows up."""
    rs = [eng.submit(PROMPTS[0], max_new_tokens=8)]
    for _ in range(3):
        eng.step()
    rs += [eng.submit(p, max_new_tokens=8) for p in PROMPTS[1:]]
    eng.run_until_drained()
    assert all(r.state == RequestState.DONE for r in rs)
    return [list(r.generated) for r in rs]


@pytest.mark.parametrize("kw", [{}, dict(spec_decode="ngram", spec_k=3)])
def test_fused_mixed_batches_match_legacy(kw):
    cfg, params = _make("olmo-1b")
    outs = {}
    for fused in (False, True):
        eng = _engine(cfg, params, fused=fused, max_batch=3, prefill_budget=4, **kw)
        outs[fused] = _staggered(eng)
    assert outs[True] == outs[False], f"mixed-batch fusion drifted ({kw})"


# ---------------------------------------------------------------------------
# preemption + restore-queue interleave
# ---------------------------------------------------------------------------


def test_fused_mid_step_preemption_token_identical():
    """A high-priority arrival preempts a decoding victim between fused
    ticks; the victim resumes (re-prefills via chunk rows) and both engines
    agree on every request's tokens."""
    cfg, params = _make("olmo-1b")
    outs = {}
    for fused in (False, True):
        eng = _engine(cfg, params, fused=fused, max_batch=1, prefill_budget=4)
        low = eng.submit(PROMPTS[0], max_new_tokens=8)
        for _ in range(4):
            eng.step()
        assert low.state == RequestState.ACTIVE
        high = eng.submit([40, 41, 42], max_new_tokens=4, priority=5)
        eng.step()  # SLO preemption evicts the decoding victim
        assert low.state == RequestState.WAITING and low.preemptions == 1
        eng.run_until_drained()
        assert low.state == high.state == RequestState.DONE
        outs[fused] = (list(low.generated), list(high.generated))
        assert eng.allocator.blocks_in_use == 0
    assert outs[True] == outs[False], "preempt/resume drifted under fusion"


def test_fused_restore_queue_interleave():
    """Spill-tier swap-ins (restore queue) interleave with fused ticks: the
    restoring request is planned around until its blocks land, then decodes
    token-identically to the legacy engine, with real restores happening."""
    cfg, params = _make("olmo-1b")
    pre = list(range(2, 26))  # 3 full blocks @ bs 8
    outs = {}
    for fused in (False, True):
        eng = _engine(
            cfg, params, fused=fused, max_batch=1, num_blocks=12,
            prefill_budget=8, restore_budget=1, spill_bytes=1 << 20,
        )
        r0 = eng.submit(pre + [30], max_new_tokens=4)
        eng.run_until_drained()
        blks = eng.allocator.alloc(eng.allocator.num_free)  # churn: spill chain
        eng.allocator.free(blks)
        assert len(eng.spill) >= 3, "chain must be fully spilled"
        r1 = eng.submit(pre + [30], max_new_tokens=4)
        eng.run_until_drained()
        s = eng.stats()
        assert s["restores"] > 0 and s["restores_pending"] == 0
        assert r1.generated == r0.generated, "spill-hit decode diverged"
        outs[fused] = list(r1.generated)
        assert eng.allocator.blocks_in_use == 0
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# TP=2 (runs under the CI fused-step lane's forced 2-device CPU)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)
@pytest.mark.parametrize("kw", [{}, dict(spec_decode="ngram", spec_k=3)])
def test_fused_tp2_token_identical(kw):
    from repro.launch.mesh import make_serving_mesh

    cfg, params = _make("olmo-1b")
    base_eng = _engine(cfg, params, fused=False)
    base = _drain(base_eng)
    eng = _engine(cfg, params, fused=True, mesh=make_serving_mesh(2), **kw)
    out = _drain(eng)
    assert out == base, f"fused TP=2 changed greedy tokens ({kw})"
