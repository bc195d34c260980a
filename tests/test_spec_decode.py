"""Speculative decoding on the paged serving engine.

Covers: the n-gram prompt-lookup and draft-model drafters, exactness of the
vectorised rejection-sampling accept/reject (greedy degeneration AND the
distributional identity for temperature > 0), token-level block-table
truncation, and the engine-level guarantee the feature is sold on — greedy
speculative decode (both modes) is token-identical to the non-speculative
paged engine, with allocator accounting clean under mid-sequence rollback.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    DraftModel,
    InferenceEngine,
    make_draft_config,
    ngram_draft,
    spec_accept,
    truncate_blocks,
)

# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------


def test_ngram_draft_finds_repeats():
    ctx = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    # suffix [4, 1, 2] occurred before, followed by 3, 4, 1...
    assert ngram_draft(ctx, 3) == [3, 4, 1]
    # a run of identical tokens proposes the whole window, not one token
    run = [9, 9, 9, 9, 9, 9]
    assert ngram_draft(run, 4) == [9, 9, 9, 9]


def test_ngram_draft_prefers_longest_suffix():
    # [7, 8] recurs with continuation 5; the unigram [8] also recurs later
    # with a different continuation — the longer suffix must win
    ctx = [7, 8, 5, 0, 8, 3, 7, 8]
    assert ngram_draft(ctx, 1, max_ngram=3) == [5]


def test_ngram_draft_no_match_and_budget():
    assert ngram_draft([1, 2, 3, 4, 5], 4) == []  # no repeats
    assert ngram_draft([1, 2, 1, 2], 0) == []  # no budget
    assert ngram_draft([5], 4) == []  # too short
    # a match near the end extrapolates its period past the boundary
    assert ngram_draft([3, 4, 9, 3, 4], 4, max_ngram=2) == [9, 3, 4, 9]


def test_truncate_blocks_token_level():
    blocks = [4, 7, 2, 9]
    assert truncate_blocks(blocks, 32, 8) == ([4, 7, 2, 9], [])
    assert truncate_blocks(blocks, 17, 8) == ([4, 7, 2], [9])
    assert truncate_blocks(blocks, 16, 8) == ([4, 7], [2, 9])
    assert truncate_blocks(blocks, 1, 8) == ([4], [7, 2, 9])
    assert truncate_blocks(blocks, 0, 8) == ([], [4, 7, 2, 9])
    assert truncate_blocks([], 5, 8) == ([], [])


def test_make_draft_config_shares_vocab():
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    dcfg = make_draft_config(cfg)
    assert dcfg.num_layers == max(cfg.num_layers // 2, 1)
    assert dcfg.padded_vocab == cfg.padded_vocab
    assert dcfg.family == cfg.family


def test_draft_model_catchup_and_rollback():
    """After a rollback, re-drafting from the same committed context must
    reproduce the same greedy proposals (stale ring entries are re-fed)."""
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    dm = DraftModel(cfg, params, max_batch=2, max_seq=64)
    ctx = [5, 9, 12, 7, 3]
    d1, q1 = dm.draft(0, ctx, 3)
    assert len(d1) == 3 and q1.shape == (3, cfg.padded_vocab)
    assert all(q1[i, d1[i]] == 1.0 for i in range(3))  # greedy -> one-hot
    dm.rollback(0, len(ctx))  # target rejected everything
    d2, _ = dm.draft(0, ctx + [42], 3)  # correction token extends the context
    dm.reset(0)
    d3, _ = dm.draft(0, ctx + [42], 3)  # cold replay of the same context
    assert d2 == d3, "rollback + catch-up diverged from a cold start"


# ---------------------------------------------------------------------------
# spec_accept: rejection sampling
# ---------------------------------------------------------------------------


def _greedy_args(B, K, V):
    return (
        jnp.zeros((B,), jnp.float32),  # temperature
        jnp.zeros((B,), jnp.int32),  # top_k
        jax.random.PRNGKey(0),
    )


def test_spec_accept_greedy_prefix():
    V, K = 16, 3
    logits = jnp.stack(
        [jax.nn.one_hot(jnp.array([3, 5, 7, 9]), V) * 10.0]
    )  # (1, K+1, V): argmax = 3,5,7,9
    drafts = jnp.array([[3, 5, 0]])  # first two match, third diverges
    qprobs = jax.nn.one_hot(drafts, V)
    valid = jnp.ones((1, K), bool)
    n_acc, final = spec_accept(logits, drafts, qprobs, valid, *_greedy_args(1, K, V))
    assert int(n_acc[0]) == 2
    assert int(final[0]) == 7  # the correction token IS the target argmax


def test_spec_accept_greedy_bonus_on_full_accept():
    V, K = 16, 2
    logits = jnp.stack([jax.nn.one_hot(jnp.array([3, 5, 7]), V) * 10.0])
    drafts = jnp.array([[3, 5]])
    n_acc, final = spec_accept(
        logits, drafts, jax.nn.one_hot(drafts, V), jnp.ones((1, K), bool), *_greedy_args(1, K, V)
    )
    assert int(n_acc[0]) == K and int(final[0]) == 7  # bonus from the K+1-th dist


def test_spec_accept_invalid_forces_reject():
    V, K = 16, 3
    logits = jnp.stack([jax.nn.one_hot(jnp.array([3, 5, 7, 9]), V) * 10.0])
    drafts = jnp.array([[3, 5, 7]])  # all would match...
    valid = jnp.array([[True, False, True]])  # ...but lane 1 proposed nothing
    n_acc, final = spec_accept(
        logits, drafts, jax.nn.one_hot(drafts, V), valid, *_greedy_args(1, K, V)
    )
    assert int(n_acc[0]) == 1
    assert int(final[0]) == 5  # plain greedy sample at the forced reject


def test_spec_accept_identical_draft_distribution_always_accepts():
    """q == p => the accept ratio is 1 for the drafted token: sampled mode
    must accept the full window regardless of the key."""
    V, K, B = 8, 3, 4
    key = jax.random.PRNGKey(1)
    logits = jax.random.normal(key, (B, K + 1, V))
    p = jax.nn.softmax(logits[:, :K], axis=-1)
    drafts = jnp.argmax(p, axis=-1)  # any supported token works; argmax is stable
    for seed in range(5):
        n_acc, _ = spec_accept(
            logits,
            drafts,
            p,
            jnp.ones((B, K), bool),
            jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jax.random.PRNGKey(seed),
        )
        assert np.all(np.asarray(n_acc) == K)


def test_spec_accept_matches_target_distribution():
    """The combined accept/resample law must equal the target distribution
    (the exactness theorem): empirical histogram over many keys ~ p."""
    V, K, N = 8, 1, 4000
    key = jax.random.PRNGKey(2)
    logits1 = jax.random.normal(key, (1, K + 1, V))
    logits = jnp.broadcast_to(logits1, (N, K + 1, V))
    # a deliberately bad one-hot draft (the ngram case): token 0 every time
    drafts = jnp.zeros((N, K), jnp.int32)
    qprobs = jax.nn.one_hot(drafts, V)
    n_acc, final = spec_accept(
        logits,
        drafts,
        qprobs,
        jnp.ones((N, K), bool),
        jnp.ones((N,), jnp.float32),
        jnp.zeros((N,), jnp.int32),
        jax.random.PRNGKey(7),
    )
    n_acc, final = np.asarray(n_acc), np.asarray(final)
    emitted = np.where(n_acc >= 1, 0, final)  # first emitted token per row
    p = np.asarray(jax.nn.softmax(logits1[0, 0]))
    freq = np.bincount(emitted, minlength=V) / N
    assert np.max(np.abs(freq - p)) < 0.04, f"emitted law diverged: {freq} vs {p}"


# ---------------------------------------------------------------------------
# engine: greedy speculative decode == non-speculative paged engine
# ---------------------------------------------------------------------------


def _make(arch, window=0):
    cfg = reduce_for_smoke(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    if window:
        cfg = cfg.replace(sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


PROMPTS = [[7, 3, 9, 4] * 4 + [5], [5, 9, 12, 5, 9, 12, 5, 9, 12, 2], [30, 31]]


def _run_engine(cfg, params, prompts, *, max_new=6, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        eng = InferenceEngine(
            cfg, params, max_batch=2, max_seq=64, block_size=8,
            cache_dtype=jnp.float32, **kw,
        )
        outs = []
        for p in prompts:
            r = eng.submit(p, max_new_tokens=max_new)
            eng.run_until_drained()
            outs.append(r.generated)
        return outs, eng.stats()


SPEC_CASES = [
    ("olmo-1b", 0, "xla", "ngram"),
    ("olmo-1b", 0, "xla", "draft"),
    ("olmo-1b", 0, "pallas", "ngram"),
    ("olmo-1b", 8, "xla", "ngram"),  # sliding window: reclaim under rollback
    ("qwen3-moe-235b-a22b", 0, "xla", "ngram"),
    ("qwen3-moe-235b-a22b", 0, "xla", "draft"),
]


@pytest.mark.parametrize("arch,window,impl,mode", SPEC_CASES)
def test_spec_engine_matches_baseline(arch, window, impl, mode, steer_decode):
    steer_decode(impl)
    cfg, params = _make(arch, window)
    kw = {}
    if mode == "draft":
        # self-drafting (draft == target): maximal acceptance, and the
        # equivalence check is still meaningful — commit/rollback runs hot
        kw = dict(draft_cfg=cfg, draft_params=params)
    base, _ = _run_engine(cfg, params, PROMPTS, attn_impl=impl)
    out, stats = _run_engine(
        cfg, params, PROMPTS, attn_impl=impl, spec_decode=mode, spec_k=4, **kw
    )
    assert out == base, f"{arch}/{mode}: speculative decode changed greedy tokens"
    assert stats["spec_steps"] > 0
    # drained engine leak check: every alloc matched by a free
    assert stats["alloc_blocks_in_use"] == 0
    assert stats["alloc_total_allocs"] == stats["alloc_total_frees"]


def test_spec_self_draft_acceptance_upper_bound():
    """Draft == target params under greedy accepts every drafted token."""
    cfg, params = _make("olmo-1b")
    out, s = _run_engine(
        cfg, params, [PROMPTS[0]], max_new=9,
        spec_decode="draft", spec_k=4, draft_cfg=cfg, draft_params=params,
    )
    assert s["acceptance_rate"] == 1.0
    assert s["accepted_per_step"] > 2.0
    assert len(out[0]) == 9


def test_spec_with_prefix_cache_and_chunked_prefill():
    """All three features composed (prefix sharing + budgeted prefill +
    speculation) must still match the dense-cache engine token-for-token."""
    cfg, params = _make("olmo-1b")
    sysp = [7, 3, 9, 4, 11, 2, 6, 8, 13, 5, 10, 12, 14, 15, 16, 17]
    prompts = [sysp + [30 + i] for i in range(3)]
    base, _ = _run_engine(cfg, params, prompts, cache_kind="dense")
    out, s = _run_engine(
        cfg, params, prompts,
        prefix_cache=True, prefill_budget=4, spec_decode="ngram", spec_k=4,
    )
    assert out == base
    assert s["prefix_hit_tokens"] >= 2 * 16  # sharing still happened


def test_verify_tokens_do_not_deflate_prefix_hit_rate():
    """The speculative verify pass rides the chunked-prefill machinery, so
    a mis-wired counter would fold its fed windows into ``prefill_tokens``
    and deflate ``prefix_hit_rate`` whenever spec decode is on.  Audit
    result, pinned here: verify work accrues to the separate
    ``verify_tokens`` stat, ``prefill_tokens`` counts exactly the prompt
    tokens the model computed, and the hit rate matches the spec-off run."""
    cfg, params = _make("olmo-1b")
    sysp = [7, 3, 9, 4, 11, 2, 6, 8, 13, 5, 10, 12, 14, 15, 16, 17]
    # repetitive tails so the ngram drafter actually proposes (verify windows
    # run hot while the shared 16-token prefix is served from cache)
    prompts = [sysp + [30 + i, 40, 41, 40, 41, 40, 41] for i in range(3)]
    _, s_off = _run_engine(cfg, params, prompts, prefix_cache=True, prefill_budget=4)
    _, s_on = _run_engine(
        cfg, params, prompts,
        prefix_cache=True, prefill_budget=4, spec_decode="ngram", spec_k=4,
    )
    assert s_on["spec_steps"] > 0 and s_on["verify_tokens"] > 0
    # every prompt token is either computed (prefill) or served from cache —
    # verify windows must appear in neither bucket
    total_prompt = sum(len(p) for p in prompts)
    for s in (s_off, s_on):
        assert s["prefill_tokens"] + s["prefix_hit_tokens"] == total_prompt, s
    assert s_on["prefill_tokens"] == s_off["prefill_tokens"]
    assert s_on["prefix_hit_rate"] == s_off["prefix_hit_rate"] > 0
    assert "verify_tokens" not in s_off  # spec-off stats carry no spec keys


def test_spec_quantized_kv_matches_quantized_baseline():
    cfg, params = _make("olmo-1b")
    base, _ = _run_engine(cfg, params, PROMPTS[:2], quantize_kv=True)
    out, _ = _run_engine(
        cfg, params, PROMPTS[:2], quantize_kv=True, spec_decode="ngram", spec_k=3
    )
    assert out == base, "speculative rollback corrupted the int8 pool path"


def test_spec_hybrid_warns_and_disables():
    cfg, params = _make("hymba-1.5b")
    with pytest.warns(RuntimeWarning, match="spec_decode"):
        eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, spec_decode="ngram")
    assert eng.spec_mode == "off"
    r = eng.submit([5, 9, 12], max_new_tokens=3)
    eng.run_until_drained()
    assert len(r.generated) == 3


def test_spec_invalid_knobs_raise():
    cfg, params = _make("olmo-1b")
    with pytest.raises(ValueError, match="spec_decode"):
        InferenceEngine(cfg, params, max_batch=1, max_seq=64, spec_decode="bogus")
    with pytest.raises(ValueError, match="spec_k"):
        InferenceEngine(cfg, params, max_batch=1, max_seq=64, spec_decode="ngram", spec_k=0)


def test_spec_headroom_enforced_at_submit():
    """Admission must reserve spec_k positions of rollback headroom."""
    cfg, params = _make("olmo-1b")
    eng = InferenceEngine(
        cfg, params, max_batch=1, max_seq=32, block_size=8, spec_decode="ngram", spec_k=4,
        eos_token=-1,  # unreachable: the request must run to its full budget
    )
    with pytest.raises(ValueError, match="headroom"):
        eng.submit(list(range(2, 22)), max_new_tokens=10)  # fits only without spec
    r = eng.submit(list(range(2, 18)), max_new_tokens=10)  # 26 + 4 <= 32
    eng.run_until_drained()
    assert len(r.generated) == 10


def test_spec_respects_max_new_budget():
    """A near-done request must not overshoot max_new even with a larger
    draft window (drafts are clamped to remaining - 1)."""
    cfg, params = _make("olmo-1b")
    base, _ = _run_engine(cfg, params, [PROMPTS[0]], max_new=2)
    out, _ = _run_engine(cfg, params, [PROMPTS[0]], max_new=2, spec_decode="ngram", spec_k=4)
    assert out == base and len(out[0]) == 2


def test_spec_eos_mid_window_truncates():
    """An accepted EOS inside the draft window must stop the request at the
    same length as the baseline engine (mid-sequence truncation path)."""
    cfg, params = _make("olmo-1b")
    probe, _ = _run_engine(cfg, params, [PROMPTS[0]], max_new=8)
    eos = probe[0][3]  # force EOS at the 4th generated token
    base, _ = _run_engine(cfg, params, [PROMPTS[0]], max_new=8, eos_token=eos)
    out, s = _run_engine(
        cfg, params, [PROMPTS[0]], max_new=8, eos_token=eos,
        spec_decode="draft", spec_k=4, draft_cfg=cfg, draft_params=params,
    )
    assert out == base
    assert out[0][-1] == eos and len(out[0]) <= 8
    assert s["alloc_blocks_in_use"] == 0
    assert s["alloc_total_allocs"] == s["alloc_total_frees"]


def test_spec_temperature_sampling_runs():
    """temperature > 0 speculation: not bit-identical to the baseline (the
    key stream differs) but counts, ranges and stats must hold."""
    cfg, params = _make("olmo-1b")
    out, s = _run_engine(
        cfg, params, PROMPTS[:2], max_new=8, spec_decode="ngram", spec_k=3
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        eng = InferenceEngine(
            cfg, params, max_batch=2, max_seq=64, block_size=8,
            cache_dtype=jnp.float32, spec_decode="ngram", spec_k=3,
        )
        rs = [eng.submit(p, max_new_tokens=8, temperature=0.9, top_k=4) for p in PROMPTS[:2]]
        eng.run_until_drained()
    for r in rs:
        assert len(r.generated) == 8
        assert all(0 <= t < cfg.padded_vocab for t in r.generated)
