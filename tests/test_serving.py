"""Serving engine: continuous batching must equal per-prompt greedy decoding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import forward, init_params
from repro.serving import InferenceEngine, RequestState


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def greedy_reference(cfg, params, prompt, n_new):
    toks = list(prompt)
    for _ in range(n_new):
        logits, _ = forward(cfg, params, {"tokens": jnp.asarray(toks, jnp.int32)[None]})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt) :]


@pytest.mark.parametrize("cache_kind", ["paged", "dense"])
def test_continuous_batching_matches_reference(setup, cache_kind):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, max_batch=3, max_seq=64, cache_kind=cache_kind)
    prompts = [[5, 9, 12], [7, 3], [20, 21, 22, 23], [4, 4, 8]]  # 4 reqs, 3 slots
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_drained()
    for p, r in zip(prompts, reqs):
        assert r.state == RequestState.DONE
        ref = greedy_reference(cfg, params, p, 6)
        assert r.generated[: len(ref)] == ref, f"slot-reuse corrupted request {p}"


def test_online_requests_admitted_before_offline(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64)
    off = eng.submit([1, 2, 3], max_new_tokens=4, online=False)
    on = eng.submit([4, 5, 6], max_new_tokens=4, online=True)
    eng.step()  # admission happens here
    assert on.state == RequestState.ACTIVE
    assert off.state == RequestState.WAITING


def test_engine_stats(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64)
    eng.submit([1, 2], max_new_tokens=3)
    eng.submit([3, 4], max_new_tokens=3)
    eng.run_until_drained()
    s = eng.stats()
    assert s["requests_done"] == 2
    assert s["tokens_out"] == 6
    assert s["mean_ttft_s"] is not None


def test_eos_stops_generation(setup):
    cfg, params = setup
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, eos_token=999999)
    r = eng.submit([1, 2, 3], max_new_tokens=5)
    eng.run_until_drained()
    assert len(r.generated) == 5  # eos never sampled -> runs to max_new_tokens


def test_never_admitted_request_has_none_ttft(setup):
    """A queued-but-never-admitted request must report ttft=None (the serve
    CLI guards its ms formatting on this), and a truncated drain must be
    distinguishable from a finished one in ``stats()``: ``mean_ttft_s`` /
    ``slot_utilization`` only describe the finished/current population, so
    the queued/active counts carry the truncation evidence into benchmark
    JSON."""
    cfg, params = setup
    # EOS unreachable: the first request must still hold the slot after 2 steps
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, eos_token=-1)
    first = eng.submit([1, 2, 3], max_new_tokens=30)
    starved = eng.submit([4, 5, 6], max_new_tokens=4)
    with pytest.warns(RuntimeWarning):
        eng.run_until_drained(max_steps=2)
    assert first.ttft is not None
    assert starved.ttft is None and starved.state == RequestState.WAITING
    s = eng.stats()
    # truncated run: one request still decoding in its slot, one never left
    # the queue — requests_done alone would under-report the workload
    assert s["requests_done"] == 0
    assert s["requests_active"] == 1
    assert s["requests_queued"] == 1
    assert s["mean_ttft_s"] is None  # no finished requests to average over
    assert s["requests_done"] + s["requests_active"] + s["requests_queued"] == 2
    eng.run_until_drained()
    s = eng.stats()
    assert s["requests_done"] == 2
    assert s["requests_active"] == 0 and s["requests_queued"] == 0
    assert s["mean_ttft_s"] is not None


def test_stats_populations_partition_mid_prefill(setup):
    """A slot still chunk-prefilling counts under ``requests_prefilling``,
    NOT ``requests_active`` — the four populations must partition the
    submitted requests or benchmark consumers double-count."""
    cfg, params = setup
    eng = InferenceEngine(cfg, params, max_batch=1, max_seq=64, prefill_budget=4)
    eng.submit(list(range(2, 26)), max_new_tokens=4)  # 24-token prompt, 4/step
    eng.submit([30, 31], max_new_tokens=4)
    with pytest.warns(RuntimeWarning):
        eng.run_until_drained(max_steps=2)  # truncates mid-prefill
    s = eng.stats()
    assert s["requests_prefilling"] == 1
    assert s["requests_active"] == 0  # mid-prefill slot is not decoding
    assert s["requests_queued"] == 1
    assert (
        s["requests_done"] + s["requests_queued"] + s["requests_active"] + s["requests_prefilling"]
        == 2
    )


def test_sample_tokens_greedy_extreme_logits():
    """Greedy rows (temperature <= 0) must never route extreme logits
    through the 1e-6 temperature clamp: ``logits / 1e-6`` overflows fp32 to
    inf inside the sampled branch (sort / categorical) before ``jnp.where``
    discards it.  The safe-select keeps every intermediate finite and the
    greedy result exactly argmax."""
    from repro.serving.sampler import sample_tokens

    V = 16
    big = np.full((V,), -3.0e38, np.float32)
    big[7] = 3.0e38  # near-fp32-max spread: naive 1e6 scaling overflows
    logits = jnp.asarray(np.stack([big, np.roll(big, 3), np.linspace(-1, 1, V, dtype=np.float32)]))
    temps = jnp.asarray([0.0, -1.0, 0.7])  # two greedy rows, one sampled
    top_ks = jnp.asarray([0, 5, 3], jnp.int32)
    out = np.asarray(sample_tokens(logits, temps, top_ks, jax.random.PRNGKey(0)))
    assert out[0] == 7 and out[1] == 10, out
    assert 0 <= out[2] < V
    # all-greedy batch with the same extreme logits: still exact argmax
    out2 = np.asarray(
        sample_tokens(logits, jnp.zeros((3,)), jnp.zeros((3,), jnp.int32), jax.random.PRNGKey(1))
    )
    assert list(out2) == [int(np.argmax(np.asarray(l))) for l in logits]


def test_spec_accept_greedy_extreme_logits():
    """The verify-path twin of the sampler fix: greedy rows in
    ``spec_accept`` scale by a benign temperature so near-fp32-max logits
    can't produce inf/NaN in the (discarded) softmax lanes, and the greedy
    accept rule stays exact argmax-prefix comparison."""
    from repro.serving.sampler import _target_probs, spec_accept

    B, K, V = 1, 2, 8
    logits = np.full((B, K + 1, V), -3.0e38, np.float32)
    argmaxes = [2, 5, 1]
    for i, a in enumerate(argmaxes):
        logits[0, i, a] = 3.0e38
    logits = jnp.asarray(logits)
    temps = jnp.zeros((B,))
    top_ks = jnp.zeros((B,), jnp.int32)
    p = np.asarray(_target_probs(logits, temps, top_ks))
    assert np.isfinite(p).all(), "greedy _target_probs produced non-finite probs"
    drafts = jnp.asarray([[2, 5]], jnp.int32)  # matches argmax prefix
    q = jax.nn.one_hot(drafts, V, dtype=jnp.float32)
    n_acc, final = spec_accept(
        logits, drafts, q, jnp.ones((B, K), bool), temps, top_ks, jax.random.PRNGKey(0)
    )
    assert int(n_acc[0]) == K and int(final[0]) == 1
