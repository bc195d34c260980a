"""The traffic generator: seeded, the same work for every seed, in range."""

import copy

import numpy as np
import pytest

from bench import spec
from bench.traffic.generate import arrival_times, schedule, tenant_prompts

MIXES = ("offline", "shared-prefix")
VOCAB = 50304


def mix(name, rate=2.0):
    m = copy.deepcopy(spec.load_json(spec.BENCH / "traffic" / f"{name}.json"))
    if m["arrival"]["kind"] != "backlog":
        m["arrival"]["rate_per_s"] = rate
    return m


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = schedule(mix(name), seed=2**31 + 77, seconds=20, vocab=VOCAB)
    b = schedule(mix(name), seed=2**31 + 77, seconds=20, vocab=VOCAB)
    assert a == b and len(a) > 10


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_and_arrivals_not_tokens(name):
    a = schedule(mix(name), seed=1, seconds=20, vocab=VOCAB)
    b = schedule(mix(name), seed=2, seconds=20, vocab=VOCAB)
    assert [x.due for x in a] == [x.due for x in b]
    assert [(len(x.prompt), x.max_new_tokens, x.tenant) for x in a] == [(len(x.prompt), x.max_new_tokens, x.tenant) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_tokens_in_range(name):
    m = mix(name)
    items = schedule(m, seed=3, seconds=20, vocab=VOCAB)
    system = m["tenants"]["system"] if m.get("tenants") else {"min": 0, "max": 0}
    for it in items:
        tail = len(it.prompt) - it.shared
        assert m["prompt"]["min"] <= tail <= m["prompt"]["max"]
        assert system["min"] <= it.shared <= system["max"]
        assert m["output"]["min"] <= it.max_new_tokens <= m["output"]["max"]
        assert min(it.prompt) >= 2 and max(it.prompt) < VOCAB


def test_lognormal_median_as_designed():
    m = mix("offline")
    m["arrival"]["count"] = 4000
    items = schedule(m, seed=4, seconds=20, vocab=VOCAB)
    assert abs(np.median([len(i.prompt) for i in items]) / 256 - 1) < 0.06
    assert abs(np.median([i.max_new_tokens for i in items]) / 384 - 1) < 0.06


def test_shared_prefix_share_of_tokens():
    """Each request starts with its tenant's system prompt; the shared share
    of prompt tokens is what the length distributions make it, about
    1024 / (1024 + 144)."""
    items = schedule(mix("shared-prefix", rate=40.0), seed=5, seconds=20, vocab=VOCAB)
    systems = tenant_prompts(items)
    assert len(set(systems.values())) == len(systems) > 8
    for it in items:
        assert it.prompt[: it.shared] == systems[it.tenant]
    share = sum(it.shared for it in items) / sum(len(it.prompt) for it in items)
    assert 0.83 < share < 0.92
    counts = np.bincount([it.tenant for it in items], minlength=16)
    assert counts[0] == counts.max()  # Zipf: the first tenant is the most popular


def test_unshared_mixes_share_nothing():
    for name in ("offline",):
        items = schedule(mix(name), seed=6, seconds=20, vocab=VOCAB)
        assert all(it.tenant == -1 and it.shared == 0 for it in items)
        assert len({it.prompt[:16] for it in items}) == len(items)


def test_poisson_rate_and_window():
    rng = np.random.default_rng(0)
    t = arrival_times({"kind": "poisson", "rate_per_s": 50.0}, 40.0, rng)
    assert np.all(np.diff(t) >= 0) and t.max() < 40.0
    assert abs(len(t) - 2000) < 4 * np.sqrt(2000)


def test_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError, match="unknown arrival kind"):
        arrival_times({"kind": "bursts", "rate_per_s": 2.0}, 10.0, np.random.default_rng(0))


def test_backlog_is_due_at_once():
    items = schedule(mix("offline"), seed=7, seconds=20, vocab=VOCAB)
    assert len(items) == mix("offline")["arrival"]["count"]
    assert all(it.due == 0.0 for it in items)


def test_open_loop_needs_a_rate():
    m = mix("shared-prefix")
    m["arrival"]["rate_per_s"] = None
    with pytest.raises(ValueError):
        schedule(m, seed=1, seconds=5, vocab=VOCAB)
