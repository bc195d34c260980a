"""Observability layer: metrics registry, request tracing, profiling, energy.

Covers the contracts docs/observability.md promises:

* histogram bucket/percentile math against a numpy oracle (error bounded by
  one factor-2 bucket width);
* exact, deterministic engine latencies under an injected ``ManualClock``
  (no sleeps);
* per-request event ordering (submit < admit < chunks < first_token <
  finish) and Chrome-trace JSON schema validity;
* the engine's phase spans add **no** host syncs: a run under an active
  profiler session makes the same host fetches and ``block_until_ready``
  calls as one without;
* ``stats()`` is a defensive snapshot with division-by-zero-guarded rates;
* energy attribution: step joules split over the requests that did work;
* TP=1 vs TP=2 metrics parity for device-invariant counters (runs in the
  CI tp-serving lane; skips on a single-device jax).
"""

import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    SCHEDULER_TRACK,
    EnergyBridge,
    Histogram,
    InferenceEngine,
    ManualClock,
    MetricsRegistry,
    Tracer,
    exponential_buckets,
    slot_track,
)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


# ---------------------------------------------------------------- registry
def test_exponential_buckets():
    assert exponential_buckets(1.0, 2.0, 3) == [1.0, 2.0, 4.0]
    with pytest.raises(ValueError):
        exponential_buckets(0.0, 2.0, 3)
    with pytest.raises(ValueError):
        exponential_buckets(1.0, 1.0, 3)


def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    # get-or-create is idempotent, kind mismatch raises
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError):
        reg.gauge("c_total")


def test_histogram_percentiles_vs_numpy_oracle():
    rng = np.random.default_rng(42)
    values = rng.lognormal(mean=-6.0, sigma=2.0, size=500)  # spans many buckets
    h = Histogram("lat_seconds")
    for v in values:
        h.observe(float(v))
    assert h.count == 500
    assert h.sum == pytest.approx(values.sum())
    assert h.min == values.min() and h.max == values.max()
    for pct in (50, 90, 99):
        est = h.percentile(pct)
        true = float(np.percentile(values, pct))
        # estimate lies in the bucket of the rank-th order stat; with
        # factor-2 buckets that bounds the ratio to ~one bucket width
        assert true / 2.5 <= est <= true * 2.5, (pct, est, true)


def test_histogram_edge_cases():
    h = Histogram("h", buckets=[1.0, 2.0, 4.0])
    assert h.percentile(50) is None and h.mean is None
    h.observe(1.5)
    assert h.percentile(50) == 1.5  # single value: clamped to min==max
    h.observe(100.0)  # overflow bucket has no upper edge -> observed max
    assert h.percentile(99) == 100.0
    with pytest.raises(ValueError):
        h.percentile(0)
    with pytest.raises(ValueError):
        Histogram("bad", buckets=[2.0, 1.0])


def test_render_text_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3)
    h = reg.histogram("lat", buckets=[1.0, 2.0])
    h.observe(0.5)
    h.observe(1.5)
    h.observe(9.0)
    text = reg.render_text()
    assert "# TYPE req_total counter" in text
    assert "req_total 3" in text
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="2"} 2' in text  # cumulative
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text


def test_snapshot_is_json_serializable():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.gauge("g").set(2)
    reg.histogram("h").observe(0.01)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["c"]["value"] == 1
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["histograms"]["h"]["p50"] == pytest.approx(0.01)
    # empty histograms serialize their stats as null, not NaN/inf
    reg.histogram("empty")
    json.dumps(reg.snapshot())
    assert reg.percentiles("empty")[50] is None
    assert reg.percentiles("missing")[99] is None


def test_manual_clock():
    clk = ManualClock(start=10.0)
    assert clk() == 10.0 and clk() == 10.0  # frozen without tick
    clk.advance(0.5)
    assert clk() == 10.5
    with pytest.raises(ValueError):
        clk.advance(-1)
    ticking = ManualClock(tick=0.25)
    assert [ticking() for _ in range(3)] == [0.0, 0.25, 0.5]


# ------------------------------------------------------------------ tracer
def test_tracer_ring_buffer_drops_oldest():
    clk = ManualClock(tick=1.0)
    tr = Tracer(clock=clk, capacity=3)
    for i in range(5):
        tr.instant(f"e{i}")
    assert [e.name for e in tr.events] == ["e2", "e3", "e4"]
    assert tr.recorded == 5 and tr.dropped == 2
    assert tr.to_chrome()["metadata"]["dropped_events"] == 2


def test_tracer_chrome_schema():
    clk = ManualClock(start=100.0, tick=0.001)
    tr = Tracer(clock=clk, capacity=64)
    tr.instant("submit", track=SCHEDULER_TRACK, req_id=0, online=True)
    t0 = tr.now()
    tr.span("prefill", t0, track=slot_track(2), req_id=0, tokens=8)
    doc = tr.to_chrome()
    evs = doc["traceEvents"]
    assert all({"name", "ph", "ts", "pid", "tid", "args"} <= set(e) for e in evs)
    names = {e["args"].get("name") for e in evs if e["ph"] == "M"}
    assert {"paged-engine", "scheduler", "slot 2"} <= names
    inst = next(e for e in evs if e["name"] == "submit")
    assert inst["ph"] == "i" and inst["ts"] == 0.0  # rebased to first event
    assert inst["args"]["req_id"] == 0
    span = next(e for e in evs if e["name"] == "prefill")
    assert span["ph"] == "X" and span["dur"] > 0 and span["tid"] == slot_track(2)
    json.dumps(doc)  # must be a valid JSON document


# ------------------------------------------------------------------ engine
def test_engine_exact_latencies_with_manual_clock(setup):
    """Frozen clock + explicit advances make latencies exact equalities."""
    cfg, params = setup
    clk = ManualClock()
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64, clock=clk)
    r = eng.submit([3, 1, 4], max_new_tokens=4)
    assert r.submit_t == 0.0
    clk.advance(0.5)  # request sits in the queue for exactly 0.5s
    eng.step()  # admit + prefill + first token, clock frozen at 0.5
    assert r.admit_t == 0.5 and r.queue_wait == 0.5
    assert r.first_token_t == 0.5 and r.ttft == 0.5
    h = eng.metrics.get("engine_ttft_seconds")
    assert h.count == 1 and h.percentile(50) == 0.5  # clamped to min==max
    assert eng.metrics.get("engine_queue_wait_seconds").percentile(99) == 0.5
    clk.advance(0.25)
    eng.run_until_drained()
    assert r.done_t == 0.75
    # 3 decode tokens after the first, all in frozen-clock steps -> tpot 0
    assert r.tpot == pytest.approx(0.25 / 3)
    assert eng.stats()["ttft_p50_s"] == 0.5


def test_engine_event_ordering_per_request(setup):
    cfg, params = setup
    clk = ManualClock(tick=1e-4)  # strictly increasing timestamps
    eng = InferenceEngine(
        cfg, params, max_batch=2, max_seq=64, block_size=8,
        prefill_budget=8, clock=clk,
    )
    reqs = [eng.submit(list(range(2, 20)), max_new_tokens=3) for _ in range(2)]
    eng.run_until_drained()
    for r in reqs:
        evs = eng.tracer.events_for(r.req_id)
        by_name = {}
        for e in evs:
            by_name.setdefault(e.name, []).append(e)
        for name in ("submit", "admit", "prefill_chunk", "first_token", "finish"):
            assert name in by_name, f"req {r.req_id} missing {name}"
        t = lambda n: by_name[n][0].ts
        assert t("submit") < t("admit") < t("prefill_chunk")
        assert t("prefill_chunk") < t("first_token") < t("finish")
        # chunks are spans on the request's slot track, in time order
        chunks = by_name["prefill_chunk"]
        assert all(e.dur is not None for e in chunks)
        assert [e.ts for e in chunks] == sorted(e.ts for e in chunks)
        assert {e.track for e in evs if e.name != "submit"} == {slot_track(r.slot)}
        # the admit -> finish envelope span brackets the whole lifetime
        # (admit_t is read one clock tick before the admit instant)
        env = by_name[f"req {r.req_id}"][0]
        assert env.ts <= t("admit") and env.ts < t("first_token") < env.ts + env.dur
    # the scheduler track carries the step spans
    steps = [e for e in eng.tracer.events if e.name == "step"]
    assert steps and all(e.track == SCHEDULER_TRACK for e in steps)


def test_spans_add_no_host_syncs(setup, monkeypatch, tmp_path):
    """Phase spans are profiler annotations only: a drain with the profiler
    recording them makes the same ``_host_fetch`` and ``block_until_ready``
    calls as one without, and the step span carries no phase timings."""
    cfg, params = setup
    blocks = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        blocks["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)

    def drain(profiled: bool):
        eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64, eos_token=-1)
        fetches = {"n": 0}
        fetch = eng._host_fetch

        def counted_fetch(*arrays):
            fetches["n"] += 1
            return fetch(*arrays)

        eng._host_fetch = counted_fetch
        blocks["n"] = 0
        eng.submit([5, 6, 7], max_new_tokens=4)
        eng.submit([8, 9], max_new_tokens=3)
        with jax.profiler.trace(str(tmp_path)) if profiled else contextlib.nullcontext():
            eng.run_until_drained()
        steps = [e for e in eng.tracer.events if e.name == "step"]
        assert steps and all(set(e.args) == {"produced"} for e in steps)
        return fetches["n"], blocks["n"], eng.host_syncs_total, eng.dispatches_total

    plain, traced = drain(False), drain(True)
    assert plain == traced
    assert plain[0] > 0 and plain[1] == 0


def test_stats_defensive_snapshot_and_guards(setup):
    cfg, params = setup
    eng = InferenceEngine(
        cfg, params, max_batch=2, max_seq=64, spec_decode="ngram", spec_k=2
    )
    s = eng.stats()  # empty drain: every derived rate must guard, not raise
    assert s["mean_ttft_s"] is None and s["ttft_p50_s"] is None
    assert s["acceptance_rate"] == 0.0 and s["accepted_per_step"] == 0.0
    assert s["prefix_hit_rate"] == 0.0 and s["joules_per_token"] == 0.0
    # mutating the snapshot must not corrupt engine state
    s["tokens_out"] = 999999
    s.clear()
    s2 = eng.stats()
    assert s2["tokens_out"] == 0 and "cache_kind" in s2


def test_energy_attribution(setup):
    cfg, params = setup
    clk = ManualClock(tick=0.01)  # nonzero step durations without sleeping
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64, clock=clk)
    reqs = [eng.submit([9 + i, 2, 3], max_new_tokens=4) for i in range(3)]
    eng.run_until_drained()
    s = eng.stats()
    assert s["energy_joules"] > 0
    assert s["joules_per_token"] == pytest.approx(s["energy_joules"] / s["tokens_out"])
    # the step joules split exactly over the requests that did the work
    assert sum(r.energy_j for r in reqs) == pytest.approx(eng.energy.joules)
    assert all(r.energy_j > 0 and r.joules_per_token > 0 for r in reqs)
    assert eng.metrics.get("engine_energy_joules_total").value == pytest.approx(
        eng.energy.joules
    )
    # a fixed roofline utilization override scales the charge deterministically
    bridge = EnergyBridge(chips=4, utilization=0.5)
    j = bridge.record_step(2.0, occupancy=1.0)
    assert j > 0 and bridge.record_step(0.0, occupancy=1.0) == 0.0
    assert bridge.joules == j


def test_pool_and_prefix_metrics_published(setup, tmp_path):
    cfg, params = setup
    shared = [11, 12, 13, 14, 15, 16, 17, 18]
    eng = InferenceEngine(
        cfg, params, max_batch=2, max_seq=64, block_size=8,
        prefix_cache=True, prefill_budget=8,
    )
    for i in range(4):
        eng.submit(shared + [40 + i], max_new_tokens=3)
    eng.run_until_drained()
    m = eng.metrics
    assert m.get("pool_allocs_total").value > 0
    assert m.get("pool_blocks_in_use").value == eng.allocator.blocks_in_use
    assert m.get("pool_blocks_cached").value == eng.allocator.num_cached
    assert m.get("prefix_entries").value == len(eng.prefix)
    assert m.get("prefix_registrations_total").value == eng.prefix.registered
    assert m.get("engine_prefix_hit_tokens_total").value == eng.prefix_hit_tokens > 0
    # snapshot + chrome trace write end-to-end
    m.write_json(tmp_path / "metrics.json")
    snap = json.loads((tmp_path / "metrics.json").read_text())
    assert snap["histograms"]["engine_ttft_seconds"]["count"] == 4
    eng.tracer.write(tmp_path / "trace.json")
    doc = json.loads((tmp_path / "trace.json").read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"submit", "admit", "first_token", "finish", "step"} <= names


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)
def test_tp_metrics_parity(setup):
    """Device-invariant counters must match exactly between TP=1 and TP=2
    (latency histograms legitimately differ; token/block/prefix accounting
    must not)."""
    from repro.launch.mesh import make_serving_mesh

    cfg, params = setup
    prompts = [[11, 12, 13, 14, 15, 16, 17, 18] + [40 + i] for i in range(4)]

    def drive(mesh):
        eng = InferenceEngine(
            cfg, params, max_batch=2, max_seq=64, block_size=8,
            cache_dtype=jnp.float32, prefix_cache=True, prefill_budget=8,
            mesh=mesh,
        )
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        eng.run_until_drained()
        return eng

    base, tp = drive(None), drive(make_serving_mesh(2))
    for name in (
        "engine_requests_submitted_total",
        "engine_requests_finished_total",
        "engine_tokens_out_total",
        "engine_prefill_tokens_total",
        "engine_prefix_hit_tokens_total",
        "pool_allocs_total",
        "pool_frees_total",
        "pool_evictions_total",
        "prefix_registrations_total",
    ):
        assert base.metrics.get(name).value == tp.metrics.get(name).value, name
    assert base.metrics.get("engine_ttft_seconds").count == 4
    assert tp.metrics.get("engine_ttft_seconds").count == 4
    # TP charges mesh-size chips into the energy bridge
    assert base.energy.chips == 1 and tp.energy.chips == 2
