"""The metric arithmetic: every sample pooled, rates over the whole window,
FLOPs and bytes from the configuration's shapes, and a reader for every
metric that BENCHMARK.json names."""

import numpy as np
import pytest

from bench import spec, work
from bench.record import RequestRecord, RunRecord, StepRecord

OLMO = spec.load_json(spec.BENCH / "configs" / "olmo-1b.json")
PEAK = spec.peaks("TPU v5 lite")


def record(requests=(), steps=(), **kw):
    base = dict(
        seconds=10.0, t_open=100.0, t_close=110.0, t_drained=130.0, chips=1, max_batch=64,
        peak=PEAK, shapes=work.Shapes.of(OLMO), setup_s=12.5,
    )
    base.update(kw)
    return RunRecord(requests=list(requests), steps=list(steps), **base)


def req(due, times):
    r = RequestRecord(due_t=due, prompt=(2, 3), target=len(times))
    r.token_t = list(times)
    r.tokens = [5] * len(times)
    return r


def metric(name, rec):
    return spec.metric_reader(name)(rec)


def test_token_gap_percentile_pools_every_gap():
    """One request with many small gaps and one with a few big ones: the
    pooled p95 is not the mean of per-request p95s."""
    a = req(100.0, 100.0 + 0.01 * np.arange(200))
    b = req(100.0, [100.0, 101.0, 102.0, 103.0])
    rec = record([a, b])
    gaps = list(np.diff(a.token_t)) + [1.0, 1.0, 1.0]
    assert metric("itl_p95_s", rec) == pytest.approx(np.percentile(gaps, 95))
    per_request = np.mean([np.percentile(np.diff(r.token_t), 95) for r in (a, b)])
    assert metric("itl_p95_s", rec) != pytest.approx(per_request)


def test_ttft_counts_from_due_and_misses_until_the_drain():
    rs = [req(100.0 + i, [100.5 + i]) for i in range(19)] + [req(105.0, [])]
    rec = record(rs)
    ttfts = [0.5] * 19 + [130.0 - 105.0]
    assert metric("ttft_p50_s", rec) == pytest.approx(np.percentile(ttfts, 50))
    assert metric("ttft_p95_s", rec) == pytest.approx(np.percentile(ttfts, 95))


def test_output_rate_is_over_the_whole_window():
    early = req(99.0, [99.5, 100.0, 100.5, 101.0, 109.9, 110.0, 111.0])
    rec = record([early])
    assert metric("output_tok_per_s", rec) == pytest.approx(4 / 10.0)  # 100.0, 100.5, 101.0, 109.9


def test_prefix_hit_share_from_window_counters():
    rec = record(
        counters_open={"engine_prefix_hit_tokens_total": 1000.0, "engine_prefill_tokens_total": 500.0},
        counters_close={"engine_prefix_hit_tokens_total": 9000.0, "engine_prefill_tokens_total": 2500.0},
    )
    assert metric("prefix_hit_share", rec) == pytest.approx(100 * 8000 / 10000)
    assert metric("prefix_hit_share", record()) is None


def steps():
    return [
        StepRecord(99.0, 99.2, 10, 0, [100] * 10, [], 10),  # before the window
        StepRecord(100.0, 100.2, 40, 0, [1000] * 40, [], 40),
        StepRecord(100.2, 100.5, 30, 512, [900] * 30, [(0, 512)], 31),
        StepRecord(100.5, 100.6, 0, 256, [], [(512, 768)], 0),
        StepRecord(100.6, 100.8, 50, 0, [1200] * 50, [], 50),
    ]


def test_decode_step_time_and_occupancy():
    rec = record(steps=steps())
    assert metric("decode_step_ms.offline", rec) == pytest.approx(200.0)
    assert metric("batch_occupancy.offline", rec) == pytest.approx(100 * (40 + 30 + 50) / 3 / 64)


def test_step_mfu_by_hand():
    s = work.Shapes.of(OLMO)
    rec = record(steps=steps())
    flops = (
        40 * s.layers * (2 * s.layer_params + 4 * s.heads * s.head_dim * 1000)
        + 30 * s.layers * (2 * s.layer_params + 4 * s.heads * s.head_dim * 900)
        + 50 * s.layers * (2 * s.layer_params + 4 * s.heads * s.head_dim * 1200)
        + s.layers * (2 * s.layer_params * 768 + 4 * s.heads * s.head_dim * 768 * 769 / 2)
        + (40 + 31 + 50) * 2 * s.d * s.vocab
    )
    assert metric("step_mfu.online", rec) == pytest.approx(100 * flops / (0.8 * 197e12))


def traced_record():
    """The steps above, traced from host time 100.05 to 100.85, which the
    trace's clock reads as 5.05e9 to 5.85e9 ns. The device is busy 0.19 s
    in the first decode step's stretch (100.0-100.2), 0.3 s in the prefill
    step's, 0.05 s in the chunk-only step's and 0.15 s in the last decode
    step's (100.6-100.8)."""
    busy = [(5.01e9, 5.2e9), (5.2e9, 5.5e9), (5.52e9, 5.57e9), (5.6e9, 5.75e9)]
    trace = {"window_ns": (5.05e9, 5.85e9), "busy_union": {"/device:TPU:0": busy}}
    return record(steps=steps(), trace=trace, trace_span=(100.05, 100.85))


def test_decode_roofline_takes_the_longer_bound():
    s = work.Shapes.of(OLMO)
    rec = traced_record()
    # the first decode step began before the traced span: only the last counts
    by_bytes = work.decode_step_bytes(s, [1200] * 50) / 819e9
    by_flops = 50 * (s.layers * (2 * s.layer_params + 4 * s.heads * s.head_dim * 1200) + 2 * s.d * s.vocab) / 197e12
    assert metric("decode_hbm_roofline.offline", rec) == pytest.approx(100 * max(by_bytes, by_flops) / 0.15)


def test_decode_roofline_divides_by_device_time_not_step_time():
    """Host time around the step (0.2 s) is not the denominator: the device
    was busy 0.15 s of it."""
    rec = traced_record()
    wide = traced_record()
    wide.trace["busy_union"]["/device:TPU:0"][-1] = (5.6e9, 5.8e9)
    assert metric("decode_hbm_roofline.offline", rec) == pytest.approx(metric("decode_hbm_roofline.offline", wide) * 0.2 / 0.15)
    assert metric("decode_hbm_roofline.offline", record(steps=steps())) is None


def test_prefill_flops_sum_token_flops():
    s = work.Shapes.of(OLMO)
    assert work.prefill_flops(s, 100, 300) == pytest.approx(sum(work.token_flops(s, p + 1) for p in range(100, 300)))


def test_olmo_sizes():
    s = work.Shapes.of(OLMO)
    params = s.layers * s.layer_params + s.head_params
    assert params == pytest.approx(1.177e9, rel=0.01)  # OLMo-1B, tied head
    assert s.kv_bytes_per_token == 128 * 1024


def test_split_metric_falls_back_to_its_base_reader(tmp_path):
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "m.py").write_text("def compute(rec):\n    return 1\n")
    (tmp_path / "bench" / "metrics" / "m.b.py").write_text("def compute(rec):\n    return 2\n")
    assert spec.metric_reader("m.a", tmp_path)(None) == 1
    assert spec.metric_reader("m.b", tmp_path)(None) == 2
    assert spec.metric_reader("m", tmp_path)(None) == 1
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("other.a", tmp_path)


def test_every_metric_has_a_reader_and_every_cell_reports_what_it_must():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for n in names:
        assert callable(spec.metric_reader(n))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
