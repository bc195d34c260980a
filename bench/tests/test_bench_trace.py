"""The reduction from a profiler trace to busy time, idle share and labelled gaps."""

from pathlib import Path

import pytest

from bench import trace_reduce as tr

PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"


def test_union_merges_and_clips():
    ops = [(5, 10, "a"), (8, 12, "b"), (20, 30, "c"), (29, 31, "d"), (-5, 1, "e")]
    assert tr.union(ops, 0, 30) == [(0, 1), (5, 12), (20, 30)]
    assert tr.gaps([(0, 1), (5, 12), (20, 30)], 0, 40) == [(1, 5), (12, 20), (30, 40)]


def synthetic():
    ev = tr.TraceEvents()
    # window 0..100 ns; device 0 busy 10..40 and 50..60 (ops overlap), device 1 busy 0..100
    ev.device_ops["/device:TPU:0"] = [(10, 30, "fusion.1"), (20, 40, "fusion.2"), (50, 60, "fusion.1")]
    ev.device_ops["/device:TPU:1"] = [(0, 100, "while.3")]
    ev.host = [
        (0, 100, tr.WINDOW_SPAN),
        (0, 12, "engine_step"),
        (40, 48, "engine_step"),
        (38, 70, "await_arrival"),
        (60, 100, "submit"),
    ]
    return ev


def test_busy_union_idle_share_and_ops():
    r = tr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((40e-9 + 100e-9) / 2)  # averaged over devices
    assert r["idle_share"] == pytest.approx(1 - 70 / 100)
    assert r["device_ops"][0] == ["while.3", pytest.approx(100e-9)]
    assert dict((n, t) for n, t in r["device_ops"])["fusion.1"] == pytest.approx(30e-9)


def test_gaps_are_labelled_by_the_host_span_overlapping_most():
    r = tr.reduce(synthetic())
    # device 0 idles 0..10 (engine_step), 40..50 (engine_step 8 vs await 10 -> await),
    # 60..100 (submit 40 vs await 10 -> submit)
    assert r["idle_gaps"] == [
        ["submit", pytest.approx(40e-9)],
        ["engine_step", pytest.approx(10e-9)],
        ["await_arrival", pytest.approx(10e-9)],
    ]
    assert r["idle_by_span"] == pytest.approx({"submit": 40e-9, "engine_step": 10e-9, "await_arrival": 10e-9})


def test_busy_between_clips_to_the_window_and_averages_devices():
    r = tr.reduce(synthetic())
    # device 0 busy 10..40 and 50..60, device 1 busy 0..100
    assert tr.busy_between(r, 35, 55) == pytest.approx((10e-9 + 20e-9) / 2)
    assert tr.busy_between(r, -50, 12) == pytest.approx((2e-9 + 12e-9) / 2)
    assert tr.busy_between(r, 90, 500) == pytest.approx((0 + 10e-9) / 2)
    assert r["window_ns"] == (0, 100)


def test_gap_outside_every_span():
    ev = tr.TraceEvents(device_ops={"/device:TPU:0": [(0, 10, "x")]}, host=[(0, 30, tr.WINDOW_SPAN)])
    assert tr.reduce(ev)["idle_gaps"] == [[tr.NO_SPAN, pytest.approx(20e-9)]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.TraceEvents(host=[(0, 10, tr.WINDOW_SPAN)]))


def test_op_name_from_hlo_text():
    assert tr.op_name("%convolution_tanh_fusion = bf16[2048,2048]{1,0} fusion(%x), kind=kOutput") == "convolution_tanh_fusion"


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5 lite: five 2048^3 matmul steps, each in
    an ``engine_step`` span, with 2 ms sleeps in ``await_arrival`` between."""
    ev = tr.read_xplane(str(PROBE))
    assert list(ev.device_ops) == ["/device:TPU:0"]
    assert len(ev.device_ops["/device:TPU:0"]) == 20
    names = [h[2] for h in ev.host]
    assert names.count("engine_step") == 5 and names.count("await_arrival") == 5
    assert names.count(tr.WINDOW_SPAN) == 1
    r = tr.reduce(ev)
    lo, hi = tr.window_of(ev)
    busy = sum(b - a for a, b in tr.union(ev.device_ops["/device:TPU:0"], lo, hi))
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0.5e-3 < r["busy_s"] < 2e-3  # five matmuls of about 0.18 ms
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["idle_by_span"]["await_arrival"] > r["idle_by_span"].get("engine_step", 0)
