"""The program's spans, named device programs and scopes read from a profiler
trace, and the readings built on them."""

from pathlib import Path

import pytest

import numpy as np

from bench import program_trace as pt
from bench import spec
from bench import trace_reduce as tr
from bench import work
from bench.record import RequestRecord, RunRecord, StepRecord

PROBE = Path(__file__).parent / "data" / "probe.xplane.pb"
HOST = tr.HOST_SPANS + (tr.WINDOW_SPAN,)


def test_probe_ops_carry_their_program_and_tf_op():
    """A trace recorded on a TPU v5 lite: five runs of one program, each two
    2048^3 matmul fusions behind a copy, in an ``engine_step`` span."""
    trace = pt.read(str(PROBE), host_names=HOST)
    ops = trace.ops["/device:TPU:0"]
    runs = trace.runs["/device:TPU:0"]
    assert len(runs) == 5 and {p for _, _, p in runs} == {"jit__lambda"}
    assert len(ops) == 20 and all(o.leaf and o.program == "jit__lambda" for o in ops)
    assert [o.run for o in ops] == [i for i in range(5) for _ in range(4)]
    for o in ops:
        a, b, _ = runs[o.run]
        assert a <= o.start and o.end <= b
    fusions = [o for o in ops if "fusion" in o.name]
    assert len(fusions) == 10 and {o.tf_op for o in fusions} == {"jit(<lambda>)/dot_general:"}
    assert {o.scope for o in ops} == {pt.UNSCOPED}
    assert {o.tf_op for o in ops if "copy" in o.name} == {""}  # copies carry no op_name
    table = pt.op_metadata(memoryview(PROBE.read_bytes()))
    assert list(table) == ["/device:TPU:0"]
    assert len(table["/device:TPU:0"]) == 2  # the two fusions carry op_names
    assert {pid for pid, _ in table["/device:TPU:0"]} == {15526583290487371244}


def test_probe_skew_puts_each_run_inside_its_step():
    trace = pt.read(str(PROBE), host_names=HOST)
    assert trace.spans == {} and trace.skew_ns == 0.0  # the probe has no program spans
    s = pt.skew(trace, fetch="engine_step")
    assert -3e6 < s < -1e6  # the device's clock runs about 2 ms behind the host's here
    steps = sorted((a, b) for a, b, n in trace.host if n == "engine_step")
    for (a, b), (ra, rb, _) in zip(steps, trace.runs["/device:TPU:0"]):
        assert a + s <= ra and rb <= b + s + 0.5e6


def test_trace_reduce_reads_the_probe_as_before():
    r = tr.reduce(tr.read_xplane(str(PROBE)))
    assert r["busy_s"] == pytest.approx(0.000723358)
    assert r["window_s"] == pytest.approx(0.01639547)
    assert r["idle_by_span"] == pytest.approx({"await_arrival": 0.013754385, "engine_step": 0.001917727})
    assert [n for n, _ in r["device_ops"]] == ["fusion", "convolution_tanh_fusion", "copy-start", "copy-done"]
    assert r["window_ns"] == (46445490.0, 62840960.0)


@pytest.mark.parametrize(
    "tf_op, scope",
    [
        ("jit(decode_step)/while/body/closed_call/attention/convert_element_type:", "attention"),
        ("jit(decode_step)/while/body/attention/qkv/dot_general:", "qkv"),
        ("jit(decode_step)/while/body/attention/cache_write/scatter:", "cache_write"),
        ("jit(decode_step)/head/norm/reduce_sum:", "norm"),
        ("jit(decode_step)/while/body/mlp/dot_general:", "mlp"),
        ("jit(decode_step)/while/body/dynamic_slice:", pt.UNSCOPED),
        ("", pt.UNSCOPED),
    ],
)
def test_scope_is_the_innermost_named_one(tf_op, scope):
    assert pt.scope_of(tf_op) == scope


@pytest.mark.parametrize(
    "tf_op, own",
    [
        ("jit(decode_step)/while/body/closed_call/attention/gather:", True),
        ("jit(decode_step)/while/body/squeeze:", True),
        ("jit(decode_step)/while:", False),  # the loop's, on an op of its body with none
        ("jit(decode_step)/cond:", False),
        ("", False),
    ],
)
def test_own_op_name(tf_op, own):
    assert pt.own_op_name(tf_op) == own


@pytest.mark.parametrize(
    "text, reads",
    [
        ("%convert.47 = f32[8192,16,16,128]{3,2,1,0:T(8,128)} convert(bf16[8192,16,16,128]{3,2,1,0} %fusion.140)",
         ("fusion.140",)),
        ("%fusion.146 = f32[64,2048,16]{2,1,0} fusion(f32[64,16,128]{2,1,0} %copy-done.3, f32[64,2048,16,128]{3,2,1,0}"
         " %bitcast.211), kind=kLoop, calls=%fused_computation.8.clone.clone", ("copy-done.3", "bitcast.211")),
        ("%copy-start = (bf16[2048,2048]{1,0}, u32[]{:S(2)}) copy-start(bf16[2048,2048]{1,0} %x.1), "
         "cross_program_prefetch_index=0", ("x.1",)),
        ("%fusion.149 = bf16[64,8192]{1,0} fusion(%a.1, /*index=5*/%b.2), calls=%f", ("a.1", "b.2")),
        ("while.5", ()),
    ],
)
def test_operands_are_read_from_the_op_text(text, reads):
    assert pt.hlo_operands(text) == reads


def test_an_op_without_op_name_takes_the_scope_it_reads():
    """Modelled on the decode program a TPU v5e compiler emits: the float32
    converts of the gathered K/V carry only the layer loop's op_name and read
    the ``attention`` gather; the scan's pool copies read loop state the
    trace does not hold."""
    D, C = "jit_decode_step", "jit_chunk_step"
    op = lambda name, tf_op, reads, program=D, module=1: pt.Op(
        0, 1, name, program, 0, tf_op, pt.scope_of(tf_op), reads=reads, module=module
    )
    ops = [
        op("fusion.140", "jit(decode_step)/while/body/closed_call/attention/gather:", ("fusion.138",)),
        op("fusion.149", "jit(decode_step)/while/body/closed_call/mlp/dot_general:", ()),
        op("slice.6", "jit(decode_step)/while/body/squeeze:", ()),
        op("convert.47", "jit(decode_step)/while:", ("fusion.140",)),  # the loop's op_name, reads the gather
        op("copy.3", "", ("convert.47",)),  # through another op with no op_name
        op("copy.67", "", ("get-tuple-element.599",)),  # reads nothing in the trace
        op("add.1", "", ("fusion.140", "fusion.149")),  # two scopes between them
        op("copy.8", "", ("slice.6",)),  # reads an op with an op_name but no scope
        op("convert.47", "", ("fusion.140",), program=C),  # no fusion.140 in this program
        # a second compilation of the decode program, whose fusion.140 is another op
        op("fusion.140", "jit(decode_step)/while/body/closed_call/mlp/dot_general:", (), module=2),
        op("convert.47", "", ("fusion.140",), module=2),
    ]
    pt.inherit_scopes(ops)
    assert [(o.scope, o.inherited) for o in ops[3:]] == [
        ("attention", True), ("attention", True), (pt.UNSCOPED, False), (pt.UNSCOPED, False),
        (pt.UNSCOPED, False), (pt.UNSCOPED, False), ("mlp", False), ("mlp", True),
    ]
    assert [o.scope for o in ops[:3]] == ["attention", "mlp", pt.UNSCOPED]
    t = pt.ProgramTrace()
    t.ops["/device:TPU:0"] = ops
    assert ["convert.47", "attention<"] in [row[:2] for row in pt.top_ops(t, 0, 1)]


def test_nesting_and_leaves():
    spans = pt.nest(
        [(30, 40, "engine.emit"), (0, 100, "engine.step"), (12, 15, "engine.admit"), (10, 20, "engine.schedule")], "w"
    )
    assert [(s.name, s.depth, s.parent) for s in spans] == [
        ("engine.step", 0, None),
        ("engine.schedule", 1, 0),
        ("engine.admit", 2, 1),
        ("engine.emit", 1, 0),
    ]
    assert pt.chain(spans, 2) == ("engine.step", "engine.schedule", "engine.admit")
    ops = [pt.Op(0, 100, "while.5", "p", 0, "", pt.UNSCOPED), pt.Op(10, 20, "a", "p", 0, "", pt.UNSCOPED),
           pt.Op(30, 40, "b", "p", 0, "", pt.UNSCOPED), pt.Op(120, 130, "c", "p", 1, "", pt.UNSCOPED)]
    pt.mark_leaves(ops)
    assert [o.leaf for o in ops] == [False, True, True, True]
    assert pt.program_of("jit_decode_step(123)") == ("jit_decode_step", 123)


def idle_trace():
    """Window 0..100 ns; device busy 10..40 and 60..80; the worker thread in
    one step fetching 20..45 and emitting 45..55; the loop thread waiting
    0..5; the harness's ``engine_step`` 4..96."""
    t = pt.ProgramTrace()
    t.ops["/device:TPU:0"] = [pt.Op(10, 40, "a", "jit_decode_step", 0, "x/attention/dot:", "attention"),
                              pt.Op(60, 80, "b", "jit_sample", 1, "", pt.UNSCOPED)]
    t.runs["/device:TPU:0"] = [(10, 40, "jit_decode_step"), (60, 80, "jit_sample")]
    t.spans["w"] = pt.nest([(5, 95, "engine.step"), (20, 45, "engine.host_fetch"), (45, 55, "engine.emit")], "w")
    t.spans["loop"] = pt.nest([(0, 5, "async.wait")], "loop")
    t.host = [(0, 100, tr.WINDOW_SPAN), (4, 96, "engine_step")]
    return t


def test_idle_is_put_down_to_the_innermost_open_span():
    t = idle_trace()
    lo, hi = pt.window(t)
    assert (lo, hi) == (0, 100)
    r = pt.idle_split(t, lo, hi)
    assert r["idle_s"] == pytest.approx(50e-9)
    assert r["by_span"] == pytest.approx(
        {"engine.step": 25e-9, "engine.emit": 10e-9, "async.wait": 5e-9, "engine.host_fetch": 5e-9, pt.NO_SPAN: 5e-9}
    )
    assert r["inside"] == pytest.approx(
        {"engine.step": 25e-9, "engine.step/engine.emit": 10e-9, "engine.step/engine.host_fetch": 5e-9,
         "async.wait": 1e-9, pt.NO_SPAN: 1e-9}
    )
    assert pt.named_child_share(r["inside"]) == pytest.approx(15 / 42)


def test_skew_from_fetch_returns_and_its_use():
    t = idle_trace()
    t.spans["w"] = pt.nest([(5, 95, "engine.step"), (20, 38, "engine.host_fetch"), (70, 79, "engine.host_fetch")], "w")
    assert pt.skew(t) == pytest.approx((2 + 1) / 2)  # busy ends 40 and 80
    t.skew_ns = 10.0  # host spans move 10 ns later onto the device clock
    assert pt.window(t) == (10, 110)
    # idle 40..60 and 80..110; the step now spans 15..105, the fetches 30..48 and 80..89
    assert pt.idle_split(t, *pt.window(t))["by_span"] == pytest.approx(
        {"engine.step": 28e-9, "engine.host_fetch": 17e-9, pt.NO_SPAN: 5e-9}
    )


def test_by_program_and_by_scope():
    t = idle_trace()
    t.ops["/device:TPU:0"].insert(0, pt.Op(5, 45, "while.1", "jit_decode_step", 0, "", pt.UNSCOPED, leaf=False))
    t.ops["/device:TPU:0"].append(pt.Op(40, 45, "c", "jit_decode_step", 0, "x/dynamic_slice:", pt.UNSCOPED))
    t.runs["/device:TPU:0"][0] = (5, 45, "jit_decode_step")
    assert pt.by_program(t, 0, 100) == pytest.approx({"jit_decode_step": 35e-9, "jit_sample": 20e-9})
    assert pt.top_ops(t, 0, 100) == [["a", "attention", pytest.approx(30e-9)], ["c", pt.UNSCOPED, pytest.approx(5e-9)]]
    s = pt.by_scope(t, 0, 100)
    assert s["attention"] == pytest.approx(30e-9) and s[pt.UNSCOPED] == pytest.approx(5e-9)
    assert s["runs"] == pytest.approx(40e-9)
    assert sum(v for k, v in s.items() if k != "runs") == pytest.approx(35e-9)


SHAPES = work.Shapes(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16, vocab=32)
PEAK = {"hbm_bytes_per_s": 32e9, "bf16_flops_per_s": 64e12}


def step_trace():
    """Two decode steps and a prefill step on the worker thread; each decode
    step runs a decode program whose attention takes 2000 and 3000 ns."""
    t = pt.ProgramTrace()
    t.host = [(1000, 100000, tr.WINDOW_SPAN)]
    t.spans["w"] = pt.nest(
        [
            (2000, 12000, "engine.step"), (2100, 2200, "engine.dispatch.decode"), (3000, 9000, "engine.host_fetch"),
            (20000, 30000, "engine.step"), (21000, 28000, "engine.host_fetch"),
            (40000, 50000, "engine.step"), (41000, 49000, "engine.host_fetch"),
        ],
        "w",
    )
    dev = "/device:TPU:0"
    t.runs[dev] = [(2500, 8500, "jit_decode_step"), (8600, 8800, "jit_sample"), (21500, 27000, "jit_decode_step"),
                   (40500, 48000, "jit_chunk_step")]
    t.ops[dev] = [
        pt.Op(2500, 8500, "while.5", "jit_decode_step", 0, "", pt.UNSCOPED, leaf=False),
        pt.Op(2600, 4600, "convert.48", "jit_decode_step", 0, "a/attention/c:", "attention"),
        pt.Op(4600, 8400, "fusion.1", "jit_decode_step", 0, "a/mlp/d:", "mlp"),
        pt.Op(8600, 8800, "reduce.1", "jit_sample", 1, "", pt.UNSCOPED),
        pt.Op(21600, 24600, "convert.48", "jit_decode_step", 2, "a/attention/c:", "attention"),
        pt.Op(24600, 26900, "fusion.2", "jit_decode_step", 2, "", pt.UNSCOPED),
        pt.Op(40600, 47000, "fusion.9", "jit_chunk_step", 3, "a/attention/c:", "attention"),
    ]
    rec = RunRecord(seconds=1.0, t_open=0.0, t_close=1.0, t_drained=1.0, chips=1, max_batch=4, peak=PEAK,
                    shapes=SHAPES, setup_s=0.0, trace_span=(1e-6, 1e-4))
    rec.steps = [
        StepRecord(1.9e-6, 12.1e-6, 2, 0, decode_keys=[100, 200]),
        StepRecord(19.9e-6, 30.1e-6, 2, 0, decode_keys=[300, 400]),
        StepRecord(39.9e-6, 50.1e-6, 1, 64, decode_keys=[500], prefill_spans=[(0, 64)]),
    ]
    return t, rec


def test_steps_match_their_engine_step_spans():
    t, rec = step_trace()
    pairs = pt.step_spans(t, rec)
    assert [(sp.start, sp.end) for _, sp in pairs] == [(2000, 12000), (20000, 30000), (40000, 50000)]
    assert len(pt.decode_only(pairs)) == 2


def test_host_step_ms_leaves_out_the_fetch():
    t, rec = step_trace()
    assert pt.host_step_ms(t, rec) == pytest.approx(((10000 - 6000) + (10000 - 7000)) / 2 / 1e6)


def test_decode_attn_roofline_of_known_steps():
    t, rec = step_trace()
    kv = 2 * 2 * 1 * 4 * 2  # K and V, two layers, one KV head of 4, bf16
    least = 0.0
    for keys in (300, 700):
        least += max(kv * keys / 32e9, 2 * 4.0 * 2 * 4 * keys / 64e12)
    assert work.decode_attn_least_s(SHAPES, [100, 200], PEAK) == pytest.approx(kv * 300 / 32e9)
    assert pt.decode_attn_roofline(t, rec) == pytest.approx(100 * least / 5000e-9)
    t.ops["/device:TPU:0"][5].reads = ("convert.48",)  # fusion.2, no op_name, reads attention
    pt.inherit_scopes(t.ops["/device:TPU:0"])
    assert pt.decode_attn_roofline(t, rec) == pytest.approx(100 * least / 7300e-9)
    t.skew_ns = 30000.0  # every decode run now starts outside its step
    assert pt.decode_attn_roofline(t, rec) is None


def test_token_delivery_counts_the_window_only():
    deliveries = [(0.5, 9.0)] + [(1.0 + i / 100, i / 1000) for i in range(100)] + [(2.5, 9.0)]
    assert pt.token_delivery_p95_s(deliveries, 1.0, 2.0) == pytest.approx(0.09405)
    assert pt.token_delivery_p95_s([], 1.0, 2.0) is None


def four_devices(t, rec):
    """The same trace on four devices, each running the same ops, in a
    record of four chips."""
    for i in range(1, 4):
        t.ops[f"/device:TPU:{i}"] = [pt.Op(**o.__dict__) for o in t.ops["/device:TPU:0"]]
        t.runs[f"/device:TPU:{i}"] = list(t.runs["/device:TPU:0"])
    rec.chips = 4
    return t, rec


def test_decode_attn_roofline_at_four_devices():
    """Device time is averaged over the planes and the least time is over
    four chips' peaks: a quarter of the one-chip share."""
    t, rec = step_trace()
    one = pt.decode_attn_roofline(t, rec)
    assert pt.decode_attn_roofline(*four_devices(t, rec)) == pytest.approx(one / 4)
    assert pt.by_program(t, 0, 1e6) == pytest.approx(pt.by_program(*step_trace()[:1], 0, 1e6))


def test_readers_of_the_program_trace():
    t, rec = step_trace()
    rec.program = t
    read = lambda name: spec.metric_reader(name)(rec)
    assert read("decode_attn_roofline.offline") == pytest.approx(pt.decode_attn_roofline(t, rec))
    assert read("host_step_ms.offline") == pytest.approx(pt.host_step_ms(t, rec))
    rec.program = pt.ProgramTrace()  # a trace with no device plane and no spans
    assert read("decode_attn_roofline.offline") is None
    assert read("host_step_ms.offline") is None
    rec.program = None
    assert read("decode_attn_roofline.offline") is None and read("host_step_ms.offline") is None


def test_token_delivery_reader_pairs_each_receipt_with_its_delay():
    t, rec = step_trace()
    rec.t_open, rec.t_close = 1.0, 2.0
    a = RequestRecord(0.0, (2,), 3, token_t=[0.5, 1.2, 1.5], token_delay=[9.0, 0.01, 0.03])
    b = RequestRecord(0.0, (2,), 2, token_t=[1.7, 2.5], token_delay=[0.02, 9.0])
    rec.requests = [a, b]
    assert spec.metric_reader("token_delivery_p95_s")(rec) == pytest.approx(np.percentile([0.01, 0.03, 0.02], 95))
    rec.requests = []
    assert spec.metric_reader("token_delivery_p95_s")(rec) is None


def no_device_plane_allowed(monkeypatch):
    """A CPU trace has no TPU plane: reduce it to an idle device so the rest
    of a traced run can go on."""
    real = tr.reduce

    def reduce(ev, top=10):
        if ev.device_ops:
            return real(ev, top)
        lo, hi = tr.window_of(ev)
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9, "idle_share": 1.0, "devices": 0, "device_ops": [],
                "idle_gaps": [], "idle_by_span": {}, "window_ns": (lo, hi), "busy_union": {"/device:TPU:0": []}}

    monkeypatch.setattr(tr, "reduce", reduce)


@pytest.mark.parametrize(
    "workload, reads",
    [("olmo-1b.offline", "host_step_ms.offline"), ("olmo-1b.shared-prefix", "token_delivery_p95_s")],
)
def test_a_traced_run_keeps_the_program_trace_in_its_record(monkeypatch, workload, reads):
    """Traced on the CPU at a tiny size: the record carries the program's
    spans read from the trace before it was deleted, the per-layer metric
    read from them is in the result, and the one that needs a device plane
    is left out."""
    import time

    from bench import harness
    from bench.tests.test_bench_faults import CPU_PEAKS, TINY_LIMIT
    from bench.tests.tiny import tiny_cell

    no_device_plane_allowed(monkeypatch)
    cell = tiny_cell(workload, limit=TINY_LIMIT)
    if cell.mix["arrival"]["kind"] == "backlog":
        cell.mix["arrival"]["count"] = 200  # still decoding in the traced middle of the window
    out = harness.run(
        workload, seed=2**31 + 21, seconds=3.0, trace=True, t_start=time.perf_counter(),
        cell=cell, require_tpu=False, peaks_table=CPU_PEAKS, keep_record=True,
    )
    assert out["correct"], out["checks"]
    rec = out["_record"]
    assert isinstance(rec.program, pt.ProgramTrace)
    names = {s.name for s in rec.program.all_spans()}
    assert {"engine.step", "engine.emit"} <= names
    assert any(n == tr.WINDOW_SPAN for _, _, n in rec.program.host)
    assert all(len(r.token_delay) == len(r.token_t) for r in rec.requests)
    assert 0 < out["metrics"][reads]["value"] < 1e3
    assert "decode_attn_roofline.offline" not in out["metrics"]
