"""Least time of the decode-only steps inside the traced span at the chip's
peaks (every weight and the live K/V of the decoding slots over HBM
bandwidth, or their FLOPs over the bf16 peak, whichever is longer), over the
time the device was busy in those steps, from the profiler trace.

A step's device work lies between the end of the step before it and its own
end, since each step ends in a host fetch of its tokens; host times map onto
the trace's clock through the span's opening, which both record."""

from bench import work
from bench.record import RunRecord, step_flops
from bench.trace_reduce import busy_between


def compute(rec: RunRecord):
    if rec.trace is None or rec.trace_span is None:
        return None
    lo, hi = rec.trace_span
    shift = rec.trace["window_ns"][0] - lo * 1e9
    least = busy = 0.0
    for prev, s in zip(rec.steps, rec.steps[1:]):
        if s.produced == 0 or s.prefill_tokens or s.decode_keys is None or prev.t1 < lo or s.t1 > hi:
            continue
        by_bytes = work.decode_step_bytes(rec.shapes, s.decode_keys) / (rec.chips * rec.peak["hbm_bytes_per_s"])
        by_flops = step_flops(rec, s) / (rec.chips * rec.peak["bf16_flops_per_s"])
        least += max(by_bytes, by_flops)
        busy += busy_between(rec.trace, prev.t1 * 1e9 + shift, s.t1 * 1e9 + shift)
    return 100.0 * least / busy if busy > 0 else None
