"""Mean host time of the traced span's decode-only ``engine.step`` spans,
less their ``engine.host_fetch`` children: what a step spends on the host
besides waiting for the device (``program_trace.host_step_ms``)."""

from bench import program_trace
from bench.record import RunRecord


def compute(rec: RunRecord):
    if rec.program is None or rec.trace_span is None:
        return None
    return program_trace.host_step_ms(rec.program, rec)
