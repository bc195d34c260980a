"""Least time of the decode attention of the traced span's decode-only steps
at the chips' peaks (``work.decode_attn_least_s``), over the device time of
the ops scoped ``attention`` in the ``jit_decode_step`` runs inside those
steps' ``engine.step`` spans, from the program's trace
(``program_trace.decode_attn_roofline``)."""

from bench import program_trace
from bench.record import RunRecord


def compute(rec: RunRecord):
    if rec.program is None or not rec.program.ops or rec.trace_span is None:
        return None
    return program_trace.decode_attn_roofline(rec.program, rec)
