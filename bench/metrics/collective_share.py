"""Device time of the collective ops (all-reduce, all-gather, reduce-scatter,
collective-permute, by HLO op name) in ``jit_decode_step`` over that
program's device time, inside the traced window, averaged over devices, in
%: what tensor parallelism's exchanges cost the decode step. Leaf ops from
the program's trace (``RunRecord.program``)."""

from bench import program_trace
from bench.record import RunRecord

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute")


def is_collective(op_name: str) -> bool:
    """``all-reduce.3``, ``all-gather-start.1``, ``collective-permute-done``:
    an HLO op of one of the ``COLLECTIVES`` (its async halves included)."""
    return op_name.startswith(COLLECTIVES)


def collective_share(pt, lo: float, hi: float, program: str = program_trace.DECODE_PROGRAM):
    """Per device, the collective leaf ops' time in ``program`` inside
    ``[lo, hi)`` over all its leaf ops' time there; the mean over devices
    with any, in %. None where the program ran no op in the window."""
    shares = []
    for ops in pt.ops.values():
        total = coll = 0.0
        for o in ops:
            if o.leaf and o.program == program and o.end > lo and o.start < hi:
                t = min(o.end, hi) - max(o.start, lo)
                total += t
                if is_collective(o.name):
                    coll += t
        if total > 0:
            shares.append(coll / total)
    return 100.0 * sum(shares) / len(shares) if shares else None


def compute(rec: RunRecord):
    if rec.program is None or not rec.program.ops:
        return None
    return collective_share(rec.program, *program_trace.window(rec.program))
