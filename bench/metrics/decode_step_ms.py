"""Mean wall time of the window's engine steps that decoded and ran no
prefill chunk."""

from bench.record import RunRecord, decode_only, window_steps


def compute(rec: RunRecord):
    steps = decode_only(window_steps(rec))
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
