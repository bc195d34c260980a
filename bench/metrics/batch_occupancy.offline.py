"""Mean decoding slots per decode step over ``max_batch``, in the window."""

from bench.record import RunRecord, window_steps


def compute(rec: RunRecord):
    dec = [s.produced for s in window_steps(rec) if s.produced > 0]
    if not dec:
        return None
    return 100.0 * sum(dec) / len(dec) / rec.max_batch
