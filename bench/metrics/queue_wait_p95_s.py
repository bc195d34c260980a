"""95th percentile of the engine's admission minus submission time (its own
clock), over the requests due in the window that were admitted."""

from bench.record import RunRecord, percentile


def compute(rec: RunRecord):
    return percentile([r.admit_wait for r in rec.requests if r.admit_wait is not None], 95)
