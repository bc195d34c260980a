"""95th percentile of every gap between two tokens a client received in a row,
over all requests due in the window."""

from bench.record import RunRecord, percentile, token_gaps


def compute(rec: RunRecord):
    return percentile(token_gaps(rec), 95)
