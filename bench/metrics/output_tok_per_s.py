"""Output tokens the clients received inside the window, over its seconds."""

from bench.record import RunRecord


def compute(rec: RunRecord):
    n = sum(1 for r in rec.requests for t in r.token_t if rec.t_open <= t < rec.t_close)
    return n / rec.seconds
