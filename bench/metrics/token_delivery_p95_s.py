"""95th percentile, over the tokens received in the window, of each token's
receipt by the client after the program's stamp of its emission
(``StreamEvent.t``, read at the start of ``engine.emit``)."""

from bench import program_trace
from bench.record import RunRecord


def compute(rec: RunRecord):
    pairs = [(t, d) for r in rec.requests for t, d in zip(r.token_t, r.token_delay)]
    return program_trace.token_delivery_p95_s(pairs, rec.t_open, rec.t_close)
