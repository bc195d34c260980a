"""Process start to window open: imports, weights, engine, compiles or cache
loads, and the warm-up."""

from bench.record import RunRecord


def compute(rec: RunRecord):
    return rec.setup_s
