"""Median, over every request due in the window, of due time to first token."""

from bench.record import RunRecord, percentile, ttfts


def compute(rec: RunRecord):
    return percentile(ttfts(rec), 50)
