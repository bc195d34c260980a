"""Prompt tokens served from the prefix cache over all prompt tokens admitted
in the window (engine counters)."""

from bench.record import RunRecord, counter_delta


def compute(rec: RunRecord):
    hit = counter_delta(rec, "engine_prefix_hit_tokens_total")
    total = hit + counter_delta(rec, "engine_prefill_tokens_total")
    return 100.0 * hit / total if total > 0 else None
