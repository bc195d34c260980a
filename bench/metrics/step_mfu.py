"""Model FLOPs of every token the window's steps computed, over their summed
wall time, over chips times the bf16 peak."""

from bench.record import RunRecord, step_flops, window_steps


def compute(rec: RunRecord):
    steps = [s for s in window_steps(rec) if s.decode_keys is not None]
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    flops = sum(step_flops(rec, s) for s in steps)
    return 100.0 * flops / (wall * rec.chips * rec.peak["bf16_flops_per_s"])
