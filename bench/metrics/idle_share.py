"""1 - the device's busy union over the traced window (bench/trace_reduce.py)."""


def compute(rec):
    return None if rec.trace is None else 100.0 * rec.trace["idle_share"]
