"""What one run leaves for the metric readers, and the arithmetic they share.

Every time here is the host's ``time.perf_counter()`` in seconds, except
``admit_wait`` and ``token_delay``, which are differences on the engine's own
clock (admission minus submission; a token's receipt minus the program's
stamp of its emission). The window is ``[t_open, t_close)``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from bench import work


@dataclass
class RequestRecord:
    due_t: float  # when the schedule said to send it
    prompt: tuple
    target: int  # output tokens asked for (EOS is unreachable)
    submit_t: Optional[float] = None  # when the client sent it
    token_t: list = field(default_factory=list)  # receipt time of each token
    token_delay: list = field(default_factory=list)  # each token's receipt after its emission stamp
    tokens: list = field(default_factory=list)
    finish: Optional[str] = None  # the stream's finish reason
    req_id: Optional[int] = None
    admit_wait: Optional[float] = None


@dataclass
class StepRecord:
    t0: float
    t1: float
    produced: int  # decode tokens of the step
    prefill_tokens: int  # prompt tokens the step computed
    decode_keys: Optional[list] = None  # keys each decode token attended over
    prefill_spans: Optional[list] = None  # (start, end) prompt positions per request
    logits_rows: int = 0  # output-head rows whose token was used


@dataclass
class RunRecord:
    seconds: float
    t_open: float
    t_close: float
    t_drained: float
    chips: int
    max_batch: int
    peak: dict
    shapes: work.Shapes
    setup_s: float
    requests: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    counters_open: dict = field(default_factory=dict)
    counters_close: dict = field(default_factory=dict)
    trace: Optional[dict] = None  # bench/trace_reduce.py's reduction of a traced run
    trace_span: Optional[tuple] = None  # host times at which the reduced span opened and closed
    program: Optional[object] = None  # bench/program_trace.py's ProgramTrace of a traced run


def percentile(values, pct: float) -> Optional[float]:
    """Linear-interpolated percentile over every sample; None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), pct))


def ttfts(rec: RunRecord) -> list:
    """From each request's due time to its first token; a request that never
    got one counts until the end of the drain (it missed)."""
    return [(r.token_t[0] if r.token_t else rec.t_drained) - r.due_t for r in rec.requests]


def token_gaps(rec: RunRecord) -> list:
    return [b - a for r in rec.requests for a, b in zip(r.token_t, r.token_t[1:])]


def window_steps(rec: RunRecord) -> list:
    return [s for s in rec.steps if rec.t_open <= s.t0 < rec.t_close]


def decode_only(steps: list) -> list:
    return [s for s in steps if s.produced > 0 and s.prefill_tokens == 0]


def step_flops(rec: RunRecord, s: StepRecord) -> float:
    f = sum(work.token_flops(rec.shapes, k) for k in s.decode_keys or ())
    f += sum(work.prefill_flops(rec.shapes, a, b) for a, b in s.prefill_spans or ())
    return f + s.logits_rows * work.logits_flops(rec.shapes)


def counter_delta(rec: RunRecord, name: str) -> float:
    return rec.counters_close.get(name, 0.0) - rec.counters_open.get(name, 0.0)
