"""Pallas TPU kernels for the perf-critical compute hot spots.

Each kernel ships as a triple: ``<name>.py`` (pl.pallas_call + BlockSpec),
``<name>_ops.py`` (jit'd public wrapper) and ``<name>_ref.py`` (pure-jnp
oracle used by the allclose test sweeps).  TPU is the target; on any other
backend the kernels run in interpret mode, decided in one place
(``utils.platform.resolve_interpret``).
"""

from repro.fp8.gemm import fp8_gemm
from repro.kernels import flash_attention_ops, paged_attention_ops
from repro.kernels.babelstream import (
    stream_add,
    stream_bytes,
    stream_copy,
    stream_dot,
    stream_mul,
    stream_triad,
)
from repro.kernels.flash_attention_ops import flash_attention
from repro.kernels.paged_attention_ops import (
    paged_attention,
    paged_prefill_attention,
    paged_prefill_attention_quantized,
)
from repro.kernels.rwkv6_scan_ops import wkv6

__all__ = [
    "flash_attention",
    "flash_attention_ops",
    "fp8_gemm",
    "paged_attention",
    "paged_attention_ops",
    "paged_prefill_attention",
    "paged_prefill_attention_quantized",
    "stream_add",
    "stream_bytes",
    "stream_copy",
    "stream_dot",
    "stream_mul",
    "stream_triad",
    "wkv6",
]
