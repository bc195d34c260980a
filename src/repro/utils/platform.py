"""Where the Pallas kernels run: compiled for a TPU, interpreted elsewhere.

This is the one place that probes the platform (``on_tpu``): kernel entry
points take ``interpret=None`` and resolve it here, and the serving path's
decode-attention choice (``kernels.paged_attention_ops.decode_uses_kernel``)
reads ``on_tpu``.  An explicit ``interpret=False`` is for compiling against
a described chip from a CPU process (``tests/test_tpu_compile.py``), where
the backend still reports the CPU.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether the default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else interpret mode on every backend but the TPU."""
    if interpret is not None:
        return interpret
    return not on_tpu()
