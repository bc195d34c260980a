"""Where the Pallas kernels run: compiled for a TPU, interpreted elsewhere.

This is the one place that decides.  Kernel entry points take
``interpret=None`` and resolve it here; an explicit ``False`` is for
compiling against a described chip from a CPU process
(``tests/test_tpu_compile.py``), where the backend still reports the CPU.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else interpret mode on every backend but the TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
