import os
import sys

import pytest

# tests import the library from src/ (works with or without PYTHONPATH=src)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture
def steer_decode(monkeypatch):
    """``steer_decode(impl)``: with ``"pallas"`` the decode-attention choice
    sees a TPU, so paged decode runs the Pallas kernel (interpreted off the
    TPU, as ``utils.platform`` decides); ``"xla"`` leaves the platform's own
    choice, the jnp oracle on the CPU.  The engine tests' ``impl`` rows steer
    decode with it and pass ``attn_impl`` for the chunk path."""

    def steer(impl: str) -> None:
        if impl == "pallas":
            from repro.kernels import paged_attention_ops

            monkeypatch.setattr(paged_attention_ops, "on_tpu", lambda: True)

    return steer
