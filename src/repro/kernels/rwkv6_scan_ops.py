"""Jit'd wrapper: model-layout (B, S, H, n) -> kernel layout (B*H, S, n)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_scan import wkv6_chunked


@partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, logw, u, *, chunk: int = 64):
    """r,k,v,logw: (B, S, H, n); u: (H, n). Returns (B, S, H, n) fp32."""
    B, S, H, n = r.shape

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, n)

    ub = jnp.broadcast_to(u[None], (B, H, n)).reshape(B * H, n)
    out = wkv6_chunked(to_bh(r), to_bh(k), to_bh(v), to_bh(logw), ub, chunk=chunk)
    return out.reshape(B, H, S, n).transpose(0, 2, 1, 3)
