"""Jit'd public wrapper for the flash attention kernel.

Accepts the model's (B, S, H, hd) layout, transposes to the kernel's
head-major layout; interpret mode off-TPU comes from ``utils.platform``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "q_block", "kv_block"))
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd) — model layout
    k: jax.Array,  # (B, Skv, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_block: int = 128,
    kv_block: int = 128,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    Sq, Skv = qt.shape[2], kt.shape[2]
    qb = min(q_block, Sq) if Sq % min(q_block, Sq) == 0 else Sq
    kb = min(kv_block, Skv) if Skv % min(kv_block, Skv) == 0 else Skv
    out = flash_attention_bhsd(
        qt,
        kt,
        vt,
        causal=causal,
        window=window,
        softcap=softcap,
        q_block=qb,
        kv_block=kb,
    )
    return out.transpose(0, 2, 1, 3)
