"""Pieces that the plain reference forwards in ``bench/references/`` share.

Written from the published architectures, not from the program: it imports
nothing of the program. Every matmul runs in float32 at ``HIGHEST``
precision; ``quant="fp8"`` rounds each matmul input (weights per output
channel, activations per token) to float8 e4m3 with a scale to its amax,
which is the control a configuration served in bf16 has to fail.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
NEG = -1e30


def fake_fp8(x: jax.Array, axis) -> jax.Array:
    """Round to e4m3 with one scale per slice along ``axis`` (reduced)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec: str, x, w, quant, w_axis):
    """einsum in float32; ``quant`` rounds x per token (last axis) and w per
    output channel (``w_axis`` = its contracted axes)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = fake_fp8(x, -1)
        w = fake_fp8(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def rope(x, theta: float):
    """x: (B, S, heads, hd) at positions 0..S-1, rotate-half convention."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """Grouped-query causal softmax attention over positions 0..S-1: query
    head ``h`` reads K/V head ``h // (H / KV)``. q (B, S, H, hd), k and v
    (B, S, KV, hd); returns (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST).reshape(B, S, H, hd)
