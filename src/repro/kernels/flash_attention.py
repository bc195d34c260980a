"""Flash attention — Pallas TPU kernel (online softmax, VMEM-tiled).

TPU adaptation of FlashAttention-2 (arXiv:2307.08691): the CUDA version's
shared-memory tiles + warp scheduling become VMEM blocks + a 4-D Pallas grid
``(batch, q_head, q_blocks, kv_blocks)`` whose minormost (kv) dimension
*revisits* the output block, carrying the running max / denominator /
accumulator in VMEM scratch between kv steps — the idiomatic TPU formulation
(grid-order accumulation instead of a thread-block inner loop).

Block sizes default to 128x128: MXU-aligned (128 lanes) and small enough
that q/k/v/acc tiles fit VMEM at head_dim <= 256 (gemma-7b's 256 included).
Supports causal masking, sliding windows (mistral/hymba), logit soft-cap
(gemma) and GQA head grouping — the feature set the ten assigned archs need.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.platform import resolve_interpret

NEG_INF = -1.0e38


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    q_block: int,
    kv_block: int,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (q_block, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (kv_block, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (q_blk, kv_blk)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kv_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = kv_pos < kv_len
    if causal:
        ok &= q_pos >= kv_pos
    if window > 0:
        ok &= (q_pos - kv_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[:, 0]  # (q_block,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_cur = alpha * l_ref[:, 0] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p.astype(v.dtype), v).astype(jnp.float32)
    m_ref[...] = m_cur[:, None]
    l_ref[...] = l_cur[:, None]

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def pl_scratch(shape, dtype):
    """VMEM scratch allocation (TPU target; interpret mode emulates it)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, KV, Skv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0, (H, KV)
    qpk = H // KV
    assert Sq % q_block == 0 and Skv % kv_block == 0, (Sq, Skv, q_block, kv_block)
    scale = 1.0 / math.sqrt(hd)
    grid = (B, H, Sq // q_block, Skv // kv_block)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        q_block=q_block,
        kv_block=kv_block,
        kv_len=Skv,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, hd), lambda b, h, qi, ki, _qpk=qpk: (b, h // _qpk, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, hd), lambda b, h, qi, ki, _qpk=qpk: (b, h // _qpk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pl_scratch((q_block, hd), jnp.float32),
            pl_scratch((q_block, 1), jnp.float32),
            pl_scratch((q_block, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
