"""Pure-jnp oracle for paged-attention decode.

Gathers the K/V blocks addressed by each sequence's block table into a
contiguous (B, nb*bs, KV, hd) view and runs exact fp32 softmax attention for
the single query token.  This is the allclose reference for the Pallas
kernel, and the serving engine's decode attention wherever the kernel does
not run (``paged_attention_ops.decode_uses_kernel``): on the CPU, for
int8/fp8 pools and for head counts the mesh can't split.  It works over the
whole table every call: on a TPU the gather and its float32 upcast of every
table block, dead ones included, cost far more than the kernel, which reads
only the live blocks.

Optionally consumes int8 block pools with per-(token, head) fp32 scales (the
``serving.kvquant`` KIVI layout) — dequantization happens after the gather.

Block-table contract (shared with the Pallas kernels): entries past a
sequence's last live block — inactive slots, mid-prefill slots, positions
beyond ``seq_lens`` — point at the reserved null block (id 0).  They are
gathered like any other block and then fully masked by the position
compare, so the null block's contents never influence an output.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def gather_blocks(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """pool: (N, bs, ...) + tables (B, nb) -> (B, nb*bs, ...) logical view."""
    B, nb = block_tables.shape
    bs = pool.shape[1]
    g = pool[block_tables]  # (B, nb, bs, ...)
    return g.reshape((B, nb * bs) + pool.shape[2:])


def paged_attention_ref(
    q: jax.Array,  # (B, H, hd) current-token queries
    k_pool: jax.Array,  # (N, bs, KV, hd) global block pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32 physical block ids (0 = null)
    seq_lens: jax.Array,  # (B,) int32 valid kv length (incl. current token)
    *,
    softcap: float = 0.0,
    window: int = 0,
    k_scale: jax.Array | None = None,  # (N, bs, KV, 1) fp32 (int8 pools)
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Returns (B, H, hd) attention output in q.dtype."""
    B, H, hd = q.shape
    KV = k_pool.shape[2]
    qpk = H // KV
    scale = 1.0 / math.sqrt(hd)

    k = gather_blocks(k_pool, block_tables).astype(jnp.float32)  # (B, S, KV, hd)
    v = gather_blocks(v_pool, block_tables).astype(jnp.float32)
    if k_scale is not None:
        k = k * gather_blocks(k_scale, block_tables)
    if v_scale is not None:
        v = v * gather_blocks(v_scale, block_tables)
    S = k.shape[1]

    qg = q.astype(jnp.float32).reshape(B, KV, qpk, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k) * scale  # (B, KV, qpk, S)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    kv_pos = jnp.arange(S, dtype=jnp.int32)[None, :]  # logical positions
    q_pos = (seq_lens - 1)[:, None]
    ok = kv_pos < seq_lens[:, None]  # causal: everything written so far
    if window > 0:
        ok &= (q_pos - kv_pos) < window
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)

    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgs,bskd->bkgd", w, v)
    return ctx.reshape(B, H, hd).astype(q.dtype)


def paged_prefill_attention_ref(
    q: jax.Array,  # (B, C, H, hd) chunk queries
    k_pool: jax.Array,  # (N, bs, KV, hd) global block pool (chunk K/V written)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32 physical block ids (0 = null)
    start: jax.Array,  # (B,) int32 absolute position of the chunk's first token
    *,
    softcap: float = 0.0,
    window: int = 0,
    k_scale: jax.Array | None = None,  # (N, bs, KV, 1) fp32 (int8 pools)
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Chunked-prefill oracle: C query tokens per sequence at absolute
    positions ``start + [0, C)`` attend causally (+ window) over the gathered
    paged view — the multi-query-token twin of ``paged_attention_ref``.
    Returns (B, C, H, hd) in q.dtype."""
    B, C, H, hd = q.shape
    KV = k_pool.shape[2]
    qpk = H // KV
    scale = 1.0 / math.sqrt(hd)

    k = gather_blocks(k_pool, block_tables).astype(jnp.float32)  # (B, S, KV, hd)
    v = gather_blocks(v_pool, block_tables).astype(jnp.float32)
    if k_scale is not None:
        k = k * gather_blocks(k_scale, block_tables)
    if v_scale is not None:
        v = v * gather_blocks(v_scale, block_tables)
    S = k.shape[1]

    qg = q.astype(jnp.float32).reshape(B, C, KV, qpk, hd)
    s = jnp.einsum("bckgd,bskd->bkcgs", qg, k) * scale  # (B, KV, C, qpk, S)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (B, C)
    kv_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]  # logical positions
    ok = kv_pos <= q_pos[:, :, None]  # causal: chunk K/V is already written
    if window > 0:
        ok &= (q_pos[:, :, None] - kv_pos) < window
    s = jnp.where(ok[:, None, :, None, :], s, NEG_INF)

    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkcgs,bskd->bckgd", w, v)
    return ctx.reshape(B, C, H, hd).astype(q.dtype)
