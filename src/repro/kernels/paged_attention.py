"""Paged attention — Pallas TPU kernels (block-table gather, online softmax).

vLLM-style attention over a paged KV cache: each sequence's K/V lives in
non-contiguous fixed-size blocks of a global pool, addressed through a
per-sequence block table.  The kernels never materialize the gathered
(B, S, KV, hd) view — the block table is a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index_map dereferences it
to DMA exactly the physical block each grid step needs:

    grid = (batch, logical_block)
    k/v spec: (1, block_size, KV, hd) @ (table[b, i], 0, 0, 0)

Each grid step reads one whole pool block, every KV head of it, and loops
the heads in-kernel.  The block's last two dims equal the pool's, which is
what the TPU tiling rule asks of a block that takes all of ``(KV, hd)``; a
one-head ``(1, bs, 1, hd)`` block is refused, and viewing the pool as
``(N, bs, KV*hd)`` to take a lane slice costs a relayout copy of the whole
pool per call.  One DMA of ``bs * KV * hd`` elements per step also keeps
the grid ``KV`` times shorter than a per-head walk.

The minormost grid dimension walks a sequence's logical blocks and *revisits*
the output block, carrying the running max / denominator / fp32 accumulator
in VMEM scratch between steps — the same grid-order online-softmax
formulation as ``kernels/flash_attention.py``.

Two entry points share that structure:

* ``paged_attention_bhd``     — decode: one query token per sequence.
* ``paged_prefill_attention_bhd`` — **chunked prefill**: ``C`` query tokens
  per sequence at absolute positions ``start + [0, C)``, attending causally
  over everything already written to the paged cache (shared prefix blocks,
  earlier chunks, and this chunk's own K/V — which the caller scatters in
  *before* calling).  Queries are laid out (B, KV, C*qpk, hd) with row
  ``r -> chunk offset r // qpk``, so the in-kernel causal/window mask is a
  per-row position compare.  This is what lets a long prompt be processed in
  budgeted chunks interleaved with decode steps instead of one blocking
  batch=1 prefill.

Tile notes: query/output tiles are ``(1, KV, rows, hd)``; prefill splits
its ``C*qpk`` rows into tiles of a multiple of 8 rows when all of them
would overflow the VMEM budget (``_row_tile``).  ``tests/test_tpu_compile.py``
compiles both kernels for a described v5e at real widths; interpret mode
accepts the smoke sizes.  Sequences
shorter than ``nb * block_size`` are handled by masking against ``seq_lens``;
table entries past a sequence's last block must point at a valid (e.g. null)
block — they are DMA'd and fully masked.  ``seq_lens`` must be >= 1 so the
first logical block always contributes a finite row-max.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.platform import resolve_interpret

NEG_INF = -1.0e38
# VMEM for a prefill row tile's query + output buffers (double-buffered),
# accumulator and lane-padded max / denominator: under the 16 MiB scoped
# default with room for the K/V blocks and temporaries
ROW_TILE_VMEM_BYTES = 12 * 2**20


def _init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _attend_head(h, q, k, v, ok, acc_ref, m_ref, l_ref, *, scale, softcap):
    """One online-softmax step of head ``h``: rows ``q`` (r, hd) against one
    block's ``k``/``v`` (bs, hd) under the (r, bs) mask ``ok``."""
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (r, bs)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[h][:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[h] = (alpha * l_ref[h][:, 0] + jnp.sum(p, axis=1))[:, None]
    acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot(p, v)
    m_ref[h] = m_cur[:, None]


def _finish(o_ref, acc_ref, l_ref):
    denom = jnp.maximum(l_ref[...], 1e-30)  # (KV, r, 1)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _scratch(KV: int, rows: int, hd: int):
    return [
        pltpu.VMEM((KV, rows, hd), jnp.float32),  # accumulator
        pltpu.VMEM((KV, rows, 1), jnp.float32),  # running max
        pltpu.VMEM((KV, rows, 1), jnp.float32),  # running denominator
    ]


def _paged_kernel(
    tbl_ref,  # scalar-prefetch (B, nb) int32
    len_ref,  # scalar-prefetch (B,) int32
    q_ref,  # (1, KV, qpk, hd)
    k_ref,  # (1, bs, KV, hd) — physical block picked by the index_map
    v_ref,
    o_ref,  # (1, KV, qpk, hd), revisited across the block dimension
    acc_ref,  # VMEM (KV, qpk, hd) fp32
    m_ref,  # VMEM (KV, qpk, 1) fp32
    l_ref,  # VMEM (KV, qpk, 1) fp32
    *,
    scale: float,
    softcap: float,
    window: int,
    block_size: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    _, KV, qpk, _ = q_ref.shape

    @pl.when(i == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    seq_len = len_ref[b]
    kv_pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, (qpk, block_size), 1)
    ok = kv_pos < seq_len  # causal over everything written so far
    if window > 0:
        ok &= (seq_len - 1 - kv_pos) < window
    for h in range(KV):
        _attend_head(
            h, q_ref[0, h], k_ref[0, :, h, :], v_ref[0, :, h, :], ok,
            acc_ref, m_ref, l_ref, scale=scale, softcap=softcap,
        )

    @pl.when(i == nb - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def paged_attention_bhd(
    q: jax.Array,  # (B, H, hd) current-token queries
    k_pool: jax.Array,  # (N, bs, KV, hd) global block pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32 physical block ids
    seq_lens: jax.Array,  # (B,) int32 valid kv length (>= 1)
    *,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert H % KV == 0, (H, KV)
    qpk = H // KV
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, KV, qpk, hd)
    kernel = functools.partial(
        _paged_kernel,
        scale=scale,
        softcap=softcap,
        window=window,
        block_size=bs,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, KV, qpk, hd), lambda b, i, tbl, sl: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, i, tbl, sl: (tbl[b, i], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, i, tbl, sl: (tbl[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, qpk, hd), lambda b, i, tbl, sl: (b, 0, 0, 0)),
        scratch_shapes=_scratch(KV, qpk, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, qpk, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, H, hd)


def _paged_prefill_kernel(
    tbl_ref,  # scalar-prefetch (B, nb) int32
    start_ref,  # scalar-prefetch (B,) int32 — absolute position of chunk row 0
    q_ref,  # (1, KV, rt, hd) — row tile of the (C*qpk) query rows
    k_ref,  # (1, bs, KV, hd) — physical block picked by the index_map
    v_ref,
    o_ref,  # (1, KV, rt, hd), revisited across the block dimension
    acc_ref,  # VMEM (KV, rt, hd) fp32
    m_ref,  # VMEM (KV, rt, 1) fp32
    l_ref,  # VMEM (KV, rt, 1) fp32
    *,
    scale: float,
    softcap: float,
    window: int,
    block_size: int,
    qpk: int,
):
    b = pl.program_id(0)
    t = pl.program_id(1)  # query-row tile (nt == 1 when untiled)
    i = pl.program_id(2)
    nb = pl.num_programs(2)
    _, KV, rt, _ = q_ref.shape

    @pl.when(i == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    # global row r = t*rt + local row; row r is chunk offset r // qpk
    q_pos = start_ref[b] + (t * rt + jax.lax.broadcasted_iota(jnp.int32, (rt, block_size), 0)) // qpk
    kv_pos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, (rt, block_size), 1)
    ok = kv_pos <= q_pos  # causal: the chunk's own K/V is already written
    if window > 0:
        ok &= (q_pos - kv_pos) < window
    for h in range(KV):
        _attend_head(
            h, q_ref[0, h], k_ref[0, :, h, :], v_ref[0, :, h, :], ok,
            acc_ref, m_ref, l_ref, scale=scale, softcap=softcap,
        )

    @pl.when(i == nb - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def _row_tile(rows: int, KV: int, hd: int, itemsize: int, rows_per_tile: int) -> int:
    """Rows per prefill tile: ``rows_per_tile`` when it divides ``rows``;
    otherwise all rows if they fit ``ROW_TILE_VMEM_BYTES``, else the largest
    multiple-of-8 divisor of ``rows`` that does."""
    if rows_per_tile > 0 and rows % rows_per_tile == 0:
        return rows_per_tile
    # q + out double-buffered, fp32 accumulator, max / denominator padded to 128 lanes
    per_row = KV * (4 * hd * itemsize + 4 * hd + 2 * 4 * 128)
    fits = lambda rt: rt * per_row <= ROW_TILE_VMEM_BYTES
    if fits(rows):
        return rows
    return max(
        (rt for rt in range(8, rows, 8) if rows % rt == 0 and fits(rt)),
        default=rows,
    )


def paged_prefill_attention_bhd(
    q: jax.Array,  # (B, C, H, hd) chunk queries
    k_pool: jax.Array,  # (N, bs, KV, hd) global block pool (chunk K/V written)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32 physical block ids
    start: jax.Array,  # (B,) int32 absolute position of the chunk's first token
    *,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool | None = None,
    rows_per_tile: int = 0,
) -> jax.Array:
    """Chunked-prefill attention: every chunk token attends causally over the
    paged logical view [0, start + its offset].  Table entries past the last
    written block must point at a valid (e.g. null) block — they are DMA'd
    and fully masked by the causal compare.  Returns (B, C, H, hd).

    ``rows_per_tile`` (autotuned, ``kernels.autotune``): tile the C*qpk
    query-row dimension so each grid step streams a ``(KV, rows_per_tile,
    hd)`` query block against one K/V page — smaller VMEM scratch at the cost
    of re-reading pages once per tile.  Rows are independent queries, so any
    divisor of the row count is numerically identical; 0 (or a non-divisor)
    means every row in one tile when that fits ``ROW_TILE_VMEM_BYTES``, else
    the largest multiple-of-8 divisor that does (``_row_tile``).
    """
    B, C, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert H % KV == 0, (H, KV)
    qpk = H // KV
    rows = C * qpk
    rt = _row_tile(rows, KV, hd, jnp.dtype(q.dtype).itemsize, rows_per_tile)
    nt = rows // rt
    scale = 1.0 / math.sqrt(hd)

    # (B, C, H, hd) -> (B, KV, C*qpk, hd), row r = (chunk offset r//qpk, group r%qpk)
    qg = q.reshape(B, C, KV, qpk, hd).transpose(0, 2, 1, 3, 4).reshape(B, KV, rows, hd)
    kernel = functools.partial(
        _paged_prefill_kernel,
        scale=scale,
        softcap=softcap,
        window=window,
        block_size=bs,
        qpk=qpk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # nb innermost: for a fixed (b, t) the online-softmax scratch walks
        # every page before the next row tile re-initializes it
        grid=(B, nt, nb),
        in_specs=[
            pl.BlockSpec((1, KV, rt, hd), lambda b, t, i, tbl, st: (b, 0, t, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, t, i, tbl, st: (tbl[b, i], 0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, t, i, tbl, st: (tbl[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, rt, hd), lambda b, t, i, tbl, st: (b, 0, t, 0)),
        scratch_shapes=_scratch(KV, rt, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), start.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, KV, C, qpk, hd).transpose(0, 2, 1, 3, 4).reshape(B, C, H, hd)
