"""Paged attention — Pallas TPU kernels (block-table gather, online softmax).

vLLM-style attention over a paged KV cache: each sequence's K/V lives in
non-contiguous fixed-size blocks of a global pool, addressed through a
per-sequence block table.  The kernels never materialize the gathered
(B, S, KV, hd) view — the block table is a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec``) that picks the physical blocks to DMA.

* ``paged_attention_bhd`` — **decode**: one query token per sequence, one
  grid step per sequence.  The pool stays in HBM (``pl.ANY``); the kernel
  copies only the sequence's live blocks, ``ceil(len / bs)`` of them, into
  VMEM in groups of ``BLOCKS_PER_STEP``, double-buffered: the next group,
  or the next sequence's first, is in flight while one is attended, so a
  sequence boundary costs no DMA wait.  Blocks are attended two at a time
  in their pool dtype's ``(bs, KV, hd)`` layout with every head at once — the
  scores are ``sum_d q[kv, d] * k[t, kv, d]``, a broadcast multiply and a
  lane reduction, and ``p @ v`` the same broadcast summed over tokens —
  accumulated in f32 with an online softmax.  A GQA group's ``qpk`` query
  rows take turns at that broadcast.
* ``paged_prefill_attention_bhd`` — **chunked prefill**: ``C`` query tokens
  per sequence at absolute positions ``start + [0, C)``, attending causally
  over everything already written to the paged cache (shared prefix blocks,
  earlier chunks, and this chunk's own K/V — which the caller scatters in
  *before* calling).  Queries are laid out (B, KV, C*qpk, hd) with row
  ``r -> chunk offset r // qpk``, so the in-kernel causal/window mask is a
  per-row position compare.  This is what lets a long prompt be processed in
  budgeted chunks interleaved with decode steps instead of one blocking
  batch=1 prefill.  Its grid is ``(batch, row tile, logical_block)``: the
  K/V BlockSpec index_map dereferences the table to DMA one whole pool
  block, every KV head of it, per step, and the heads loop in-kernel; the
  minormost dimension *revisits* the output block, carrying the running
  max / denominator / fp32 accumulator in VMEM scratch between steps — the
  same grid-order online-softmax formulation as
  ``kernels/flash_attention.py``.  The block's last two dims equal the
  pool's, which is what the TPU tiling rule asks of a block that takes all
  of ``(KV, hd)``; a one-head ``(1, bs, 1, hd)`` block is refused, and
  viewing the pool as ``(N, bs, KV*hd)`` to take a lane slice costs a
  relayout copy of the whole pool per call.

Tile notes: prefill query/output tiles are ``(1, KV, rows, hd)``, and
prefill splits its ``C*qpk`` rows into tiles of a multiple of 8 rows when
all of them would overflow the VMEM budget (``_row_tile``).
``tests/test_tpu_compile.py`` compiles both kernels for a described v5e at
real widths; interpret mode accepts the smoke sizes.  Sequences shorter
than ``nb * block_size`` are handled by masking against the length.  In
prefill, table entries past a sequence's last block must point at a valid
(e.g. null) block — they are DMA'd and fully masked; decode never reads
them.  Lengths (``seq_lens``, ``start + 1``) must be >= 1 so the first
logical block always contributes a finite row-max.
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
# decode: pool blocks per DMA group, the VMEM its double-buffered K and V
# groups may take, and the blocks one online-softmax step attends (two
# shorten the serial max / rescale chain per token; measured on a v5e)
BLOCKS_PER_STEP = 16
DECODE_VMEM_BYTES = 8 * 2**20
BLOCKS_PER_ATTEND = 2


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


def live_blocks(seq_len, bs: int, window: int):
    """(first live block, live block count) of a sequence of ``seq_len``
    tokens: blocks ``[0, ceil(seq_len / bs))``, from the window's first block
    on under a sliding window.  The decode kernel's rule for the blocks it
    reads; it takes a traced length in the kernel and numpy lengths on the
    host (the serving engine's blocks-read counter)."""
    first = (seq_len - window).clip(0) // bs if window > 0 else 0
    return first, (seq_len + bs - 1) // bs - first


def _decode_kernel(
    len_ref,  # scalar-prefetch (B,) int32
    tbl_ref,  # scalar-prefetch (B, nb) int32
    q_ref,  # (qpk, KV, hd) slot b's queries
    k_hbm,  # (N, bs, KV, hd) pool, left where it lies (ANY)
    v_hbm,
    o_ref,  # (qpk, KV, hd)
    k_buf,  # VMEM (2, ppb, bs, KV, hd) pool dtype: two groups of blocks
    v_buf,
    sems,  # DMA semaphores (2, 2): [K or V, buffer]
    buf_ref,  # SMEM (1,) int32: the buffer that holds the next group to use
    *,
    scale: float,
    softcap: float,
    window: int,
    ppb: int,
):
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    bs = k_buf.shape[2]
    qpk, KV, hd = q_ref.shape
    wide = min(BLOCKS_PER_ATTEND, ppb)

    def copy_group(s, g, buf, start: bool):
        """Start (or wait for) the DMAs of slot ``s``'s ``g``-th group of
        live blocks into buffer ``buf``; blocks past the live ones get none."""
        first, n = live_blocks(len_ref[s], bs, window)
        for j in range(ppb):

            @pl.when(g * ppb + j < n)
            def _():
                blk = tbl_ref[s, first + g * ppb + j]
                for kv, (pool, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(pool.at[blk], dst.at[buf, j], sems.at[kv, buf])
                    if start:
                        cp.start()
                    else:
                        cp.wait()

    @pl.when(b == 0)
    def _first():
        buf_ref[0] = 0
        copy_group(0, 0, 0, True)

    seq_len = len_ref[b]
    first, n = live_blocks(seq_len, bs, window)
    groups = (n + ppb - 1) // ppb
    q = q_ref[...].astype(jnp.float32) * scale

    def attend(buf, blk0, u):
        """One online-softmax step over ``u`` live blocks of buffer ``buf``
        from its block ``j``; the running (max, denominator, accumulator)
        is the loop's carry."""

        def body(j, state):
            m, l, acc = state
            k = k_buf[buf, pl.ds(j, u)].astype(jnp.float32).reshape(u * bs, KV, hd)
            v = v_buf[buf, pl.ds(j, u)].astype(jnp.float32).reshape(u * bs, KV, hd)
            pos = (blk0 + j) * bs + jax.lax.broadcasted_iota(jnp.int32, (u * bs, KV, 1), 0)
            ok = pos < seq_len
            if window > 0:
                ok &= (seq_len - 1 - pos) < window
            ms, ls, accs = [], [], []
            for g in range(qpk):
                # every head of the blocks in one broadcast: (t, KV, hd) x (KV, hd)
                s = jnp.sum(k * q[g][None], axis=-1, keepdims=True)  # (t, KV, 1)
                if softcap > 0:
                    s = softcap * jnp.tanh(s / softcap)
                s = jnp.where(ok, s, NEG_INF)
                m_cur = jnp.maximum(m[g], jnp.max(s, axis=0))  # (KV, 1)
                alpha = jnp.exp(m[g] - m_cur)
                p = jnp.exp(s - m_cur[None])
                ms.append(m_cur)
                ls.append(alpha * l[g] + jnp.sum(p, axis=0))
                accs.append(alpha * acc[g] + jnp.sum(p * v, axis=0))
            return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)

        return body

    def group(g, state):
        buf = buf_ref[0]
        nxt = 1 - buf

        @pl.when(g + 1 < groups)
        def _():
            copy_group(b, g + 1, nxt, True)

        @pl.when((g + 1 == groups) & (b + 1 < slots))
        def _():
            copy_group(b + 1, 0, nxt, True)

        copy_group(b, g, buf, False)
        live_here = jnp.minimum(ppb, n - g * ppb)
        blk0 = first + g * ppb
        # ``wide`` blocks a step, then the odd ones out one at a time
        paired = live_here // wide * wide
        state = jax.lax.fori_loop(
            0, live_here // wide, lambda i, st: attend(buf, blk0, wide)(i * wide, st), state
        )
        if wide > 1:
            state = jax.lax.fori_loop(paired, live_here, attend(buf, blk0, 1), state)
        buf_ref[0] = nxt
        return state

    state = (
        jnp.full((qpk, KV, 1), NEG_INF, jnp.float32),
        jnp.zeros((qpk, KV, 1), jnp.float32),
        jnp.zeros((qpk, KV, hd), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, groups, group, state)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _blocks_per_step(nb: int, bs: int, KV: int, hd: int, dtype) -> int:
    """Blocks one DMA group carries: ``BLOCKS_PER_STEP``, at most the table
    width, and few enough that the K and V buffers (two groups each, one
    block padded to the dtype's (sublane, lane) tile) fit ``DECODE_VMEM_BYTES``."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    block = bs * (-(-KV // sublanes) * sublanes) * (-(-hd // 128) * 128) * itemsize
    return max(1, min(BLOCKS_PER_STEP, nb, DECODE_VMEM_BYTES // (4 * block)))


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
    """Decode attention: one query token per sequence over its live blocks.

    One grid step per sequence.  The pool stays in HBM; the kernel copies
    the sequence's live blocks (``[0, ceil(len / bs))``, and from the
    window's first block on under a sliding window) into VMEM in groups of
    ``BLOCKS_PER_STEP``, double-buffered, the next group (or the next
    sequence's first) in flight while one is attended.  Table entries past
    the live blocks are never read, and no DMA is issued for them.  The
    blocks are attended ``BLOCKS_PER_ATTEND`` at a time in their pool
    dtype's ``(bs, KV, hd)`` layout, all heads at once:
    ``sum_d q[kv, d] * k[t, kv, d]`` and the same broadcast for ``p @ v``,
    in f32; a GQA group's ``qpk`` query rows take turns.
    """
    B, H, hd = q.shape
    N, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert H % KV == 0, (H, KV)
    qpk = H // KV
    ppb = _blocks_per_step(nb, bs, KV, hd, k_pool.dtype)

    # (B, H, hd) -> (B, qpk, KV, hd): head kv*qpk + g is row g of kv group kv
    qg = q.reshape(B, KV, qpk, hd).transpose(0, 2, 1, 3)
    kernel = functools.partial(
        _decode_kernel,
        scale=1.0 / math.sqrt(hd),
        softcap=softcap,
        window=window,
        ppb=ppb,
    )
    per_slot = pl.BlockSpec((None, qpk, KV, hd), lambda b, sl, tbl: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[per_slot, pool_spec, pool_spec],
        out_specs=per_slot,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, bs, KV, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb, bs, KV, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, qpk, KV, hd), q.dtype),
        # a group in flight crosses into the next sequence's grid step
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32), qg, k_pool, v_pool)
    return out.transpose(0, 2, 1, 3).reshape(B, H, hd)


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
