"""Attention: GQA/MQA self-attention, sliding window, cross-attention, decode.

Two execution paths:

* ``xla``   — pure-jnp attention with optional *query chunking* (a lax.scan over
  query blocks with a full softmax per block).  Memory is bounded by
  ``q_chunk x kv_len`` instead of ``q_len x kv_len``, which is what makes the
  32k prefill cells lowerable within a v5e HBM budget.  This is the path the
  dry-run lowers.
* ``flash`` — the Pallas TPU kernel in ``repro.kernels.flash_attention``
  (online-softmax VMEM tiling).  Selected via ``impl="flash"``; validated in
  interpret mode on CPU.

Shapes follow the (batch, seq, heads, head_dim) convention throughout.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, apply_norm, apply_rope, softcap

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def attention_specs(cfg, *, cross: bool = False) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.use_bias:
        specs["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), "zeros")
        specs["bk"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), "zeros")
        specs["bv"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), "zeros")
        specs["bo"] = ParamSpec((D,), ("embed",), "zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), "ones")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), "ones")
    if cross:
        # Llama-3.2-vision style gating: cross-attn output enters the residual
        # through a zero-initialized tanh gate.
        specs["gate"] = ParamSpec((1,), (None,), "zeros")
    return specs


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------


@jax.named_scope("qkv")
def _qkv(cfg, p, x, kv_x=None, fp8=None):
    """Project to q,k,v. kv_x: source for k/v (cross-attention).

    ``fp8``: an ``repro.fp8.Fp8Ctx`` — routes the projection GEMMs through
    quantized matmuls (the head-split is a free reshape around a 2-D GEMM).
    """
    kv_src = x if kv_x is None else kv_x
    if fp8 is not None:
        D = p["wq"].shape[0]
        H, hd = p["wq"].shape[1], p["wq"].shape[2]
        KV = p["wk"].shape[1]
        q = fp8.matmul("attn_q", x, p["wq"].reshape(D, H * hd)).reshape(x.shape[:-1] + (H, hd))
        k = fp8.matmul("attn_k", kv_src, p["wk"].reshape(D, KV * hd)).reshape(kv_src.shape[:-1] + (KV, hd))
        v = fp8.matmul("attn_v", kv_src, p["wv"].reshape(D, KV * hd)).reshape(kv_src.shape[:-1] + (KV, hd))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(x.dtype))
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _rms_head(q, p["q_norm"], cfg.norm_eps)
        k = _rms_head(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _rms_head(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("out_proj")
def _out(cfg, p, ctx, dtype, fp8=None):
    if fp8 is not None:
        H, hd, D = p["wo"].shape
        out = fp8.matmul("attn_o", ctx.reshape(ctx.shape[:-2] + (H * hd,)), p["wo"].reshape(H * hd, D))
        out = out.astype(dtype)
    else:
        out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"].astype(dtype))
    if cfg.use_bias:
        out = out + p["bo"].astype(dtype)
    return out


# ---------------------------------------------------------------------------
# core attention math (grouped heads, fp32 softmax)
# ---------------------------------------------------------------------------


def _scores(q, k, q_per_kv, scale):
    """q: (B,Sq,H,hd), k: (B,Skv,KV,hd) -> (B,KV,G,Sq,Skv) fp32."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Sq, KV, q_per_kv, hd)
    s = jnp.einsum("bqhgk,bshk->bhgqs", qg, k)
    return s.astype(jnp.float32) * scale


def _attend_block(cfg, q, k, v, mask, q_per_kv):
    """Exact softmax attention for one (possibly chunked) query block.

    mask: (B?, 1, 1, Sq, Skv) additive fp32 mask (0 / NEG_INF).
    """
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = _scores(q, k, q_per_kv, scale)
    if cfg.attn_logit_softcap > 0:
        s = softcap(s, cfg.attn_logit_softcap)
    s = s + mask
    w = jax.nn.softmax(s, axis=-1)
    B, Sq = q.shape[0], q.shape[1]
    ctx = jnp.einsum("bhgqs,bshk->bqhgk", w.astype(v.dtype), v)
    return ctx.reshape(B, Sq, cfg.num_heads, cfg.head_dim)


def make_mask(q_pos, kv_pos, *, causal: bool, window: int) -> jax.Array:
    """Additive mask (..., Sq, Skv) from absolute positions."""
    rel = q_pos[..., :, None] - kv_pos[..., None, :]  # q - kv
    ok = jnp.ones(rel.shape, bool)
    if causal:
        ok &= rel >= 0
    if window > 0:
        ok &= rel < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def self_attention(
    cfg,
    p: dict,
    x: jax.Array,
    *,
    positions: jax.Array | None = None,
    q_chunk: int = 0,
    impl: str = "xla",
    sh=None,
    fp8=None,
) -> jax.Array:
    """Full-sequence self-attention (training / prefill)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = _qkv(cfg, p, x, fp8=fp8)
    if cfg.rotary_pct > 0 and not cfg.learned_pos_embedding:
        q = apply_rope(q, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
        k = apply_rope(k, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    if sh is not None:
        q = sh(q, ("batch", "seq", "heads", None))
        # K/V: head-sharded when kv_heads divides the model axis, else
        # REPLICATED (Megatron GQA duplication).  The seq-parallel fallback
        # is deliberately absent: seq-sharded K/V against head-sharded scores
        # forces XLA into "involuntary full rematerialization" reshards
        # inside every layer loop (measured 80+ s collective term on
        # mistral-nemo train_4k — EXPERIMENTS.md §Perf iteration 1).
        k = sh(k, ("batch", None, "kv_heads", None))
        v = sh(v, ("batch", None, "kv_heads", None))

    if impl == "flash":
        from repro.kernels import flash_attention_ops

        ctx = flash_attention_ops.flash_attention(
            q, k, v, causal=cfg.causal, window=cfg.sliding_window, softcap=cfg.attn_logit_softcap
        )
        return _out(cfg, p, ctx, x.dtype, fp8=fp8)

    qpk = cfg.q_per_kv
    if q_chunk and S > q_chunk and S % q_chunk == 0:
        nchunk = S // q_chunk
        qs = q.reshape(B, nchunk, q_chunk, cfg.num_heads, cfg.head_dim).transpose(1, 0, 2, 3, 4)
        pos_q = positions.reshape(B, nchunk, q_chunk).transpose(1, 0, 2)

        def body(carry, inp):
            qc, pq = inp
            m = make_mask(pq, positions, causal=cfg.causal, window=cfg.sliding_window)
            m = m[:, None, None]  # (B,1,1,qc,S)
            return carry, _attend_block(cfg, qc, k, v, m, qpk)

        # remat: without it the scan saves every chunk's (qc x S) score matrix
        _, ctx = jax.lax.scan(jax.checkpoint(body), None, (qs, pos_q))
        ctx = ctx.transpose(1, 0, 2, 3, 4).reshape(B, S, cfg.num_heads, cfg.head_dim)
    else:
        m = make_mask(positions, positions, causal=cfg.causal, window=cfg.sliding_window)
        ctx = _attend_block(cfg, q, k, v, m[:, None, None], qpk)
    if sh is not None:
        ctx = sh(ctx, ("batch", "seq", "heads", None))
    return _out(cfg, p, ctx, x.dtype, fp8=fp8)


def cross_attention(cfg, p: dict, x: jax.Array, kv_tokens: jax.Array, *, sh=None) -> jax.Array:
    """Cross-attention onto (unpositioned) vision tokens, with tanh gating."""
    q, k, v = _qkv(cfg, p, x, kv_x=kv_tokens)
    B, Sq = x.shape[:2]
    zero = jnp.zeros((B, 1, 1, Sq, kv_tokens.shape[1]), jnp.float32)
    ctx = _attend_block(cfg, q, k, v, zero, cfg.q_per_kv)
    out = _out(cfg, p, ctx, x.dtype)
    return jnp.tanh(p["gate"].astype(x.dtype)) * out


def prefill_attention(cfg, p, x, *, positions=None, q_chunk: int = 0, sh=None):
    """Self-attention that also returns the K/V tensors for the cache."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = _qkv(cfg, p, x)
    if cfg.rotary_pct > 0 and not cfg.learned_pos_embedding:
        q = apply_rope(q, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
        k = apply_rope(k, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    qpk = cfg.q_per_kv
    if q_chunk and S > q_chunk and S % q_chunk == 0:
        nchunk = S // q_chunk
        qs = q.reshape(B, nchunk, q_chunk, cfg.num_heads, cfg.head_dim).transpose(1, 0, 2, 3, 4)
        pos_q = positions.reshape(B, nchunk, q_chunk).transpose(1, 0, 2)

        def body(carry, inp):
            qc, pq = inp
            m = make_mask(pq, positions, causal=cfg.causal, window=cfg.sliding_window)
            return carry, _attend_block(cfg, qc, k, v, m[:, None, None], qpk)

        _, ctx = jax.lax.scan(jax.checkpoint(body), None, (qs, pos_q))
        ctx = ctx.transpose(1, 0, 2, 3, 4).reshape(B, S, cfg.num_heads, cfg.head_dim)
    else:
        m = make_mask(positions, positions, causal=cfg.causal, window=cfg.sliding_window)
        ctx = _attend_block(cfg, q, k, v, m[:, None, None], qpk)
    return _out(cfg, p, ctx, x.dtype), k, v


def decode_attention(
    cfg,
    p: dict,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_pos: jax.Array,
    pos: jax.Array,
    *,
    sh=None,
):
    """Single-token decode against a (possibly ring-buffered) KV cache.

    x:        (B, 1, D) current token embedding stream
    cache_k/v:(B, W, KV, hd) cache buffer (W = full seq or sliding window)
    cache_pos:(B, W) absolute position held in each slot (-1 = empty)
    pos:      (B,) absolute position of the current token
    Returns (out, new_k, new_v, new_cache_pos).
    """
    B, W = cache_pos.shape
    q, k, v = _qkv(cfg, p, x)
    q, k = _rope_decode(cfg, q, k, pos)

    slot = pos % W  # ring-buffer slot (full cache: W >= S so slot == pos)
    b_idx = jnp.arange(B)
    # scatter write: fuses into an in-place update on the donated cache buffer
    with jax.named_scope("cache_write"):
        new_k = cache_k.at[b_idx, slot].set(k[:, 0].astype(cache_k.dtype))
        new_v = cache_v.at[b_idx, slot].set(v[:, 0].astype(cache_v.dtype))
        new_cache_pos = cache_pos.at[b_idx, slot].set(pos)

    m = make_mask(pos[:, None], new_cache_pos, causal=cfg.causal, window=cfg.sliding_window)
    m = jnp.where(new_cache_pos[:, None, :] < 0, NEG_INF, m)  # empty slots
    ctx = _attend_block(cfg, q, new_k, new_v, m[:, None, None], cfg.q_per_kv)
    return _out(cfg, p, ctx, x.dtype), new_k, new_v, new_cache_pos


def _rope_decode(cfg, q, k, pos):
    """RoPE at each slot's one decode position (part of the ``qkv`` scope)."""
    if cfg.rotary_pct > 0 and not cfg.learned_pos_embedding:
        with jax.named_scope("qkv"):
            q = apply_rope(q, pos[:, None], rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
            k = apply_rope(k, pos[:, None], rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    return q, k


@jax.named_scope("cache_write")
def _write_kv(cache: dict, k, v, phys, off, quant_mode):
    """Scatter new K/V rows into the paged pools at (block ``phys``, offset
    ``off``), quantizing on the way in for int8/fp8 pools.  Returns the new
    cache dict."""
    new_cache = dict(cache)
    if quant_mode is not None:
        from repro.serving.kvquant import quantize

        kq, ks = quantize(k, quant_mode)
        vq, vs = quantize(v, quant_mode)
        new_cache["k"] = cache["k"].at[phys, off].set(kq)
        new_cache["v"] = cache["v"].at[phys, off].set(vq)
        new_cache["k_scale"] = cache["k_scale"].at[phys, off].set(ks)
        new_cache["v_scale"] = cache["v_scale"].at[phys, off].set(vs)
    else:
        new_cache["k"] = cache["k"].at[phys, off].set(k.astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[phys, off].set(v.astype(cache["v"].dtype))
    return new_cache


@jax.named_scope("attention")
def paged_chunk_attention(
    cfg,
    p: dict,
    x: jax.Array,
    cache: dict,
    tbl_row: jax.Array,
    start: jax.Array,
    *,
    impl: str = "xla",
    sh=None,
    mesh=None,
    widths: jax.Array | None = None,
):
    """Chunked-prefill attention against a paged (block-pooled) KV cache.

    x:       (B, C, D) chunk token embedding stream
    cache:   {"k","v": (N, bs, KV, hd) pools, "tbl": engine table (unused
             here — mid-prefill slots keep a null engine row so interleaved
             decode steps can't touch their blocks), ...}
    tbl_row: (B, nb) int32 — the *request's* block table, covering every
             logical block of prompt + generation
    start:   (B,) int32 absolute position of the chunk's first token.
    widths:  (B,) int32, optional — per-row count of VALID lanes.  Rows in a
             fused mixed batch feed fewer than C real tokens; lanes at or
             past ``widths[b]`` are redirected to the null block so their
             K/V scatter lands in scratch, never in a live block (same
             masked-scatter pattern as ``serving.kvcache.truncate_block_rows``).
             Their attention outputs are garbage the caller must discard.

    The chunk's K/V is scattered into its blocks first (position t lands in
    block ``tbl_row[b, t // bs]`` at offset ``t % bs``), then every chunk
    query attends causally over the logical view [0, start + offset] — the
    shared prefix blocks grafted by admission, earlier chunks, and this
    chunk itself.  ``impl="pallas"`` uses the multi-query-token
    ``kernels.paged_prefill_attention`` kernel, ``impl="xla"`` the jnp
    oracle; quantized (int8/fp8) pools quantize on the way in and take the
    dequantizing reference.  ``mesh``: tensor-parallel serving mesh — the
    Pallas kernel runs per-shard under ``shard_map`` on its local head slice
    (head counts that don't divide the model axis raise).  Returns (out, new_cache) with the same keys as ``cache``.
    """
    from repro.serving.kvquant import kv_quant_mode_of

    k_pool = cache["k"]
    B, C, _ = x.shape
    bs = k_pool.shape[1]
    quant_mode = kv_quant_mode_of(k_pool.dtype)
    quantized = quant_mode is not None

    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (B, C)
    q, k, v = _qkv(cfg, p, x)
    if cfg.rotary_pct > 0 and not cfg.learned_pos_embedding:
        with jax.named_scope("qkv"):
            q = apply_rope(q, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
            k = apply_rope(k, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)

    # dead lanes may index past the table; clamp — their write goes to scratch
    idx = jnp.minimum(positions // bs, tbl_row.shape[1] - 1)
    phys = jnp.take_along_axis(tbl_row, idx, axis=1)  # (B, C)
    if widths is not None:
        from repro.models.cache import NULL_BLOCK

        lane = jnp.arange(C, dtype=jnp.int32)[None, :]
        phys = jnp.where(lane < widths[:, None], phys, NULL_BLOCK)
    off = positions % bs
    new_cache = _write_kv(cache, k, v, phys, off, quant_mode)

    if quantized:
        from repro.kernels.paged_attention_ops import paged_prefill_attention_quantized

        ctx = paged_prefill_attention_quantized(
            q,
            new_cache["k"],
            new_cache["v"],
            new_cache["k_scale"],
            new_cache["v_scale"],
            tbl_row,
            start,
            softcap=cfg.attn_logit_softcap,
            window=cfg.sliding_window,
        )
    elif impl == "pallas":
        from repro.kernels.paged_attention_ops import paged_prefill_attention

        ctx = paged_prefill_attention(
            q,
            new_cache["k"],
            new_cache["v"],
            tbl_row,
            start,
            softcap=cfg.attn_logit_softcap,
            window=cfg.sliding_window,
            mesh=mesh,
        )
    else:
        from repro.kernels.paged_attention_ref import paged_prefill_attention_ref

        ctx = paged_prefill_attention_ref(
            q,
            new_cache["k"],
            new_cache["v"],
            tbl_row,
            start,
            softcap=cfg.attn_logit_softcap,
            window=cfg.sliding_window,
        )
    return _out(cfg, p, ctx, x.dtype), new_cache


def paged_decode_attention(
    cfg,
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    *,
    sh=None,
    mesh=None,
):
    """Single-token decode against a paged (block-pooled) KV cache.

    x:     (B, 1, D) current token embedding stream
    cache: {"k","v": (N, bs, KV, hd) pools, "tbl": (B, nb) block table,
            ["k_scale","v_scale": (N, bs, KV, 1) for int8 pools]}
    pos:   (B,) absolute position of the current token.

    The new K/V lands in block ``tbl[b, pos // bs]`` at offset ``pos % bs``.
    Inactive batch slots carry all-null block tables, so their writes hit the
    reserved null block, never a live request's memory.  Attention runs over
    the logical view [0, pos] via the block table, through
    ``kernels.paged_attention_ops.paged_decode``: the Pallas kernel on a TPU
    for unquantized pools, the jnp oracle elsewhere.  ``mesh``:
    tensor-parallel serving mesh for the Pallas path (see
    ``paged_chunk_attention``).

    Returns (out, new_cache) with the same keys as ``cache``.
    """
    from repro.kernels.paged_attention_ops import paged_decode
    from repro.serving.kvquant import kv_quant_mode_of

    k_pool, tbl = cache["k"], cache["tbl"]
    B = x.shape[0]
    bs = k_pool.shape[1]
    quant_mode = kv_quant_mode_of(k_pool.dtype)

    q, k, v = _qkv(cfg, p, x)
    q, k = _rope_decode(cfg, q, k, pos)

    b_idx = jnp.arange(B)
    phys = tbl[b_idx, pos // bs]  # physical block holding this position
    off = pos % bs
    new_cache = _write_kv(cache, k[:, 0], v[:, 0], phys, off, quant_mode)

    ctx = paged_decode(
        q[:, 0],
        new_cache["k"],
        new_cache["v"],
        tbl,
        pos + 1,
        k_scale=new_cache.get("k_scale"),
        v_scale=new_cache.get("v_scale"),
        softcap=cfg.attn_logit_softcap,
        window=cfg.sliding_window,
        mesh=mesh,
    )
    out = _out(cfg, p, ctx[:, None], x.dtype)
    return out, new_cache
