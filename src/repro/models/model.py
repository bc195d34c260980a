"""The full model: embedding -> scanned block stack -> head.

Three entry points, all pure functions of (cfg, params, batch):

* ``forward``     — training forward pass: (logits, aux_loss)
* ``prefill``     — inference prefill: (last-position logits, stacked cache)
* ``decode_step`` — one-token decode:  (logits, new cache)

The block stack is a ``lax.scan`` over stacked (L, ...) parameters with a
configurable activation-checkpoint policy, so HLO size (and CPU compile time
in the dry-run) is independent of depth.  VLM architectures scan over
*layer groups* (cross_attn_every-1 self layers + 1 cross layer).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.layers import apply_norm, softcap

REMAT_POLICIES = {
    "none": None,
    "full": "nothing_saveable",
    "dots": "checkpoint_dots",
    "dots_no_batch": "checkpoint_dots_with_no_batch_dims",
}


def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    policy = REMAT_POLICIES[remat]
    if policy == "nothing_saveable":
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=getattr(jax.checkpoint_policies, policy))


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def embed_input(cfg, params, batch, *, sh=None):
    """Returns (x, positions). batch keys: tokens|frames [, positions]."""
    e = params["embed"]
    if cfg.family == "audio":
        frames = batch["frames"]
        x = frames @ e["frame_proj"].astype(frames.dtype)
        S = x.shape[1]
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), x.shape[:2])
        x = x + e["pos"][:S][None].astype(x.dtype)
    else:
        tokens = batch["tokens"]
        x = jnp.take(e["tok"], tokens, axis=0)
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
        if cfg.learned_pos_embedding:
            x = x + jnp.take(e["pos"], pos, axis=0).astype(x.dtype)
        if cfg.scale_embedding:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x, pos


@jax.named_scope("head")
def lm_logits(cfg, params, x, *, logits_dtype=jnp.float32, sh=None):
    """Final norm + output projection (tied or untied; padded-vocab mask)."""
    x = apply_norm(cfg, params["final_norm"], x)
    if sh is not None:
        # logits must be VOCAB-sharded, not seq-sharded: inheriting the
        # sequence-parallel sharding forces XLA to all-gather the fp32 vocab
        # table (measured 2.7 GB/device x several copies on mistral-nemo)
        x = sh(x, ("batch",) + ("seq_unsharded",) * (x.ndim - 2) + ("embed",))
    if "lm_head" in params:
        w = params["lm_head"].astype(x.dtype)
        logits = x @ w
    else:
        w = params["embed"]["tok"].astype(x.dtype)
        logits = jnp.einsum("...d,vd->...v", x, w)
    logits = logits.astype(logits_dtype)
    if sh is not None:
        logits = sh(logits, ("batch",) + ("seq_unsharded",) * (logits.ndim - 2) + ("vocab",))
    if cfg.padded_vocab != cfg.vocab_size:
        # mask the alignment-padding columns (never predicted / never labeled)
        col = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(col >= cfg.vocab_size, jnp.asarray(-1e9, logits.dtype), logits)
    return logits


# ---------------------------------------------------------------------------
# block-stack runners
# ---------------------------------------------------------------------------


def _train_body(cfg, *, positions, q_chunk, sh, attn_impl, vision_tokens=None, fp8=None):
    fam = cfg.family
    kw = dict(positions=positions, q_chunk=q_chunk, sh=sh, attn_impl=attn_impl)
    if fp8 is not None:
        from repro.fp8 import fp8_supported

        if not fp8_supported(cfg):
            # ssm has no quantizable projections; vlm scans layer *groups*
            # (amax drain across the nested scan is not wired)
            raise ValueError(f"fp8 training is not supported for family={fam}")
        kw["fp8"] = fp8

    if fam in ("dense", "audio"):

        def body(carry, p_layer):
            return (B.dense_block(cfg, p_layer, carry[0], **kw), carry[1]), None

    elif fam == "moe":

        def body(carry, p_layer):
            x, aux = carry
            x, a = B.moe_block(cfg, p_layer, x, **kw)
            return (x, aux + a), None

    elif fam == "ssm":

        def body(carry, p_layer):
            return (B.rwkv_block(cfg, p_layer, carry[0], sh=sh), carry[1]), None

    elif fam == "hybrid":

        def body(carry, p_layer):
            return (B.hybrid_block(cfg, p_layer, carry[0], **kw), carry[1]), None

    elif fam == "vlm":

        def body(carry, p_group):
            x, aux = carry

            def self_body(xc, p_layer):
                return B.dense_block(cfg, p_layer, xc, **kw), None

            x, _ = jax.lax.scan(self_body, x, p_group["self"])
            x = B.cross_block(cfg, p_group["cross"], x, vision_tokens, sh=sh)
            return (x, aux), None

    else:
        raise ValueError(fam)
    if fp8 is None:
        return body

    def body_fp8(carry, xs):
        # bind this layer's scale slice, run the family body, then emit the
        # layer's observed amaxes as a scan output (drain inside the body:
        # observations are tracers of THIS scan/remat trace and must not
        # escape it; per-layer ys keep one delayed scale per tensor)
        p_layer, scales = xs
        fp8.bind_layer_scales(scales)
        carry, _ = body(carry, p_layer)
        return carry, fp8.drain()

    return body_fp8


def forward(
    cfg, params, batch, *, sh=None, q_chunk=0, remat="none", attn_impl="xla", compute_dtype=None, fp8=None
):
    """Training forward. Returns (logits, aux_loss), or (logits, aux_loss,
    amaxes) when an ``fp8`` context is passed (see ``repro.fp8.policy``).

    ``compute_dtype``: cast the activation stream (not the master weights) —
    every weight use casts its own layer slice via ``.astype(x.dtype)``, which
    keeps the stacked fp32 params (and their gradients) on the FSDP sharding
    through the layer scan instead of materializing an unsharded bf16 tree.
    (FP8 sites instead quantize the fp32 slice directly — same sharding
    property, 1-byte wire format.)
    """
    x, positions = embed_input(cfg, params, batch, sh=sh)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    vision_tokens = batch.get("vision_tokens")
    if vision_tokens is not None and compute_dtype is not None:
        vision_tokens = vision_tokens.astype(compute_dtype)
    body = _train_body(
        cfg,
        positions=positions,
        q_chunk=q_chunk,
        sh=sh,
        attn_impl=attn_impl,
        vision_tokens=vision_tokens,
        fp8=fp8,
    )
    body = _maybe_remat(body, remat)
    aux0 = jnp.zeros((), jnp.float32)
    if fp8 is None:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
        logits = lm_logits(cfg, params, x, sh=sh)
        return logits, aux / cfg.num_layers
    # scan the per-layer scale slices alongside the stacked params; the ys
    # are each layer's observed amaxes -> dict site-key -> (num_layers,)
    (x, aux), amaxes = jax.lax.scan(body, (x, aux0), (params["blocks"], fp8.layer_scales()))
    logits = lm_logits(cfg, params, x, sh=sh)
    return logits, aux / cfg.num_layers, amaxes


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def prefill(cfg, params, batch, *, sh=None, q_chunk=0, remat="none"):
    """Inference prefill. Returns (last-position logits (B,V), raw cache).

    The raw cache holds full-length K/V; ``repro.serving.kvcache`` converts it
    into the ring-buffered decode cache (or grafts it into paged blocks).

    ``batch["last_index"]`` (optional, (B,) int32): per-sequence index of the
    last *real* token — the logits are taken there instead of at position
    S-1.  This is what makes right-padded length-bucketed prefill (the
    serving engine's recompilation fix) exact for causal attention archs: pad
    positions beyond ``last_index`` can never influence earlier K/V.
    """
    x, positions = embed_input(cfg, params, batch, sh=sh)
    vision_tokens = batch.get("vision_tokens")
    fam = cfg.family
    kw = dict(positions=positions, q_chunk=q_chunk, sh=sh)

    if fam in ("dense", "audio"):

        def body(x, p_layer):
            return B.dense_block_prefill(cfg, p_layer, x, **kw)

    elif fam == "moe":

        def body(x, p_layer):
            return B.moe_block_prefill(cfg, p_layer, x, **kw)

    elif fam == "ssm":

        def body(x, p_layer):
            return B.rwkv_block_prefill(cfg, p_layer, x, sh=sh)

    elif fam == "hybrid":

        def body(x, p_layer):
            return B.hybrid_block_prefill(cfg, p_layer, x, **kw)

    elif fam == "vlm":

        def body(x, p_group):
            def self_body(xc, p_layer):
                return B.dense_block_prefill(cfg, p_layer, xc, **kw)

            x, self_cache = jax.lax.scan(self_body, x, p_group["self"])
            x, cross_cache = B.cross_block_prefill(cfg, p_group["cross"], x, vision_tokens, sh=sh)
            return x, {"self": self_cache, "cross": cross_cache}

    else:
        raise ValueError(fam)

    body = _maybe_remat(body, remat)
    x, raw_cache = jax.lax.scan(body, x, params["blocks"])
    last_index = batch.get("last_index")
    if last_index is None:
        x_last = x[:, -1:]
    else:
        x_last = jnp.take_along_axis(x, last_index.astype(jnp.int32)[:, None, None], axis=1)
    logits = lm_logits(cfg, params, x_last, sh=sh)[:, 0]
    return logits, raw_cache


# ---------------------------------------------------------------------------
# chunked prefill (paged cache)
# ---------------------------------------------------------------------------

CHUNKED_PREFILL_FAMILIES = ("dense", "moe")


def supports_chunked_prefill(cfg) -> bool:
    """Chunked prefill needs (a) a paged cache and (b) per-chunk state that is
    fully captured by the written K/V.  Hybrid conv/SSM (and rwkv) recurrent
    states absorb the whole prompt in one pass and cannot be resumed
    mid-prompt, so those families keep the blocking prefill+graft path."""
    return cfg.family in CHUNKED_PREFILL_FAMILIES


def _chunk_stack(
    cfg, params, cache, tokens, start, tbl_row, *, sh=None, attn_impl="xla", mesh=None, widths=None
):
    """Shared chunk runner: embed C tokens at ``start + [0, C)``, scatter
    their K/V into the paged cache through ``tbl_row`` and attend causally
    over the paged history.  Returns (x (B, C, D), new cache).

    ``widths`` ((B,) int32, optional): per-row valid-lane counts for fused
    mixed batches — lanes at or past ``widths[b]`` scatter to the null block
    and their outputs are garbage the caller discards."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(f"no chunked prefill for family {cfg.family!r} ({cfg.name})")
    C = tokens.shape[1]
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x, _ = embed_input(cfg, params, {"tokens": tokens, "positions": positions}, sh=sh)
    step = B.dense_block_chunk if cfg.family == "dense" else B.moe_block_chunk

    def body(x, xs):
        p_layer, c_layer = xs
        x, nc = step(
            cfg, p_layer, x, c_layer, tbl_row, start,
            sh=sh, attn_impl=attn_impl, mesh=mesh, widths=widths,
        )
        return x, nc

    return jax.lax.scan(body, x, (params["blocks"], cache))


def prefill_step(cfg, params, cache, tokens, start, tbl_row, *, sh=None, attn_impl="xla", mesh=None):
    """Process one prompt *chunk* against a paged cache.

    tokens:  (B, C) int32 — C consecutive prompt tokens
    start:   (B,) int32 absolute position of the chunk's first token
    tbl_row: (B, nb) int32 — the request's block table (the engine's
             ``cache["tbl"]`` rows stay null until the prompt completes, so
             interleaved decode steps can't touch a half-prefilled request).

    Writes the chunk's K/V into the request's blocks, attends causally over
    the paged history [0, start + C) — shared prefix blocks included — and
    returns (logits (B, V) at the chunk's LAST token, new cache); the final
    chunk's logits are the prompt logits admission samples from.

    Exactness: for dense archs chaining chunks reproduces full-prompt
    ``prefill`` exactly (attention is causal, FFN/norms per-token).  For MoE
    the expert-capacity limit is computed per routed batch, so when capacity
    *binds* (low ``capacity_factor``) which tokens overflow can differ
    between chunked, exact-length, and pad-bucketed prefill — all three are
    defensible GShard semantics (the chunked path is the only one where pad
    tokens never compete for capacity), but they only coincide token-for-
    token when no token is dropped.
    """
    x, new_cache = _chunk_stack(
        cfg, params, cache, tokens, start, tbl_row, sh=sh, attn_impl=attn_impl, mesh=mesh
    )
    logits = lm_logits(cfg, params, x[:, -1], sh=sh)
    return logits, new_cache


def verify_step(cfg, params, cache, tokens, start, tbl_row, *, sh=None, attn_impl="xla", mesh=None):
    """Score C candidate tokens against a paged cache in one pass.

    Same chunk machinery as ``prefill_step`` (scatter-then-attend through
    ``kernels.paged_prefill_attention``), but returns the logits at EVERY
    chunk position, (B, C, V) — the speculative-decoding verification pass:
    feeding ``[last_committed, d_1, ..., d_k]`` yields the target model's
    distribution after each drafted token, so ``sampler.spec_accept`` can
    accept/reject the whole draft window from one model call instead of k
    sequential ``decode_step``s.

    The fed tokens' K/V is written to the cache as a side effect; the caller
    rolls back (``serving.kvcache.truncate_block_rows``) whatever the
    accept/reject pass does not commit.  MoE caveat as ``prefill_step``: the
    expert-capacity limit is computed over the B*C routed batch, so chunked
    scoring coincides with one-token decode only when capacity doesn't bind.
    """
    x, new_cache = _chunk_stack(
        cfg, params, cache, tokens, start, tbl_row, sh=sh, attn_impl=attn_impl, mesh=mesh
    )
    logits = lm_logits(cfg, params, x, sh=sh)
    return logits, new_cache


def unified_step(
    cfg, params, cache, tokens, start, widths, tbl_rows, *, sh=None, attn_impl="xla", mesh=None
):
    """One fused dispatch over a mixed row batch (the one-dispatch step).

    tokens:   (R, W) int32 — each row feeds up to W consecutive tokens
    start:    (R,) int32 absolute position of each row's first token
    widths:   (R,) int32 valid lanes per row — a decode row feeds 1, a
              prefill-chunk row feeds its chunk length, a spec-verify row
              feeds spec_k + 1; lanes past the width scatter to the null
              block and their logits are garbage the caller discards
    tbl_rows: (R, nb) int32 per-row block tables (a mid-prefill row's table
              is its private block list; decode/verify rows pass the
              published engine row)

    Rows are independent batch entries through the same chunk machinery as
    ``prefill_step`` / ``verify_step``; because every layer scatters all
    rows' K/V before attending, several chunk rows of ONE request may ride
    in the same dispatch (a later chunk reads the earlier chunk's same-layer
    K/V exactly as sequential chunking would).  Returns (logits (R, W, V),
    new cache) — all-lane logits so the caller can fold sampling and
    speculative accept into the same compiled graph.
    """
    x, new_cache = _chunk_stack(
        cfg, params, cache, tokens, start, tbl_rows,
        sh=sh, attn_impl=attn_impl, mesh=mesh, widths=widths,
    )
    logits = lm_logits(cfg, params, x, sh=sh)
    return logits, new_cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_step(cfg, params, cache, token, pos, *, sh=None, mesh=None):
    """One decode step.

    token: (B, 1) int32 (ignored dims for audio); pos: (B,) int32 absolute
    position of this token.  Returns (logits (B, V), new cache).

    The cache may be the dense slot layout (``models.cache.init_cache``) or
    the paged block-pool layout (``models.cache.init_paged_cache``) for
    dense/moe/hybrid families — the per-layer cache keys select the path.
    Paged decode attention is chosen from the platform and the pool
    (``kernels.paged_attention_ops.decode_uses_kernel``); dense slot caches
    always use the jnp path.  ``mesh``: tensor-parallel serving mesh — the
    Pallas paged kernel runs per-shard under ``shard_map`` on its local head
    slice (jnp paths partition via GSPMD and ignore it).
    """
    if cfg.is_encoder_only:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    batch = {"tokens": token, "positions": pos[:, None]}
    x, _ = embed_input(cfg, params, batch, sh=sh)
    fam = cfg.family

    if fam in ("dense", "moe"):
        step = B.dense_block_decode if fam == "dense" else B.moe_block_decode

        def body(x, xs):
            p_layer, c_layer = xs
            x, nc = step(cfg, p_layer, x, c_layer, pos, sh=sh, mesh=mesh)
            return x, nc

    elif fam == "ssm":

        def body(x, xs):
            p_layer, c_layer = xs
            x, nc = B.rwkv_block_decode(cfg, p_layer, x, c_layer, pos, sh=sh)
            return x, nc

    elif fam == "hybrid":

        def body(x, xs):
            p_layer, c_layer = xs
            x, nc = B.hybrid_block_decode(cfg, p_layer, x, c_layer, pos, sh=sh, mesh=mesh)
            return x, nc

    elif fam == "vlm":

        def body(x, xs):
            p_group, c_group = xs

            def self_body(xc, inner):
                p_layer, c_layer = inner
                xc, nc = B.dense_block_decode(cfg, p_layer, xc, c_layer, pos, sh=sh)
                return xc, nc

            x, new_self = jax.lax.scan(self_body, x, (p_group["self"], c_group["self"]))
            x, new_cross = B.cross_block_decode(cfg, p_group["cross"], x, c_group["cross"], sh=sh)
            return x, {"self": new_self, "cross": new_cross}

    else:
        raise ValueError(fam)

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    logits = lm_logits(cfg, params, x[:, 0], sh=sh)
    return logits, new_cache
