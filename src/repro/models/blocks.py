"""Block assembly per architecture family.

Every family exposes three entry points used by ``models/model.py``:

* ``block_specs(cfg)``              — ParamSpec tree for ONE layer (unstacked)
* ``block_apply(cfg, p, x, ...)``   — full-sequence forward (train / prefill)
* ``block_decode(cfg, p, x, cache)``— one-token step against a layer cache

Caches are per-layer pytrees; ``models/cache.py`` builds the stacked
(L, ...) versions and their abstract ShapeDtypeStruct twins for the dry-run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import (
    attention_specs,
    cross_attention,
    decode_attention,
    paged_chunk_attention,
    paged_decode_attention,
    prefill_attention,
    self_attention,
)
from repro.models.ffn import ffn, ffn_specs
from repro.models.layers import ParamSpec, apply_norm, norm_specs
from repro.models.moe import moe_ffn, moe_specs


def _rmsn(x, eps=1e-5):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)).astype(x.dtype)


@jax.named_scope("attention")
def _decode_attn(cfg, p_attn, h, cache, pos, *, sh=None, mesh=None):
    """Decode attention against either cache layout.

    Paged caches (block pools + ``tbl`` block tables) and dense slot caches
    share the block decode path — the cache tree's keys select the layout, so
    ``decode_step``'s layer scan is layout-agnostic.  Returns (out, new
    attention-cache entries).
    """
    if "tbl" in cache:
        return paged_decode_attention(cfg, p_attn, h, cache, pos, sh=sh, mesh=mesh)
    a, nk, nv, npos = decode_attention(cfg, p_attn, h, cache["k"], cache["v"], cache["pos"], pos, sh=sh)
    return a, {"k": nk, "v": nv, "pos": npos}


# ---------------------------------------------------------------------------
# dense (olmo, mistral-nemo, stablelm, gemma) and audio encoder (hubert)
# ---------------------------------------------------------------------------


def dense_block_specs(cfg) -> dict:
    specs = {
        "norm1": norm_specs(cfg),
        "attn": attention_specs(cfg),
        "norm2": norm_specs(cfg),
        "mlp": ffn_specs(cfg),
    }
    return specs


def dense_block(cfg, p, x, *, positions=None, q_chunk=0, sh=None, attn_impl="xla", fp8=None):
    h = apply_norm(cfg, p["norm1"], x)
    a = self_attention(
        cfg, p["attn"], h, positions=positions, q_chunk=q_chunk, sh=sh, impl=attn_impl, fp8=fp8
    )
    if cfg.parallel_residual:
        # GPT-NeoX / StableLM parallel form: one LN, attn + FFN both from it
        f = ffn(cfg, p["mlp"], h, sh=sh, fp8=fp8)
        x = x + a + f
    else:
        x = x + a
        h2 = apply_norm(cfg, p["norm2"], x)
        x = x + ffn(cfg, p["mlp"], h2, sh=sh, fp8=fp8)
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x


def dense_block_prefill(cfg, p, x, *, positions=None, q_chunk=0, sh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a, k, v = prefill_attention(cfg, p["attn"], h, positions=positions, q_chunk=q_chunk, sh=sh)
    if cfg.parallel_residual:
        f = ffn(cfg, p["mlp"], h, sh=sh)
        x = x + a + f
    else:
        x = x + a
        x = x + ffn(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x), sh=sh)
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x, {"k": k, "v": v}


def dense_block_chunk(cfg, p, x, cache, tbl_row, start, *, sh=None, attn_impl="xla", mesh=None, widths=None):
    """Chunked-prefill step: like ``dense_block_decode`` but for a C-token
    chunk written/attended through the request's own paged block table.
    ``widths`` (fused mixed batches): per-row valid-lane counts — pad lanes
    scatter to the null block and their outputs are discarded upstream."""
    h = apply_norm(cfg, p["norm1"], x)
    a, new_attn = paged_chunk_attention(
        cfg, p["attn"], h, cache, tbl_row, start, sh=sh, impl=attn_impl, mesh=mesh, widths=widths
    )
    if cfg.parallel_residual:
        f = ffn(cfg, p["mlp"], h, sh=sh)
        x = x + a + f
    else:
        x = x + a
        x = x + ffn(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x), sh=sh)
    return x, new_attn


def dense_block_decode(cfg, p, x, cache, pos, *, sh=None, mesh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a, new_attn = _decode_attn(cfg, p["attn"], h, cache, pos, sh=sh, mesh=mesh)
    if cfg.parallel_residual:
        f = ffn(cfg, p["mlp"], h, sh=sh)
        x = x + a + f
    else:
        x = x + a
        x = x + ffn(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x), sh=sh)
    return x, new_attn


# ---------------------------------------------------------------------------
# MoE (arctic: +dense residual FFN; qwen3: plain top-8)
# ---------------------------------------------------------------------------


def moe_block_specs(cfg) -> dict:
    specs = {
        "norm1": norm_specs(cfg),
        "attn": attention_specs(cfg),
        "norm2": norm_specs(cfg),
        "moe": moe_specs(cfg),
    }
    if cfg.moe.dense_residual:
        specs["dense_mlp"] = ffn_specs(cfg, cfg.d_ff)
        specs["norm_dense"] = norm_specs(cfg)
    return specs


def moe_block(cfg, p, x, *, positions=None, q_chunk=0, sh=None, attn_impl="xla", fp8=None):
    """Returns (x, aux_loss).  ``fp8`` quantizes attention projections (+ the
    dense-residual FFN); routed expert FFNs stay in compute dtype."""
    h = apply_norm(cfg, p["norm1"], x)
    a = self_attention(
        cfg, p["attn"], h, positions=positions, q_chunk=q_chunk, sh=sh, impl=attn_impl, fp8=fp8
    )
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    mo, aux = moe_ffn(cfg, p["moe"], h2, sh=sh)
    if cfg.moe.dense_residual:
        # Arctic: dense FFN in parallel with the routed experts
        mo = mo + ffn(cfg, p["dense_mlp"], apply_norm(cfg, p["norm_dense"], x), sh=sh, fp8=fp8)
    x = x + mo
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x, aux


def moe_block_prefill(cfg, p, x, *, positions=None, q_chunk=0, sh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a, k, v = prefill_attention(cfg, p["attn"], h, positions=positions, q_chunk=q_chunk, sh=sh)
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    mo, aux = moe_ffn(cfg, p["moe"], h2, sh=sh)
    if cfg.moe.dense_residual:
        mo = mo + ffn(cfg, p["dense_mlp"], apply_norm(cfg, p["norm_dense"], x), sh=sh)
    x = x + mo
    return x, {"k": k, "v": v}


def moe_block_chunk(cfg, p, x, cache, tbl_row, start, *, sh=None, attn_impl="xla", mesh=None, widths=None):
    """Chunked-prefill step for MoE blocks.  Routing sees exactly the chunk's
    tokens (no length-bucket pad tokens competing for expert capacity).
    Fused mixed batches (``widths``) reintroduce pad lanes into the routed
    batch — same expert-capacity caveat as bucketed prefill."""
    h = apply_norm(cfg, p["norm1"], x)
    a, new_attn = paged_chunk_attention(
        cfg, p["attn"], h, cache, tbl_row, start, sh=sh, impl=attn_impl, mesh=mesh, widths=widths
    )
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    mo, _ = moe_ffn(cfg, p["moe"], h2, sh=sh)
    if cfg.moe.dense_residual:
        mo = mo + ffn(cfg, p["dense_mlp"], apply_norm(cfg, p["norm_dense"], x), sh=sh)
    x = x + mo
    return x, new_attn


def moe_block_decode(cfg, p, x, cache, pos, *, sh=None, mesh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a, new_attn = _decode_attn(cfg, p["attn"], h, cache, pos, sh=sh, mesh=mesh)
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    mo, _ = moe_ffn(cfg, p["moe"], h2, sh=sh)
    if cfg.moe.dense_residual:
        mo = mo + ffn(cfg, p["dense_mlp"], apply_norm(cfg, p["norm_dense"], x), sh=sh)
    x = x + mo
    return x, new_attn


# ---------------------------------------------------------------------------
# RWKV6 (attention-free)
# ---------------------------------------------------------------------------


def rwkv_block_specs(cfg) -> dict:
    return {
        "norm1": norm_specs(cfg),
        "time_mix": rwkv_mod.time_mix_specs(cfg),
        "norm2": norm_specs(cfg),
        "channel_mix": rwkv_mod.channel_mix_specs(cfg),
    }


def rwkv_block(cfg, p, x, *, sh=None, **_):
    out, _state = rwkv_mod.time_mix(cfg, p["time_mix"], apply_norm(cfg, p["norm1"], x))
    x = x + out
    out, _cmx = rwkv_mod.channel_mix(cfg, p["channel_mix"], apply_norm(cfg, p["norm2"], x), sh=sh)
    x = x + out
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x


def rwkv_block_prefill(cfg, p, x, *, sh=None, **_):
    h = apply_norm(cfg, p["norm1"], x)
    out, (tm_x, state) = rwkv_mod.time_mix(cfg, p["time_mix"], h)
    x = x + out
    h2 = apply_norm(cfg, p["norm2"], x)
    out, cm_x = rwkv_mod.channel_mix(cfg, p["channel_mix"], h2, sh=sh)
    x = x + out
    return x, {"tm_x": tm_x, "cm_x": cm_x, "state": state}


def rwkv_block_decode(cfg, p, x, cache, pos, *, sh=None):
    h = apply_norm(cfg, p["norm1"], x)
    out, (tm_x, state) = rwkv_mod.time_mix_step(cfg, p["time_mix"], h, cache["tm_x"], cache["state"])
    x = x + out
    h2 = apply_norm(cfg, p["norm2"], x)
    out, cm_x = rwkv_mod.channel_mix(cfg, p["channel_mix"], h2, prev_x=cache["cm_x"], sh=sh)
    x = x + out
    return x, {"tm_x": tm_x, "cm_x": cm_x, "state": state}


# ---------------------------------------------------------------------------
# Hymba hybrid: parallel attention + SSM heads
# ---------------------------------------------------------------------------


def hybrid_block_specs(cfg) -> dict:
    D = cfg.d_model
    return {
        "norm1": norm_specs(cfg),
        "attn": attention_specs(cfg),
        "ssm": ssm_mod.ssm_specs(cfg),
        "beta_attn": ParamSpec((D,), ("embed",), "ones"),
        "beta_ssm": ParamSpec((D,), ("embed",), "ones"),
        "norm2": norm_specs(cfg),
        "mlp": ffn_specs(cfg),
    }


def _hybrid_combine(p, a, m, dtype):
    return 0.5 * (p["beta_attn"].astype(dtype) * _rmsn(a) + p["beta_ssm"].astype(dtype) * _rmsn(m))


def hybrid_block(cfg, p, x, *, positions=None, q_chunk=0, sh=None, attn_impl="xla", fp8=None):
    h = apply_norm(cfg, p["norm1"], x)
    a = self_attention(
        cfg, p["attn"], h, positions=positions, q_chunk=q_chunk, sh=sh, impl=attn_impl, fp8=fp8
    )
    m, _states = ssm_mod.ssm_mix(cfg, p["ssm"], h, sh=sh)
    x = x + _hybrid_combine(p, a, m, x.dtype)
    x = x + ffn(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x), sh=sh, fp8=fp8)
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x


def hybrid_block_prefill(cfg, p, x, *, positions=None, q_chunk=0, sh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a, k, v = prefill_attention(cfg, p["attn"], h, positions=positions, q_chunk=q_chunk, sh=sh)
    m, (conv_state, ssm_state) = ssm_mod.ssm_mix(cfg, p["ssm"], h, sh=sh)
    x = x + _hybrid_combine(p, a, m, x.dtype)
    x = x + ffn(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x), sh=sh)
    return x, {"k": k, "v": v, "conv": conv_state, "ssm": ssm_state}


def hybrid_block_decode(cfg, p, x, cache, pos, *, sh=None, mesh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a, new_attn = _decode_attn(cfg, p["attn"], h, cache, pos, sh=sh, mesh=mesh)
    m, (conv_state, ssm_state) = ssm_mod.ssm_step(cfg, p["ssm"], h, cache["conv"], cache["ssm"])
    x = x + _hybrid_combine(p, a, m, x.dtype)
    x = x + ffn(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x), sh=sh)
    return x, dict(new_attn, conv=conv_state, ssm=ssm_state)


# ---------------------------------------------------------------------------
# VLM cross-attention layer (llama-3.2-vision)
# ---------------------------------------------------------------------------


def cross_block_specs(cfg) -> dict:
    return {
        "norm1": norm_specs(cfg),
        "attn": attention_specs(cfg, cross=True),
        "norm2": norm_specs(cfg),
        "mlp": ffn_specs(cfg),
        "gate_mlp": ParamSpec((1,), (None,), "zeros"),
    }


def cross_block(cfg, p, x, vision_tokens, *, sh=None):
    h = apply_norm(cfg, p["norm1"], x)
    a = cross_attention(cfg, p["attn"], h, vision_tokens, sh=sh)
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    x = x + jnp.tanh(p["gate_mlp"].astype(x.dtype)) * ffn(cfg, p["mlp"], h2, sh=sh)
    if sh is not None:
        x = sh(x, ("batch", "seq", "embed"))
    return x


def cross_block_prefill(cfg, p, x, vision_tokens, *, sh=None):
    """Cross-attention at prefill; caches the projected vision K/V (static
    thereafter — image tokens never grow during decode)."""
    from repro.models.attention import _out, _attend_block, _qkv  # shared internals

    h = apply_norm(cfg, p["norm1"], x)
    q, ck, cv = _qkv(cfg, p["attn"], h, kv_x=vision_tokens)
    B, Sq = h.shape[:2]
    zero = jnp.zeros((B, 1, 1, Sq, vision_tokens.shape[1]), jnp.float32)
    ctx = _attend_block(cfg, q, ck, cv, zero, cfg.q_per_kv)
    a = jnp.tanh(p["attn"]["gate"].astype(x.dtype)) * _out(cfg, p["attn"], ctx, x.dtype)
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    x = x + jnp.tanh(p["gate_mlp"].astype(x.dtype)) * ffn(cfg, p["mlp"], h2, sh=sh)
    return x, {"ck": ck, "cv": cv}


def cross_block_decode(cfg, p, x, cache, *, sh=None):
    from repro.models.attention import _out, _attend_block, _qkv

    h = apply_norm(cfg, p["norm1"], x)
    pa = p["attn"]
    q = jnp.einsum("bsd,dhk->bshk", h, pa["wq"].astype(h.dtype))
    if cfg.qk_norm:
        from repro.models.attention import _rms_head

        q = _rms_head(q, pa["q_norm"], cfg.norm_eps)
    B = h.shape[0]
    zero = jnp.zeros((B, 1, 1, 1, cache["ck"].shape[1]), jnp.float32)
    ctx = _attend_block(cfg, q, cache["ck"], cache["cv"], zero, cfg.q_per_kv)
    a = jnp.tanh(pa["gate"].astype(x.dtype)) * _out(cfg, pa, ctx, x.dtype)
    x = x + a
    h2 = apply_norm(cfg, p["norm2"], x)
    x = x + jnp.tanh(p["gate_mlp"].astype(x.dtype)) * ffn(cfg, p["mlp"], h2, sh=sh)
    return x, cache
