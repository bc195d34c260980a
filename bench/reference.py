"""Plain reference forward of the dense decoder, and its lower-precision control.

Written from the published architecture, not from the program: pre-norm
decoder layers with OLMo's non-parametric LayerNorm, rotate-half RoPE over
the whole head, grouped-query causal softmax attention (query head ``h``
reads K/V head ``h // (H / KV)``), a SwiGLU MLP, a final norm and the output
head, which is the embedding table (tied). It imports nothing
of the program and runs on the benchmark's own weights (``bench/weights.py``).

``quant=None`` computes in float32 at ``HIGHEST`` matmul precision: the
reference. ``quant="fp8"`` is the control: the same forward with every matmul
input (weights per output channel, activations per token) and every cached
K/V row (per token and head) rounded to float8 e4m3 with a scale to its
amax. That is the step below the configuration's bf16 that a later change
might take; the comparison has to fail it.

The layers run under one ``lax.scan``, so only one layer's weights are in
float32 at a time.
"""

from __future__ import annotations

import json
import math
from functools import partial

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


def _mm(spec: str, x, w, quant, w_axis):
    """einsum in float32; ``quant`` rounds x per token (last axis) and w per
    output channel (``w_axis`` = its contracted axes)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = fake_fp8(x, -1)
        w = fake_fp8(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _norm(x, eps: float):
    """Non-parametric LayerNorm."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta: float):
    """x: (B, S, heads, hd) at positions 0..S-1, rotate-half convention."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg: dict, quant, x, lp):
    eps = cfg["architecture"]["norm_eps"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    B, S, _ = x.shape
    a = lp["attn"]
    h = _norm(x, eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"], quant, 0)
    k = _mm("bsd,dhk->bshk", h, a["wk"], quant, 0)
    v = _mm("bsd,dhk->bshk", h, a["wv"], quant, 0)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    if quant == "fp8":  # the cache holds K/V in the lower precision
        k, v = fake_fp8(k, -1), fake_fp8(v, -1)
    qg = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST).reshape(B, S, H, hd)
    x = x + _mm("bshk,hkd->bsd", ctx, a["wo"], quant, (0, 1))
    m = lp["mlp"]
    h = _norm(x, eps)
    g = _mm("bsd,df->bsf", h, m["w_gate"], quant, 0)
    u = _mm("bsd,df->bsf", h, m["w_up"], quant, 0)
    x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], quant, 0)
    return x, None


@partial(jax.jit, static_argnums=(0, 1))
def hidden(cfg_key, quant, params, tokens):
    """Final hidden states (B, S, D) in float32, after the final norm."""
    cfg = json.loads(cfg_key)
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_layer, cfg, quant), x, params["blocks"])
    return _norm(x, cfg["architecture"]["norm_eps"])


def _head(params, h, quant):
    return _mm("bsd,vd->bsv", h, params["embed"]["tok"], quant, 1)


@jax.jit
def served_gaps(params, ref_hidden, targets):
    """Per position: how far the served token's reference logit lies below
    the reference's best (0 where it is the best). ``targets`` (B, S) holds the
    token served after each position, -1 where none was served."""
    logits = _head(params, ref_hidden, None)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(targets >= 0, best - got, 0.0)


@jax.jit
def control_gaps(params, ref_hidden, ctl_hidden, targets):
    """Per position: the reference gap of the token the control puts first."""
    ref = _head(params, ref_hidden, None)
    ctl = _head(params, ctl_hidden, "fp8")
    first = jnp.argmax(ctl, axis=-1)
    got = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
    return jnp.where(targets >= 0, jnp.max(ref, axis=-1) - got, 0.0)


def cfg_key(cfg: dict) -> str:
    """What the forward reads of a configuration, as a hashable static
    argument of the jitted functions."""
    keep = ("architecture", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta")
    return json.dumps({k: cfg[k] for k in keep}, sort_keys=True)
