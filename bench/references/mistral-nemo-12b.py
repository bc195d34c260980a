"""Mistral-NeMo's plain reference forward, its control, its weight layout and
the fields the program's registry entry has to state.

Written from the published architecture (Mistral-Nemo-Base-2407), not from
the program: pre-norm decoder layers with RMSNorm and a learned scale,
rotate-half RoPE over the whole head, grouped-query causal softmax attention
(32 query heads over 8 K/V heads; query head ``h`` reads K/V head
``h // 4``), a SwiGLU MLP, a final RMSNorm and an untied output head. The
head width is its own: 32 heads x 128 = 4096, not d_model 5120. It imports
nothing of the program and runs on the benchmark's own weights
(``bench/weights.py``, built from ``layout``).

``quant=None`` computes in float32 at ``HIGHEST`` matmul precision: the
reference. ``quant=CONTROL`` is the control: the same forward with every
matmul input and every cached K/V row (per token and head) rounded to float8
e4m3 (``common.fake_fp8``), the step below the configuration's bf16.

The layers run under one ``lax.scan``, so only one layer's weights are in
float32 at a time, and attention runs one block of ``QUERY_BLOCK`` query
positions at a time (``blocked_causal_attention``): at the harness's
reference shape (4 sequences of 4352 positions) the whole float32 score
matrix of a layer is 9.7 GB, a block of 256 queries 0.57 GB.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.references.common import HIGHEST, NEG, fake_fp8, mm, rope

CONTROL = "fp8"
NORM = "rmsnorm"
QUERY_BLOCK = 256  # query positions a block; the reference length is a multiple of 256


def _check(cfg: dict) -> None:
    arch = cfg["architecture"]
    if arch["norm"] != NORM or arch["tie_word_embeddings"] or arch["family"] != "dense":
        raise ValueError(f"this reference is Mistral-NeMo's ({NORM} with a scale, untied head), not {arch}")


def layout(cfg: dict) -> dict:
    """``{path: leaf}`` of every weight (L layers): ``embed.tok (V, D)``,
    ``lm_head (D, V)``, ``blocks.attn.{wq (L, D, H, hd), wk (L, D, KV, hd),
    wv (L, D, KV, hd), wo (L, H, hd, D)}``, ``blocks.mlp.{w_gate (L, D, F),
    w_up (L, D, F), w_down (L, F, D)}``, and the RMSNorm scales
    ``blocks.norm1/norm2.scale (L, D)`` and ``final_norm.scale (D,)``, drawn
    around 1."""
    _check(cfg)
    L, D, F, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {
        "embed/tok": ((V, D), 1.0),
        "lm_head": ((D, V), 1 / math.sqrt(D)),
        "blocks/attn/wq": ((L, D, H, hd), 1 / math.sqrt(D)),
        "blocks/attn/wk": ((L, D, KV, hd), 1 / math.sqrt(D)),
        "blocks/attn/wv": ((L, D, KV, hd), 1 / math.sqrt(D)),
        "blocks/attn/wo": ((L, H, hd, D), 1 / math.sqrt(H * hd)),
        "blocks/mlp/w_gate": ((L, D, F), 1 / math.sqrt(D)),
        "blocks/mlp/w_up": ((L, D, F), 1 / math.sqrt(D)),
        "blocks/mlp/w_down": ((L, F, D), 1 / math.sqrt(F)),
        "blocks/norm1/scale": ((L, D), 0.2, 1.0),
        "blocks/norm2/scale": ((L, D), 0.2, 1.0),
        "final_norm/scale": ((D,), 0.2, 1.0),
    }


def program_sizes(cfg: dict) -> dict:
    """The registry entry's fields set from the configuration file."""
    return dict(
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["architecture"]["norm_eps"],
    )


def program_fields(cfg: dict) -> dict:
    """The fields the registry entry has to state already: this forward's
    architecture under the program's names."""
    _check(cfg)
    arch = cfg["architecture"]
    return {
        "family": arch["family"],
        "norm": NORM,
        "activation": arch["activation"],
        "tie_embeddings": False,
        "use_bias": arch["attention_bias"],
        "rotary_pct": 1.0,
        "sliding_window": 0,
        "qk_norm": False,
        "parallel_residual": False,
        "scale_embedding": False,
        "attn_logit_softcap": 0.0,
    }


def blocked_causal_attention(q, k, v, block: int = QUERY_BLOCK):
    """Grouped-query causal softmax attention over positions 0..S-1, one
    block of ``block`` query positions at a time (``lax.map``), each against
    every key: query head ``h`` reads K/V head ``h // (H / KV)``. q (B, S, H,
    hd), k and v (B, S, KV, hd), S a multiple of ``block``; returns (B, S, H,
    hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if S % block:
        raise ValueError(f"{S} positions are not whole blocks of {block}")
    n = S // block
    qb = jnp.moveaxis(q.reshape(B, n, block, KV, H // KV, hd), 1, 0)  # (n, B, block, KV, g, hd)
    keys = jnp.arange(S)

    def one(args):
        i, qi = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=HIGHEST) / math.sqrt(hd)
        causal = (i * block + jnp.arange(block))[:, None] >= keys[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, (jnp.arange(n), qb))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def _norm(x, scale, eps: float):
    """RMSNorm with a learned scale."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _layer(cfg: dict, quant, x, lp):
    eps = cfg["architecture"]["norm_eps"]
    a = lp["attn"]
    h = _norm(x, lp["norm1"]["scale"], eps)
    q = mm("bsd,dhk->bshk", h, a["wq"], quant, 0)
    k = mm("bsd,dhk->bshk", h, a["wk"], quant, 0)
    v = mm("bsd,dhk->bshk", h, a["wv"], quant, 0)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    if quant == CONTROL:  # the cache holds K/V in the lower precision
        k, v = fake_fp8(k, -1), fake_fp8(v, -1)
    x = x + mm("bshk,hkd->bsd", blocked_causal_attention(q, k, v), a["wo"], quant, (0, 1))
    m = lp["mlp"]
    h = _norm(x, lp["norm2"]["scale"], eps)
    g = mm("bsd,df->bsf", h, m["w_gate"], quant, 0)
    u = mm("bsd,df->bsf", h, m["w_up"], quant, 0)
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], quant, 0), None


@partial(jax.jit, static_argnums=(0, 1))
def _hidden(cfg_key, quant, params, tokens):
    cfg = json.loads(cfg_key)
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_layer, cfg, quant), x, params["blocks"])
    return _norm(x, params["final_norm"]["scale"], cfg["architecture"]["norm_eps"])


def hidden(cfg: dict, quant, params, tokens):
    """Final hidden states (B, S, D) in float32, after the final norm."""
    keep = ("architecture", "rope_theta")
    return _hidden(json.dumps({k: cfg[k] for k in keep}, sort_keys=True), quant, params, tokens)


def head(params, h, quant):
    """Logits (B, S, V) in float32 of final hidden states, through the
    untied head; traced inside the harness's own jitted comparison."""
    return mm("bsd,dv->bsv", h, params["lm_head"], quant, 0)
