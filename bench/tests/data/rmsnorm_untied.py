"""A reference module of another architecture, for the test that adds a
configuration by new files alone: Mistral-style pre-norm decoder layers with
RMSNorm and a learned scale, rotate-half RoPE, grouped-query causal softmax
attention, a SwiGLU MLP, a final RMSNorm and an untied output head. The
interface is the one ``spec.reference`` documents."""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.references.common import causal_attention, fake_fp8, mm, rope

CONTROL = "fp8"


def layout(cfg: dict) -> dict:
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
    return {
        "family": "dense",
        "norm": "rmsnorm",
        "activation": "swiglu",
        "tie_embeddings": False,
        "use_bias": False,
        "qk_norm": False,
        "parallel_residual": False,
        "attn_logit_softcap": 0.0,
    }


def _norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _layer(cfg: dict, quant, x, lp):
    eps = cfg["architecture"]["norm_eps"]
    a = lp["attn"]
    h = _norm(x, lp["norm1"]["scale"], eps)
    q = mm("bsd,dhk->bshk", h, a["wq"], quant, 0)
    k = mm("bsd,dhk->bshk", h, a["wk"], quant, 0)
    v = mm("bsd,dhk->bshk", h, a["wv"], quant, 0)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    if quant == CONTROL:
        k, v = fake_fp8(k, -1), fake_fp8(v, -1)
    x = x + mm("bshk,hkd->bsd", causal_attention(q, k, v), a["wo"], quant, (0, 1))
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
    keep = ("architecture", "rope_theta")
    return _hidden(json.dumps({k: cfg[k] for k in keep}, sort_keys=True), quant, params, tokens)


def head(params, h, quant):
    return mm("bsd,dv->bsv", h, params["lm_head"], quant, 0)
