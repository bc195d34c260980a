"""OLMo's plain reference forward, its control, its weight layout and the
fields the program's registry entry has to state.

Written from the published architecture, not from the program: pre-norm
decoder layers with OLMo's non-parametric LayerNorm, rotate-half RoPE over
the whole head, grouped-query causal softmax attention, a SwiGLU MLP, a final
norm and the output head, which is the embedding table (tied). It imports
nothing of the program and runs on the benchmark's own weights
(``bench/weights.py``, built from ``layout``).

``quant=None`` computes in float32 at ``HIGHEST`` matmul precision: the
reference. ``quant=CONTROL`` is the control: the same forward with every
matmul input and every cached K/V row (per token and head) rounded to float8
e4m3 (``common.fake_fp8``). That is the step below the configuration's bf16
that a later change might take; the comparison has to fail it.

The layers run under one ``lax.scan``, so only one layer's weights are in
float32 at a time.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.references.common import causal_attention, fake_fp8, mm, rope

CONTROL = "fp8"
NORM = "layernorm_nonparametric"


def _check(cfg: dict) -> None:
    arch = cfg["architecture"]
    if arch["norm"] != NORM or not arch["tie_word_embeddings"] or arch["family"] != "dense":
        raise ValueError(f"this reference is OLMo's ({NORM}, tied head), not {arch}")


def layout(cfg: dict) -> dict:
    """``{path: (shape, std)}`` of every weight, and ``{}`` for the norm
    nodes, which hold none (L layers): ``embed.tok (V, D)``,
    ``blocks.attn.{wq (L, D, H, hd), wk (L, D, KV, hd), wv (L, D, KV, hd),
    wo (L, H, hd, D)}``, ``blocks.mlp.{w_gate (L, D, F), w_up (L, D, F),
    w_down (L, F, D)}``, and empty ``blocks.norm1/norm2`` and
    ``final_norm``."""
    _check(cfg)
    L, D, F, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {
        "embed/tok": ((V, D), 1 / math.sqrt(D)),
        "blocks/attn/wq": ((L, D, H, hd), 1 / math.sqrt(D)),
        "blocks/attn/wk": ((L, D, KV, hd), 1 / math.sqrt(D)),
        "blocks/attn/wv": ((L, D, KV, hd), 1 / math.sqrt(D)),
        "blocks/attn/wo": ((L, H, hd, D), 1 / math.sqrt(H * hd)),
        "blocks/mlp/w_gate": ((L, D, F), 1 / math.sqrt(D)),
        "blocks/mlp/w_up": ((L, D, F), 1 / math.sqrt(D)),
        "blocks/mlp/w_down": ((L, F, D), 1 / math.sqrt(F)),
        "blocks/norm1": {},
        "blocks/norm2": {},
        "final_norm": {},
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
        "norm": "layernorm_np",
        "activation": arch["activation"],
        "tie_embeddings": True,
        "use_bias": arch["attention_bias"],
        "rotary_pct": 1.0,
        "sliding_window": 0,
        "qk_norm": False,
        "parallel_residual": False,
        "scale_embedding": False,
        "attn_logit_softcap": 0.0,
    }


def _norm(x, eps: float):
    """Non-parametric LayerNorm."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _layer(cfg: dict, quant, x, lp):
    eps = cfg["architecture"]["norm_eps"]
    a = lp["attn"]
    h = _norm(x, eps)
    q = mm("bsd,dhk->bshk", h, a["wq"], quant, 0)
    k = mm("bsd,dhk->bshk", h, a["wk"], quant, 0)
    v = mm("bsd,dhk->bshk", h, a["wv"], quant, 0)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    if quant == CONTROL:  # the cache holds K/V in the lower precision
        k, v = fake_fp8(k, -1), fake_fp8(v, -1)
    ctx = causal_attention(q, k, v)
    x = x + mm("bshk,hkd->bsd", ctx, a["wo"], quant, (0, 1))
    m = lp["mlp"]
    h = _norm(x, eps)
    g = mm("bsd,df->bsf", h, m["w_gate"], quant, 0)
    u = mm("bsd,df->bsf", h, m["w_up"], quant, 0)
    x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], quant, 0)
    return x, None


@partial(jax.jit, static_argnums=(0, 1))
def _hidden(cfg_key, quant, params, tokens):
    cfg = json.loads(cfg_key)
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(partial(_layer, cfg, quant), x, params["blocks"])
    return _norm(x, cfg["architecture"]["norm_eps"])


def hidden(cfg: dict, quant, params, tokens):
    """Final hidden states (B, S, D) in float32, after the final norm."""
    keep = ("architecture", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta")
    return _hidden(json.dumps({k: cfg[k] for k in keep}, sort_keys=True), quant, params, tokens)


def head(params, h, quant):
    """Logits (B, S, V) in float32 of final hidden states; traced inside the
    harness's own jitted comparison."""
    return mm("bsd,vd->bsv", h, params["embed"]["tok"], quant, 1)
