"""Feed-forward blocks: gated (SwiGLU/GeGLU) and plain (GELU/SiLU/ReLU^2)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ACTIVATIONS, ParamSpec


def is_gated(activation: str) -> bool:
    return activation in ("swiglu", "geglu")


def gate_fn(activation: str):
    if activation == "swiglu":
        return jax.nn.silu
    if activation == "geglu":
        return jax.nn.gelu
    return ACTIVATIONS[activation]


def ffn_specs(cfg, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    specs = {}
    if is_gated(cfg.activation):
        specs["w_gate"] = ParamSpec((D, F), ("embed", "mlp"))
        specs["w_up"] = ParamSpec((D, F), ("embed", "mlp"))
    else:
        specs["w_up"] = ParamSpec((D, F), ("embed", "mlp"))
    specs["w_down"] = ParamSpec((F, D), ("mlp", "embed"))
    if cfg.use_bias:
        specs["b_up"] = ParamSpec((F,), ("mlp",), "zeros")
        specs["b_down"] = ParamSpec((D,), ("embed",), "zeros")
    return specs


@jax.named_scope("mlp")
def ffn(cfg, p: dict, x: jax.Array, *, sh=None, fp8=None) -> jax.Array:
    """``fp8``: an ``repro.fp8.Fp8Ctx`` — routes the up/gate/down GEMMs
    through quantized matmuls (biases/activation stay in compute dtype)."""
    act = gate_fn(cfg.activation)
    if fp8 is not None:
        up = fp8.matmul("ffn_up", x, p["w_up"])
    else:
        up = x @ p["w_up"].astype(x.dtype)
    if cfg.use_bias:
        up = up + p["b_up"].astype(x.dtype)
    if is_gated(cfg.activation):
        if fp8 is not None:
            gate = act(fp8.matmul("ffn_gate", x, p["w_gate"]))
        else:
            gate = act(x @ p["w_gate"].astype(x.dtype))
        h = gate * up
    else:
        h = act(up)
    if sh is not None:
        h = sh(h, ("batch", "seq", "mlp"))
    if fp8 is not None:
        out = fp8.matmul("ffn_down", h, p["w_down"])
    else:
        out = h @ p["w_down"].astype(x.dtype)
    if cfg.use_bias:
        out = out + p["b_down"].astype(x.dtype)
    return out
