"""Shared model layers: norms, rotary embeddings, param-spec primitives.

Everything is a pure function over plain pytrees; ``ParamSpec`` trees describe
shapes/logical-axes/init so that the same tree definition serves
``init_params`` (real arrays), ``abstract_params`` (ShapeDtypeStructs for the
dry-run) and the sharding rule engine (logical axes -> PartitionSpec).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]  # logical axis names, len == ndim
    init: str = "fan_in"  # "fan_in" | "normal" | "zeros" | "ones" | "rwkv_decay" | "ssm_a" | "ssm_dt"
    scale: float = 1.0  # extra multiplier on the init stddev / value


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def spec_tree_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# initialization
# ---------------------------------------------------------------------------


def materialize(spec: ParamSpec, key: jax.Array, dtype) -> jax.Array:
    """Create a concrete parameter for ``spec``."""
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init == "normal":
        return (spec.scale * 0.02 * jax.random.normal(key, shape)).astype(dtype)
    if spec.init == "fan_in":
        fan_in = shape[0] if len(shape) == 1 else int(np.prod(shape[:-1]))
        # For stacked (layers, ...) params the leading "layers" dim is not fan-in.
        if len(shape) >= 3 and spec.axes and spec.axes[0] == "layers":
            fan_in = int(np.prod(shape[1:-1]))
        std = spec.scale / math.sqrt(max(fan_in, 1))
        return (std * jax.random.truncated_normal(key, -3.0, 3.0, shape)).astype(dtype)
    if spec.init == "rwkv_decay":
        # RWKV6 decay base: spread in [-6, -1] so exp(-exp(w)) spans slow/fast.
        n = shape[-1]
        ratio = jnp.arange(n) / max(n - 1, 1)
        base = -6.0 + 5.0 * ratio**0.7
        return jnp.broadcast_to(base, shape).astype(dtype)
    if spec.init == "ssm_a":
        # S4D-real init: A = -(1..N) per state channel.
        n = shape[-1]
        a = jnp.arange(1, n + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(a), shape).astype(dtype)
    if spec.init == "ssm_dt":
        # dt bias such that softplus(dt) ~ [1e-3, 1e-1] log-uniform.
        u = jax.random.uniform(key, shape)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return jnp.log(jnp.expm1(dt)).astype(dtype)  # inverse softplus
    raise ValueError(f"unknown init {spec.init!r}")


def init_tree(specs, key: jax.Array, dtype) -> Any:
    """Materialize a whole ParamSpec tree with per-leaf folded keys."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = [materialize(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def abstract_tree(specs, dtype) -> Any:
    return spec_tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_specs(cfg, d: int | None = None) -> dict:
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones")}
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec((d,), ("embed",), "ones"), "bias": ParamSpec((d,), ("embed",), "zeros")}
    if cfg.norm == "layernorm_np":  # OLMo: non-parametric LN
        return {}
    raise ValueError(f"unknown norm {cfg.norm!r}")


@jax.named_scope("norm")
def apply_norm(cfg, p: dict, x: jax.Array) -> jax.Array:
    """Normalize in fp32, cast back to input dtype (standard LM practice)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
        xf = xf * p["scale"].astype(jnp.float32)
    else:  # layernorm / layernorm_np
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        if p:  # parametric
            xf = xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return xf.astype(dtype)


def group_norm_heads(x: jax.Array, scale: jax.Array, bias: jax.Array, num_heads: int, eps: float) -> jax.Array:
    """GroupNorm with one group per head over the channel dim (RWKV ln_x)."""
    dtype = x.dtype
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, num_heads, d // num_heads)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(*lead, d) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return xf.astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, rotary_pct: float, theta: float) -> jax.Array:
    """Inverse frequencies for the rotated fraction of the head dim."""
    rot_dim = int(head_dim * rotary_pct) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))


def apply_rope(x: jax.Array, positions: jax.Array, *, rotary_pct: float = 1.0, theta: float = 10000.0) -> jax.Array:
    """Apply RoPE. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    rot_dim = int(head_dim * rotary_pct) // 2 * 2
    inv_freq = rope_frequencies(head_dim, rotary_pct, theta)
    # angles: (..., seq, rot_dim/2)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]  # broadcast over heads
    cos = cos[..., None, :]
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = x_rot[..., : rot_dim // 2], x_rot[..., rot_dim // 2 :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}
