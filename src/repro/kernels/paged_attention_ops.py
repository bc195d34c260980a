"""Jit'd public wrappers for the paged-attention kernels (decode + chunked
prefill).

Routes fp pools through the Pallas kernels (interpret mode off-TPU, chosen
by ``utils.platform``);
quantized (int8/fp8) pools with per-(token, head) scales fall back to the
dequantizing jnp reference.  The serving path's decode attention is chosen
in one place, ``decode_uses_kernel``: the kernel on a TPU, the oracle
elsewhere (``paged_decode``).

Dtype contract: the pool dtype selects the path, and the two must never
mix — fp entry points raise on quantized pools (scales are required:
``paged_decode``, ``*_quantized``), and the quantized paths expect the exact
``serving.kvquant`` layout (int8/e4m3 ``k``/``v`` + fp32 per-(token, head)
``k_scale``/``v_scale``).  The chunked-prefill wrappers serve both the
prefill chunks and the speculative-decoding verify pass
(``models.verify_step``) — same kernel, different caller.

Layout choices (decode kernel vs C=1 prefill kernel, prefill query-row
tiling) come from the ``kernels.autotune`` cache, consulted at trace time —
shapes are static under ``jax.jit``, so each compiled graph bakes in one
tuned layout.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import autotune
from repro.kernels.paged_attention import paged_attention_bhd, paged_prefill_attention_bhd
from repro.kernels.paged_attention_ref import paged_attention_ref, paged_prefill_attention_ref
from repro.utils.platform import on_tpu


def _quantized_pool(dtype) -> bool:
    from repro.serving.kvquant import is_quantized_kv

    return is_quantized_kv(dtype)


def model_axis_size(mesh) -> int:
    """Size of the tensor-parallel ("model") axis; 1 when no mesh is active."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("model", 1))


def kernel_shardable(mesh, num_q_heads: int, num_kv_heads: int) -> bool:
    """A Pallas call is opaque to GSPMD, so on a multi-device mesh the kernel
    must run per-shard under ``shard_map`` on its local head slice.  That
    requires BOTH head counts to divide the model axis (the contiguous
    per-device q-head slice then stays aligned with its GQA kv group).
    The engine refuses ``attn_impl="pallas"`` on a mesh where this is False."""
    tp = model_axis_size(mesh)
    if tp <= 1:
        return True
    return num_q_heads % tp == 0 and num_kv_heads % tp == 0


def decode_uses_kernel(pool_dtype, mesh, num_q_heads: int, num_kv_heads: int) -> bool:
    """The one rule for paged decode attention: the Pallas kernel on a TPU,
    for an unquantized pool whose heads split over the mesh's model axis;
    the jnp oracle everywhere else (the CPU, int8/fp8 pools, which keep
    the dequantizing reference, and head counts the mesh can't split).
    A test steers the choice by patching this module's ``on_tpu``, which
    leaves the kernels' interpret mode to ``utils.platform``."""
    return (
        on_tpu()
        and not _quantized_pool(pool_dtype)
        and kernel_shardable(mesh, num_q_heads, num_kv_heads)
    )


def _tp_dispatch(mesh, kernel, q_spec, num_q_heads: int, num_kv_heads: int):
    """One TP dispatch rule for both paged kernels: per-shard ``shard_map``
    on the local head slice.  Head counts that don't divide the model axis
    raise: running the jnp reference instead would hide that the kernel never
    ran.  ``q_spec`` is the query (and output) PartitionSpec — the only thing
    that differs between the decode (B, H, hd) and chunked-prefill
    (B, C, H, hd) entry points."""
    if not kernel_shardable(mesh, num_q_heads, num_kv_heads):
        raise ValueError(
            f"Pallas paged attention can't take a head slice: {num_q_heads} q / "
            f"{num_kv_heads} kv heads over a model axis of {model_axis_size(mesh)}"
        )
    pool = P(None, None, "model", None)  # every block, local head slice
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(q_spec, pool, pool, P(None, None), P(None)),
        out_specs=q_spec,
        check_vma=False,
    )


@partial(jax.jit, static_argnames=("softcap", "window", "mesh"))
def paged_attention(
    q: jax.Array,  # (B, H, hd) current-token queries
    k_pool: jax.Array,  # (N, bs, KV, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32
    seq_lens: jax.Array,  # (B,) int32, >= 1
    *,
    softcap: float = 0.0,
    window: int = 0,
    mesh=None,
) -> jax.Array:
    if _quantized_pool(k_pool.dtype):
        raise ValueError("quantized pools need scales: use paged_decode")
    tuned = autotune.get_config(
        k_pool.shape[3], k_pool.shape[1], block_tables.shape[1], k_pool.dtype
    )
    if tuned["decode_kernel"] == "prefill1":
        # C=1 prefill layout: start = seq_lens - 1 makes the causal/window
        # masks degenerate to the decode masks exactly
        base = partial(
            paged_prefill_attention_bhd,
            softcap=softcap,
            window=window,
            rows_per_tile=tuned["prefill_rows_per_tile"],
        )

        def kernel(qq, kk, vv, tbl, lens):
            return base(qq[:, None], kk, vv, tbl, lens - 1)[:, 0]

    else:
        kernel = partial(paged_attention_bhd, softcap=softcap, window=window)
    if model_axis_size(mesh) > 1:
        kernel = _tp_dispatch(
            mesh,
            kernel,
            P(None, "model", None),
            q.shape[1],
            k_pool.shape[2],
        )
    return kernel(q, k_pool, v_pool, block_tables, seq_lens)


def paged_decode(
    q: jax.Array,  # (B, H, hd) current-token queries
    k_pool: jax.Array,  # (N, bs, KV, hd), fp or int8/fp8
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32
    seq_lens: jax.Array,  # (B,) int32, >= 1
    *,
    k_scale: jax.Array | None = None,  # (N, bs, KV, 1) fp32 (quantized pools)
    v_scale: jax.Array | None = None,
    softcap: float = 0.0,
    window: int = 0,
    mesh=None,
) -> jax.Array:
    """The serving path's decode attention, kernel or oracle by
    ``decode_uses_kernel``."""
    if decode_uses_kernel(k_pool.dtype, mesh, q.shape[1], k_pool.shape[2]):
        return paged_attention(
            q, k_pool, v_pool, block_tables, seq_lens, softcap=softcap, window=window, mesh=mesh
        )
    return paged_attention_ref(
        q,
        k_pool,
        v_pool,
        block_tables,
        seq_lens,
        softcap=softcap,
        window=window,
        k_scale=k_scale,
        v_scale=v_scale,
    )


@partial(jax.jit, static_argnames=("softcap", "window", "mesh"))
def paged_prefill_attention(
    q: jax.Array,  # (B, C, H, hd) chunk queries
    k_pool: jax.Array,  # (N, bs, KV, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32
    start: jax.Array,  # (B,) int32 absolute position of the chunk's first token
    *,
    softcap: float = 0.0,
    window: int = 0,
    mesh=None,
) -> jax.Array:
    if _quantized_pool(k_pool.dtype):
        raise ValueError("quantized pools need scales: use paged_prefill_attention_quantized")
    tuned = autotune.get_config(
        k_pool.shape[3], k_pool.shape[1], block_tables.shape[1], k_pool.dtype
    )
    kernel = partial(
        paged_prefill_attention_bhd,
        softcap=softcap,
        window=window,
        rows_per_tile=tuned["prefill_rows_per_tile"],
    )
    if model_axis_size(mesh) > 1:
        kernel = _tp_dispatch(
            mesh,
            kernel,
            P(None, None, "model", None),
            q.shape[2],
            k_pool.shape[2],
        )
    return kernel(q, k_pool, v_pool, block_tables, start)


@partial(jax.jit, static_argnames=("softcap", "window"))
def paged_prefill_attention_quantized(
    q: jax.Array,
    k_pool: jax.Array,  # int8 (N, bs, KV, hd)
    v_pool: jax.Array,
    k_scale: jax.Array,  # fp32 (N, bs, KV, 1)
    v_scale: jax.Array,
    block_tables: jax.Array,
    start: jax.Array,
    *,
    softcap: float = 0.0,
    window: int = 0,
) -> jax.Array:
    return paged_prefill_attention_ref(
        q,
        k_pool,
        v_pool,
        block_tables,
        start,
        softcap=softcap,
        window=window,
        k_scale=k_scale,
        v_scale=v_scale,
    )
