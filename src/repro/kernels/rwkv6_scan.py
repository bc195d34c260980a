"""RWKV6 WKV chunked scan — Pallas TPU kernel.

The one assigned architecture whose hot loop is NOT a matmul: Finch's
data-dependent-decay recurrence (arXiv:2404.05892).  The reference CUDA
kernel is a sequential per-(batch, head) scan; the TPU adaptation runs the
chunked-parallel formulation from ``models/rwkv.py`` inside one kernel:

* grid = (batch*heads, num_chunks); the chunk axis *revisits* a VMEM scratch
  carrying the (n x n) state matrix, so the recurrence crosses chunks without
  leaving VMEM;
* within a chunk everything is matmul/VPU-shaped: cumulative log-decays,
  pairwise-safe decay tensor (all exponents <= 0), two (chunk x n) dots and
  the rank-1 state update.

Operands arrive head-major (BH, S, n) so BlockSpecs are clean 1:1 tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention import pl_scratch
from repro.utils.platform import resolve_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # (chunk, n)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # log decays, <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, n) bonus
    S0 = state_ref[...]  # (n, n) fp32

    la = jnp.cumsum(lw, axis=0)  # inclusive
    la_prev = la - lw  # exclusive

    # inter-chunk: r~_t = r_t * exp(la_{t-1}); out_inter = r~ @ S0
    r_dec = r * jnp.exp(la_prev)
    out = jax.lax.dot(r_dec, S0)  # (chunk, n)

    # intra-chunk: scores_ts = sum_c r_t[c] k_s[c] exp(la_{t-1}[c] - la_s[c]), s < t
    dd = la_prev[:, None, :] - la[None, :, :]  # (t, s, n) <= 0 for s < t
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    strict = t_idx > s_idx
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(dd), axis=-1)
    scores = jnp.where(strict, scores, 0.0)
    out = out + jax.lax.dot(scores, v)

    # diagonal bonus: (r_t . (u * k_t)) v_t
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)
    out = out + diag * v

    # state update: S' = diag(exp(la_L)) S0 + sum_s exp(la_L - la_s) k_s v_s^T
    la_last = la[-1:]  # (1, n)
    k_dec = k * jnp.exp(la_last - la)
    state_ref[...] = jnp.exp(la_last).T * S0 + jax.lax.dot(k_dec.T, v)

    o_ref[0, ...] = out.astype(o_ref.dtype)


def wkv6_chunked(
    r: jax.Array,  # (BH, S, n)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (BH, S, n), log decay <= 0
    u: jax.Array,  # (BH, n) per-head bonus
    *,
    chunk: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    BH, S, n = r.shape
    assert S % chunk == 0, (S, chunk)
    grid = (BH, S // chunk)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n), lambda b, c: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, n), jnp.float32),
        scratch_shapes=[pl_scratch((n, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, logw, u)
