"""Pallas tiled FP8 GEMM: fp8 operand tiles, in-kernel dequant, fp32 accumulate.

Grid layout is the idiomatic TPU matmul formulation: a 3-D grid
``(M/bm, N/bn, K/bk)`` whose minormost (k) dimension *revisits* the output
block, carrying the running fp32 accumulator in VMEM scratch between k steps.
Tiles default to 128x128 — MXU-aligned (the systolic array is 128x128) and
comfortably VMEM-resident (an fp8 128x128 tile is 16 KiB; the fp32
accumulator 64 KiB).

The fp8 A/B tiles are upcast + dequantized *in-kernel*: the HBM->VMEM stream
moves 1 byte/element (the whole point of FP8 — half the bf16 wire/memory
traffic, and the MXU's fp8 throughput is 2x bf16 on GH200-class parts), while
every multiply-accumulate happens in fp32.  The per-tensor scales ride in
SMEM and divide the accumulator once, on the final k step.

Off-TPU the kernel runs in interpret mode (``utils.platform``); TPU is the
target.  ``repro.fp8.gemm_ref.fp8_gemm_ref`` is the pure-jnp oracle.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.platform import resolve_interpret

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk)


def _fp8_gemm_kernel(a_scale_ref, b_scale_ref, a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)  # (bm, bk) dequant deferred: scale is
    b = b_ref[...].astype(jnp.float32)  # (bk, bn) uniform, divide once at end
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finalize():
        inv = 1.0 / (a_scale_ref[0] * b_scale_ref[0])
        o_ref[...] = (acc_ref[...] * inv).astype(o_ref.dtype)


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


@partial(
    jax.jit, static_argnames=("block", "out_dtype", "interpret")
)
def fp8_gemm(
    a: jax.Array,  # (M, K) fp8
    b: jax.Array,  # (K, N) fp8
    a_scale: jax.Array,  # () fp32
    b_scale: jax.Array,  # () fp32
    *,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:
    """Dequantizing FP8 GEMM: returns ``(a/a_scale) @ (b/b_scale)``.

    Shapes need not be multiples of the block sizes — operands are
    zero-padded up (fp8 zero is exact, padding contributes nothing) and the
    output sliced back.
    """
    (M, K), (K2, N) = a.shape, b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = (min(block[0], M), min(block[1], N), min(block[2], K))
    Mp, Np, Kp = -(-M // bm) * bm, -(-N // bn) * bn, -(-K // bk) * bk
    a = _pad_to(a, Mp, Kp)
    b = _pad_to(b, Kp, Np)
    scale_spec = pl.BlockSpec((1,), lambda i, j, k: (0,), memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _fp8_gemm_kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            scale_spec,
            scale_spec,
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a_scale.reshape(1).astype(jnp.float32), b_scale.reshape(1).astype(jnp.float32), a, b)
    return out[:M, :N]
