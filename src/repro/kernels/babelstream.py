"""BabelStream — Pallas TPU memory-bandwidth kernels (paper Fig. 10).

The paper benchmarks GH200 HBM bandwidth with BabelStream across nine
programming models; this is the TPU-native tenth: each kernel streams
HBM->VMEM->HBM through 1-D BlockSpec tiles sized to keep several tiles in
flight (double-buffered by the Pallas pipeline).  The five classic kernels:

    copy   c = a            2 x N x sizeof  bytes
    mul    b = s * c        2 x
    add    c = a + b        3 x
    triad  a = b + s * c    3 x
    dot    s = sum(a * b)   2 x (+ partials)

``benchmarks/babelstream.py`` derives achievable-bandwidth fractions from
these byte counts against the 819 GB/s v5e HBM roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.platform import resolve_interpret

DEFAULT_BLOCK = 65_536  # elements per tile: 256 KiB f32 -> fits VMEM 2x-buffered


def _copy_kernel(a_ref, c_ref):
    c_ref[...] = a_ref[...]


def _mul_kernel(c_ref, b_ref, *, scalar: float):
    b_ref[...] = scalar * c_ref[...]


def _add_kernel(a_ref, b_ref, c_ref):
    c_ref[...] = a_ref[...] + b_ref[...]


def _triad_kernel(b_ref, c_ref, a_ref, *, scalar: float):
    a_ref[...] = b_ref[...] + scalar * c_ref[...]


def _dot_kernel(a_ref, b_ref, p_ref):
    p_ref[0] = jnp.sum(a_ref[...].astype(jnp.float32) * b_ref[...].astype(jnp.float32))


def _grid_1d(n: int, block: int):
    assert n % block == 0, (n, block)
    return (n // block,)


def _spec(block: int):
    return pl.BlockSpec((block,), lambda i: (i,))


def stream_copy(a: jax.Array, *, block: int = DEFAULT_BLOCK, interpret: bool | None = None) -> jax.Array:
    n = a.shape[0]
    return pl.pallas_call(
        _copy_kernel,
        grid=_grid_1d(n, block),
        in_specs=[_spec(block)],
        out_specs=_spec(block),
        out_shape=jax.ShapeDtypeStruct((n,), a.dtype),
        interpret=resolve_interpret(interpret),
    )(a)


def stream_mul(c: jax.Array, scalar: float = 0.4, *, block: int = DEFAULT_BLOCK, interpret: bool | None = None) -> jax.Array:
    n = c.shape[0]
    return pl.pallas_call(
        functools.partial(_mul_kernel, scalar=scalar),
        grid=_grid_1d(n, block),
        in_specs=[_spec(block)],
        out_specs=_spec(block),
        out_shape=jax.ShapeDtypeStruct((n,), c.dtype),
        interpret=resolve_interpret(interpret),
    )(c)


def stream_add(a: jax.Array, b: jax.Array, *, block: int = DEFAULT_BLOCK, interpret: bool | None = None) -> jax.Array:
    n = a.shape[0]
    return pl.pallas_call(
        _add_kernel,
        grid=_grid_1d(n, block),
        in_specs=[_spec(block), _spec(block)],
        out_specs=_spec(block),
        out_shape=jax.ShapeDtypeStruct((n,), a.dtype),
        interpret=resolve_interpret(interpret),
    )(a, b)


def stream_triad(b: jax.Array, c: jax.Array, scalar: float = 0.4, *, block: int = DEFAULT_BLOCK, interpret: bool | None = None) -> jax.Array:
    n = b.shape[0]
    return pl.pallas_call(
        functools.partial(_triad_kernel, scalar=scalar),
        grid=_grid_1d(n, block),
        in_specs=[_spec(block), _spec(block)],
        out_specs=_spec(block),
        out_shape=jax.ShapeDtypeStruct((n,), b.dtype),
        interpret=resolve_interpret(interpret),
    )(b, c)


def stream_dot(a: jax.Array, b: jax.Array, *, block: int = DEFAULT_BLOCK, interpret: bool | None = None) -> jax.Array:
    n = a.shape[0]
    partials = pl.pallas_call(
        _dot_kernel,
        grid=_grid_1d(n, block),
        in_specs=[_spec(block), _spec(block)],
        out_specs=pl.BlockSpec((1,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n // block,), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a, b)
    return jnp.sum(partials)


def stream_bytes(kernel: str, n: int, itemsize: int) -> int:
    """HBM bytes moved per kernel invocation (BabelStream convention)."""
    mult = {"copy": 2, "mul": 2, "add": 3, "triad": 3, "dot": 2}[kernel]
    return mult * n * itemsize
