"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e at real widths.

Nothing runs: the TPU compiler is handed shapes for a chip that is described,
not attached, and raises what the chip's compiler would raise — misaligned
tiles, VMEM overruns — which interpret mode cannot catch.  Each test checks
that the compiled program holds the kernel (``tpu_custom_call``) and not a
fallback.  The backend still reports the CPU here, so the kernels are called
with ``interpret=False``.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.fp8.gemm import fp8_gemm
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import paged_attention_bhd, paged_prefill_attention_bhd


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can't
    # be read back without a chip: keep the cache off around these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _reads_pool_in_place(compiled, pool) -> bool:
    """No temporary as large as one pool: the kernel DMAs blocks from the
    pool where it lies instead of from a relaid-out copy of it."""
    pool_bytes = pool.size * pool.dtype.itemsize
    return compiled.memory_analysis().temp_size_in_bytes < pool_bytes


# one chip's paged pool: 2048 blocks of 16 tokens.  (q heads, kv heads):
# olmo-1b whole (16 MHA heads), and mistral-nemo-12b's per-chip share of a
# TP=4 deployment (32/8 heads over 4 chips)
PAGED_HEADS = {"olmo-1b": (16, 16), "mistral-nemo-12b-tp4-share": (8, 2)}
POOL_BLOCKS, BLOCK, HEAD_DIM, BATCH, TABLE = 2048, 16, 128, 8, 128


def _paged_shapes(one_chip, H, KV, q_shape):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((POOL_BLOCKS, BLOCK, KV, HEAD_DIM), jnp.bfloat16)
    return (
        S(q_shape, jnp.bfloat16),
        pool,
        pool,
        S((BATCH, TABLE), jnp.int32),
        S((BATCH,), jnp.int32),
    )


# the olmo-1b.offline deployment's decode: 64 slots, a 128-block table (2048
# tokens), 2048 usable blocks and the null block
DECODE_BATCH, DECODE_POOL_BLOCKS = 64, 2049


@pytest.mark.parametrize("heads", sorted(PAGED_HEADS))
def test_paged_decode_compiles(one_chip, heads):
    """The decode kernel at the deployment's shapes DMAs pool blocks where
    they lie: no temporary at all, let alone a pool-sized one."""
    H, KV = PAGED_HEADS[heads]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((DECODE_POOL_BLOCKS, BLOCK, KV, HEAD_DIM), jnp.bfloat16)
    shapes = (
        S((DECODE_BATCH, H, HEAD_DIM), jnp.bfloat16),
        pool,
        pool,
        S((DECODE_BATCH, TABLE), jnp.int32),
        S((DECODE_BATCH,), jnp.int32),
    )
    compiled = _compile(lambda *a: paged_attention_bhd(*a, interpret=False), *shapes)
    assert _has_kernel(compiled)
    assert _reads_pool_in_place(compiled, shapes[1])
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_decode_step_compiles_with_kernel(one_chip, monkeypatch):
    """The whole olmo-1b decode program at the deployment's shapes, with the
    decode choice a TPU makes: it holds the kernel, and none of the oracle's
    gathered K/V (``[B * nb, bs, KV, hd]``) in f32 or in the pool's dtype."""
    import importlib

    from repro.configs import get_config
    from repro.kernels import paged_attention_ops
    from repro.models import abstract_params, decode_step, init_paged_cache

    monkeypatch.setattr(paged_attention_ops, "on_tpu", lambda: True)
    # the module, not the same-named wrapper ``repro.kernels`` re-exports
    kernel_module = importlib.import_module("repro.kernels.paged_attention")
    monkeypatch.setattr(kernel_module, "resolve_interpret", lambda interpret=None: False)
    cfg = get_config("olmo-1b")
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    params = place(abstract_params(cfg, jnp.bfloat16))
    cache = place(
        jax.eval_shape(
            lambda: init_paged_cache(cfg, DECODE_POOL_BLOCKS, BLOCK, DECODE_BATCH, TABLE, jnp.bfloat16)
        )
    )
    token = jax.ShapeDtypeStruct((DECODE_BATCH, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((DECODE_BATCH,), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t, q: decode_step(cfg, p, c, t, q), donate_argnums=(1,))
    text = step.lower(params, cache, token, pos).compile().as_text()
    assert "tpu_custom_call" in text
    gathered = f"[{DECODE_BATCH * TABLE},{BLOCK},{cfg.num_kv_heads},{cfg.head_dim}]"
    assert f"f32{gathered}" not in text and f"bf16{gathered}" not in text


@pytest.mark.parametrize("heads", sorted(PAGED_HEADS))
def test_paged_prefill_compiles(one_chip, heads):
    """Chunked prefill and speculative verify share this kernel; 256 is the
    widest chunk a 256-token prefill budget produces."""
    H, KV = PAGED_HEADS[heads]
    shapes = _paged_shapes(one_chip, H, KV, (BATCH, 256, H, HEAD_DIM))
    compiled = _compile(lambda *a: paged_prefill_attention_bhd(*a, interpret=False), *shapes)
    assert _has_kernel(compiled)
    assert _reads_pool_in_place(compiled, shapes[1])


def test_paged_prefill_row_tiles_fit_vmem(one_chip):
    """A 1024-token chunk (a 1024-token prefill budget) at olmo-1b width has
    16 x 1024 query rows: too many for one tile in VMEM, so the kernel must
    split them into row tiles by itself."""
    H, KV = PAGED_HEADS["olmo-1b"]
    shapes = _paged_shapes(one_chip, H, KV, (1, 1024, H, HEAD_DIM))
    compiled = _compile(lambda *a: paged_prefill_attention_bhd(*a, interpret=False), *shapes)
    assert _has_kernel(compiled)


def test_flash_attention_compiles(one_chip):
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    x = S((1, 16, 2048, 128))
    compiled = _compile(lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False), x, x, x)
    assert _has_kernel(compiled)


def test_fp8_gemm_compiles(one_chip):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    M, K, N = 4096, 2048, 8192
    compiled = _compile(
        lambda a, b, sa, sb: fp8_gemm(a, b, sa, sb, interpret=False),
        S((M, K), jnp.float8_e4m3fn),
        S((K, N), jnp.float8_e4m3fn),
        S((), jnp.float32),
        S((), jnp.float32),
    )
    assert _has_kernel(compiled)
