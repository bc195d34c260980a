"""The mistral-nemo-12b cell's own files at tiny sizes over four devices
(``chips`` 4, ``mesh.model`` 4), past the harness's look for a chip. Grouped
query attention stays grouped on every device: 16 query heads over 4 K/V
heads, 4 query heads to a K/V head, so each device holds 4 query heads and
the one K/V head they read; the head width (16) is apart from d_model (64).
Two runs: a sound one with the fp8 control beside it, and one in which the
program's attention has query head ``h`` read K/V head ``h % KV`` instead of
``h // (H / KV)``. Prints one JSON line: ``{"sound": {...}, "gqa_fault":
{...}}``, each with ``correct``, ``checks``, ``device``, ``attempted`` and
``failed``, the sound run with its ``_calibration`` too.

Needs four devices before JAX starts; on the CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/nemo_four_chips.py
"""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "mistral-nemo-12b-tp4.rag"
SEED = 2**31 + 171
LIMIT = 0.05
SIZES = {"num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 16, "hidden_size": 64}


def tiny_gqa_cell():
    """The cell's configuration and mix cut as ``tiny.tiny_cell`` cuts them,
    with the heads of ``SIZES`` and the cell's own four-chip mesh."""
    from bench import spec
    from bench.tests.tiny import tiny_cell

    real = spec.load_cell(CELL)
    cut = tiny_cell(CELL, limit=LIMIT)
    cfg = copy.deepcopy(cut.config)
    cfg.update(SIZES)
    cfg["mesh"] = real.config["mesh"]
    return dataclasses.replace(cut, config=cfg, chips=real.chips)


def misgrouped(oracle):
    """``oracle`` (the program's jnp attention, query heads on axis -2) with
    query head ``h`` reading K/V head ``h % KV``: the queries are laid out so
    that the oracle's own grouping ``j // qpk`` pairs them that way, and its
    output laid back."""
    import jax.numpy as jnp

    def attend(q, k_pool, *a, **k):
        H, KV = q.shape[-2], k_pool.shape[2]
        qpk = H // KV
        order = jnp.asarray([(j // qpk) + KV * (j % qpk) for j in range(H)])  # head read at slot j
        out = oracle(jnp.take(q, order, axis=-2), k_pool, *a, **k)
        return jnp.take(out, jnp.argsort(order), axis=-2)

    return attend


def main() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    from repro.kernels import paged_attention_ops, paged_attention_ref

    if jax.device_count() < 4:
        raise SystemExit(f"needs four devices, JAX found {jax.device_count()}")
    cell = tiny_gqa_cell()
    peaks = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    keep = ("correct", "checks", "device", "attempted", "failed", "_calibration")

    def run(**kw):
        out = harness.run(
            cell.name, seed=SEED, seconds=3.0, trace=False, t_start=time.perf_counter(), cell=cell,
            require_tpu=False, peaks_table=peaks, **kw,
        )
        return {k: out[k] for k in keep if k in out}

    sound = run(control=True)
    # decode attention (the oracle off the TPU) and the chunk path's oracle
    paged_attention_ops.paged_attention_ref = misgrouped(paged_attention_ref.paged_attention_ref)
    paged_attention_ref.paged_prefill_attention_ref = misgrouped(paged_attention_ref.paged_prefill_attention_ref)
    print(json.dumps({"sound": sound, "gqa_fault": run()}), flush=True)


if __name__ == "__main__":
    main()
