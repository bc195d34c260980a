"""The mistral-nemo-12b cell: its configuration file states the program's
registry entry at published widths, its reference module blocks attention
over query positions and agrees with the unblocked form, and its own files
run over four devices with grouped-query attention split per device (a
subprocess, since the device count is fixed when JAX starts): a sound run is
correct, its fp8 control is not, and a fault in the grouping of query heads
onto K/V heads is caught. Also the reader of ``collective_share``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, weights, work
from bench.program_trace import Op, ProgramTrace
from bench.references.common import causal_attention

ROOT = Path(__file__).resolve().parents[2]
CELL = "mistral-nemo-12b-tp4.rag"
LIMIT = 0.05  # bench/tests/nemo_four_chips.py's limit at its tiny sizes


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_is_the_program_entry_at_published_widths(cell):
    cfg, ref = cell.config, spec.reference(cell)
    pc = harness.program_config(cfg, ref)
    assert (pc.num_layers, pc.d_model, pc.num_heads, pc.num_kv_heads, pc.head_dim) == (40, 5120, 32, 8, 128)
    assert (pc.d_ff, pc.vocab_size, pc.rope_theta) == (14336, 131072, 1e6)
    assert pc.num_heads * pc.head_dim != pc.d_model  # the head width is its own
    assert cell.chips == 4 and cfg["mesh"] == {"data": 1, "model": 4} and cfg["reduced"] == []
    from repro.models import abstract_params

    weights.check_layout(ref.layout(cfg), abstract_params(pc, jnp.bfloat16))


def test_deployment_reckonings(cell):
    cfg, dep = cell.config, cell.config["deployment"]
    s = work.Shapes.of(cfg)
    params = s.layers * s.layer_params + 2 * s.head_params  # untied: embedding and head
    assert 12.2e9 < params < 12.3e9
    assert 6.0e9 < params * 2 / cell.chips < 6.2e9  # bf16 a chip
    per_chip_token = s.kv_bytes_per_token // cell.chips
    assert per_chip_token == 40 * 1024
    assert abs((dep["num_blocks"] - 1) * dep["block_size"] * per_chip_token / 2**30 - 3.0) < 0.01
    assert dep["max_seq"] % dep["block_size"] == 0 and dep["max_seq"] >= harness.ref_length(cell)


@pytest.mark.parametrize("block", [8, 16, 64])
def test_blocked_attention_equals_the_unblocked_form(cell, block):
    ref = spec.reference(cell)
    B, S, H, KV, hd = 2, 64, 8, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, hd), jnp.float32)
    got = ref.blocked_causal_attention(q, k, v, block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(causal_attention(q, k, v)), rtol=1e-5, atol=1e-5)


def test_blocked_attention_groups_query_heads_by_kv_head(cell):
    """Query head ``h`` reads K/V head ``h // (H / KV)``: with K/V head 1's
    values all ones and head 0's all zeros, query heads 0-3 read zeros and
    4-7 ones."""
    ref = spec.reference(cell)
    B, S, H, KV, hd = 1, 32, 8, 2, 4
    q = jax.random.normal(jax.random.PRNGKey(4), (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(5), (B, S, KV, hd))
    v = jnp.concatenate([jnp.zeros((B, S, 1, hd)), jnp.ones((B, S, 1, hd))], axis=2)
    out = np.asarray(ref.blocked_causal_attention(q, k, v, 8))
    np.testing.assert_allclose(out[:, :, :4], 0.0, atol=1e-6)
    np.testing.assert_allclose(out[:, :, 4:], 1.0, atol=1e-6)


def test_blocked_attention_refuses_a_partial_block(cell):
    ref = spec.reference(cell)
    x = jnp.zeros((1, 24, 2, 4))
    with pytest.raises(ValueError, match="whole blocks"):
        ref.blocked_causal_attention(x, x, x, 16)


def test_reference_of_another_architecture_is_refused(cell):
    ref = spec.reference(cell)
    olmo = spec.load_cell("olmo-1b.offline").config
    with pytest.raises(ValueError, match="Mistral-NeMo"):
        ref.layout(olmo)


@pytest.fixture(scope="module")
def four_chip_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "nemo_four_chips.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_gqa_run_over_four_devices_is_correct_and_its_control_is_not(four_chip_runs):
    sound = four_chip_runs["sound"]
    cal = sound["_calibration"]
    assert cal["program_correct"] and max(cal["gaps"]) <= LIMIT, cal["gaps"]
    assert not sound["correct"] and max(cal["control_gaps"]) > LIMIT, cal["control_gaps"]
    assert sound["device"]["count"] == 4
    assert sound["attempted"] > 0 and sound["failed"] == 0


def test_misgrouped_query_heads_are_caught(four_chip_runs):
    faulty = four_chip_runs["gqa_fault"]
    assert not faulty["correct"]
    assert faulty["checks"]["served_logit_gap"]["value"] > LIMIT


# -- collective_share --------------------------------------------------------


def op(start, end, name, program="jit_decode_step", leaf=True):
    return Op(start, end, name, program, 0, "", "unscoped", leaf=leaf)


def collective_share(pt):
    from bench.record import RunRecord

    rec = RunRecord(seconds=1.0, t_open=0.0, t_close=1.0, t_drained=1.0, chips=2, max_batch=1,
                    peak={}, shapes=None, setup_s=0.0, program=pt)
    return spec.metric_reader("collective_share.rag")(rec)


def test_collective_share_counts_the_decode_programs_collectives_only():
    dev0 = [
        op(0, 60, "fusion.1"),
        op(60, 80, "all-reduce.3"),
        op(80, 90, "all-gather-start.1"),
        op(90, 100, "collective-permute-done.2"),
        op(100, 300, "all-reduce.9", program="jit_chunk_step"),  # another program
        op(300, 400, "while.5", leaf=False),  # holds other ops: not counted
        op(400, 500, "reduce.4"),  # a local reduction, not a collective
    ]
    # device 0: 40 of 200 ns; device 1: 10 of 100 ns
    dev1 = [op(0, 90, "fusion.1"), op(90, 100, "reduce-scatter.2")]
    pt = ProgramTrace(ops={"/device:TPU:0": dev0, "/device:TPU:1": dev1})
    assert collective_share(pt) == pytest.approx(100 * (40 / 200 + 10 / 100) / 2)


def test_collective_share_without_collectives_is_zero_and_without_ops_none():
    pt = ProgramTrace(ops={"/device:TPU:0": [op(0, 50, "fusion.1"), op(50, 80, "copy.2")]})
    assert collective_share(pt) == 0.0
    assert collective_share(ProgramTrace()) is None
    other = ProgramTrace(ops={"/device:TPU:0": [op(0, 50, "all-reduce.1", program="jit_chunk_step")]})
    assert collective_share(other) is None
