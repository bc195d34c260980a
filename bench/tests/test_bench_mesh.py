"""A cell on four chips runs through ``harness.run`` as one engine over the
program's serving mesh: on four virtual CPU devices (a subprocess, since the
device count is fixed when JAX starts) a sound run is correct, and one whose
row-parallel projection keeps each device's partial sum, with the all-reduce
left out, is not."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY_LIMIT = 0.05


@pytest.fixture(scope="module")
def four_chip_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "four_chips.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_four_chip_run_is_correct(four_chip_runs):
    sound = four_chip_runs["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["served_logit_gap"]["value"] <= TINY_LIMIT
    assert sound["device"]["count"] == 4
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["metrics"]["output_tok_per_s"]["value"] > 0


def test_weights_are_placed_over_the_model_axis(four_chip_runs):
    placed = four_chip_runs["placed"]
    assert {n for n, _ in placed.values()} == {4}
    assert "model" in placed["['blocks']['mlp']['w_down']"][1]
    assert "model" in placed["['blocks']['attn']['wq']"][1]


def test_missing_all_reduce_is_caught(four_chip_runs):
    faulty = four_chip_runs["no_allreduce"]
    assert not faulty["correct"]
    assert faulty["checks"]["served_logit_gap"]["value"] > TINY_LIMIT
