"""No TPU, too few chips, or a chip missing from the peaks table: no run."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, spec

ROOT = Path(__file__).resolve().parents[2]


def test_no_tpu_is_refused():
    with pytest.raises(SystemExit, match="no TPU"):
        harness.check_devices(1)


def test_too_few_chips_is_refused():
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.check_devices(4, require_tpu=False)


def test_device_missing_from_peaks_is_refused():
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        spec.peaks("cpu")
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_entry_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.offline", "--seed", "3", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
