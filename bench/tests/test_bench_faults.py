"""Whole runs on the CPU at a tiny size, past the harness's look for a chip:
a sound run is correct, the run with its lower-precision control in the
program's place is not, and a token altered where the model step produces it
makes ``correct`` false.

The tiny cells are the real cells' files with every size cut
(``bench/tests/tiny.py``); the limit there is the tiny size's own, set
between the sound readings (under 0.01) and the control's (above 0.1)."""

import time

from bench import harness
from bench.tests.tiny import alter_served_tokens, tiny_cell

CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
TINY_LIMIT = 0.05


def tiny_run(workload, seed, **kw):
    return harness.run(
        workload, seed=seed, seconds=3.0, trace=False, t_start=time.perf_counter(),
        cell=tiny_cell(workload, limit=TINY_LIMIT), require_tpu=False, peaks_table=CPU_PEAKS, **kw,
    )


def test_sound_run_is_correct_and_its_control_is_not():
    out = tiny_run("olmo-1b.shared-prefix", 2**31 + 11, control=True)
    cal = out["_calibration"]
    assert cal["program_correct"], cal["gaps"]
    assert not out["correct"], out["checks"]
    assert out["checks"]["served_logit_gap"]["value"] == max(cal["control_gaps"]) > TINY_LIMIT
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_s", "itl_p95_s", "setup_s"}
    assert sum(cal["sample_tokens"]) >= 50
    assert max(cal["gaps"]) <= TINY_LIMIT


def test_token_altered_in_the_model_step_is_caught(monkeypatch):
    alter_served_tokens(monkeypatch)
    out = tiny_run("olmo-1b.offline", 2**31 + 12)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["value"] > TINY_LIMIT


def test_sound_backlog_run_is_correct():
    out = tiny_run("olmo-1b.offline", 2**31 + 13)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"output_tok_per_s", "setup_s"}
    assert out["metrics"]["output_tok_per_s"]["value"] > 0
