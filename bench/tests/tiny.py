"""Tiny cells for the CPU tests: the real configuration and mix files with
every size cut down, so a whole run takes seconds on the CPU backend."""

from __future__ import annotations

import copy

from bench import spec

TINY_SIZES = {
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "vocab_size": 256,
}
TINY_DEPLOYMENT = {"max_batch": 4, "max_seq": 256, "block_size": 16, "num_blocks": 65, "prefill_budget": 32}
TINY_LENGTHS = {
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 64},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
}


def tiny_cell(workload: str, *, rate_per_s: float = 4.0, limit: float = 1.0) -> spec.Cell:
    real = spec.load_cell(workload)
    cfg = copy.deepcopy(real.config)
    cfg.update(TINY_SIZES)
    cfg["deployment"].update(TINY_DEPLOYMENT)
    cfg["limits"]["served_logit_gap"] = limit
    mix = copy.deepcopy(real.mix)
    mix.update(copy.deepcopy(TINY_LENGTHS))
    if mix["arrival"]["kind"] == "backlog":
        mix["arrival"]["count"] = 12
    else:
        mix["arrival"]["rate_per_s"] = rate_per_s
    if mix.get("tenants"):
        mix["tenants"]["count"] = 3
        mix["tenants"]["system"] = {"dist": "uniform", "min": 40, "max": 90}
        mix["prompt"] = {"dist": "uniform", "min": 4, "max": 16}
    return spec.Cell(real.name, cfg, mix, 1, real.end_to_end, real.per_layer)
