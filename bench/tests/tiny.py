"""Tiny cells for the CPU tests: the real configuration and mix files with
every size cut down, so a whole run takes seconds on the CPU backend, and
the faults planted in the program that a run has to catch."""

from __future__ import annotations

import copy
import dataclasses

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


def tiny_cell(workload: str, *, rate_per_s: float = 4.0, limit: float = 1.0, chips: int = 1) -> spec.Cell:
    """``chips`` > 1 lays the cell out as ``(data=1, model=chips)`` with as
    many K/V heads as query heads, so every head count divides the mesh."""
    real = spec.load_cell(workload)
    cfg = copy.deepcopy(real.config)
    cfg.update(TINY_SIZES)
    if chips > 1:
        cfg["num_key_value_heads"] = cfg["num_attention_heads"]
        cfg["mesh"] = {"data": 1, "model": chips}
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
    return dataclasses.replace(real, config=cfg, mix=mix, chips=chips)


def alter_served_tokens(monkeypatch) -> None:
    """Plant a fault in the program's model step: in every third batch row
    of each decode step, the best token loses and the runner-up is served."""
    import jax.numpy as jnp

    import repro.serving.engine as engine

    real = engine.decode_step

    def altered(cfg, *a, **k):
        logits, cache = real(cfg, *a, **k)
        best = jnp.argmax(logits, axis=-1)
        rows = (jnp.arange(logits.shape[0]) % 3 == 0)[:, None]
        hit = rows & (jnp.arange(logits.shape[-1])[None, :] == best[:, None])
        return jnp.where(hit, -1e9, logits), cache

    monkeypatch.setattr(engine, "decode_step", altered)
