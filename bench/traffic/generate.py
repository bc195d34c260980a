"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``bench/traffic/<mix>.json``) names an arrival process, length
distributions and optional tenants with shared system prompts:

- ``arrival.kind``: ``backlog`` (``count`` requests all due at 0) or
  ``poisson`` (``rate_per_s``);
- ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"dist": "uniform", "min", "max"}`` (inclusive, tokens).
  With tenants, ``prompt`` is the unique tail after the system prompt;
- ``tenants``: ``{"count", "zipf_s", "system": <length dist>}`` or null;
- ``length_seed``: seeds the arrival times, the lengths and the tenant draw.

Arrival times and the sequence of (tenant, prompt length, output length) come
from ``length_seed`` alone, so every run seed does the same work; the run
seed draws every token. (In a backlog the order decides which requests the
engine holds at once, so even a reordering would change the work.)
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

KINDS = ("backlog", "poisson")
FIRST_TOKEN = 2  # ids 0 and 1 are left out of prompts (padding / EOS-like ids)


@dataclass(frozen=True)
class Item:
    due: float  # seconds after the window opens
    prompt: tuple
    max_new_tokens: int
    tenant: int  # -1: no tenant
    shared: int  # leading prompt tokens shared with the tenant's other requests


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"length range {lo}..{hi}")
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(float(spec["median"])), float(spec["sigma"]), n))
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def arrival_times(arrival: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    kind = arrival["kind"]
    if kind == "backlog":
        return np.zeros(int(arrival["count"]))
    if kind not in KINDS:
        raise ValueError(f"unknown arrival kind {kind!r}")
    rate = arrival.get("rate_per_s")
    if not rate or rate <= 0:
        raise ValueError(f"{kind} arrivals need a positive rate_per_s, got {rate!r}")
    times, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= seconds:
            return np.asarray(times)
        times.append(t)


def schedule(mix: dict, *, seed: int, seconds: float, vocab: int) -> list[Item]:
    """Requests due in a window of ``seconds``, ordered by due time."""
    fixed = np.random.default_rng(int(mix["length_seed"]))
    due = arrival_times(mix["arrival"], seconds, fixed)
    n = len(due)
    tails = draw_lengths(mix["prompt"], n, fixed)
    outs = draw_lengths(mix["output"], n, fixed)
    ten = mix.get("tenants")
    if ten:
        k = int(ten["count"])
        p = 1.0 / np.arange(1, k + 1) ** float(ten["zipf_s"])
        tenant = fixed.choice(k, size=n, p=p / p.sum())
        system_len = draw_lengths(ten["system"], k, fixed)
    else:
        tenant = np.full(n, -1)
        system_len = np.zeros(0, np.int64)

    tokens = np.random.default_rng([seed, 2])
    systems = [tokens.integers(FIRST_TOKEN, vocab, int(m)) for m in system_len]
    items = []
    for i in range(n):
        t = int(tenant[i])
        tail = tokens.integers(FIRST_TOKEN, vocab, int(tails[i]))
        prompt = np.concatenate([systems[t], tail]) if t >= 0 else tail
        items.append(
            Item(
                due=float(due[i]),
                prompt=tuple(int(x) for x in prompt),
                max_new_tokens=int(outs[i]),
                tenant=t,
                shared=int(system_len[t]) if t >= 0 else 0,
            )
        )
    return items


def tenant_prompts(items: list[Item]) -> dict[int, tuple]:
    """Each tenant's system prompt, as the schedule's requests carry it."""
    out: dict[int, tuple] = {}
    for it in items:
        if it.tenant >= 0 and it.tenant not in out:
            out[it.tenant] = it.prompt[: it.shared]
    return out
