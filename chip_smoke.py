#!/usr/bin/env python3
"""Chip smoke test: the serving main path on a TPU at published widths.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # tensor-parallel serving on a 2x2 host

One chip:

1. **Serving.** olmo-1b at full width (16 layers, d_model 2048, 16 heads of
   128, vocab 50304) with bf16 weights from ``--seed``, built by
   ``launch/serve.py``'s ``init_serving_params``.  The paged pool holds
   2048 blocks of 16 tokens (4 GiB).  Eight requests of 128-1024 prompt
   tokens, five of them sharing a 256-token system prompt, ask for 32 new
   tokens each with EOS unreachable.  They drain through the chunked paged path under a
   256-token prefill budget, once per step path (legacy, fused) and
   chunk attention implementation (xla, pallas; decode attention is the
   Pallas kernel on a TPU either way).  Each engine drains twice: the
   first drain compiles, the second (fresh prompts of the same shapes) is
   warm.  Every request must produce exactly 32 tokens and every drain must
   leave zero blocks in use.
2. **Logits.** The paged model path (two prefill chunks, then one decode
   step) against the model's own float32 forward under
   ``default_matmul_precision("highest")``, for both implementations:
   ``max|logit - ref| <= LOGIT_TOL * rms(ref)`` per row.
3. **Greedy agreement.** Every engine's tokens equal the xla/legacy
   engine's.  A divergence passes only at a near-tie: where the float32
   reference puts the two candidate tokens within ``2 * LOGIT_TOL *
   rms(ref)`` of each other, the bound within which two engines that both
   pass step 2 may legitimately disagree.
4. **Kernel presence.** The compiled pallas chunk program and both decode
   programs contain ``tpu_custom_call``: the kernel ran, not a reference.
5. **Training.** Three bert-large steps at full width, seq 512, through
   ``launch/train.py``'s set-up; every loss finite.

Four chips (``--four-chips``, nothing else): mistral-nemo-12b at full
widths.  With 4 layers in float32 it serves the same requests at TP=1 and
TP=4 (pallas): logits agree within ``TP_TOL`` and greedy tokens are
identical.  Float32 at ``highest`` matmul precision keeps the comparison
free of bf16 rounding, so a difference means a sharding fault.  Then the
full 40 layers in bf16 at TP=4 answer the requests with the pallas
kernels, each chip holding 8 q-heads and 2 kv-heads.

Everything runs in this one process, which alone holds the chip(s).  The
compile seconds, drain seconds and ``peak_bytes_in_use`` printed on the way
describe this run only; they are not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``; any failed check exits non-zero before it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

NEW_TOKENS = 32
# bf16 serving vs the float32 reference, as max|logit - ref| in units of the
# row's rms(ref).  bf16 rounding grows it with depth: at olmo-1b width it
# measured 0.029 / 0.040 / 0.064 for 1 / 2 / 4 layers on the CPU backend,
# about 0.13 extrapolated to 16.  A kernel reading the wrong head or block
# errs by O(1).
LOGIT_TOL = 0.25
TP_TOL = 1e-3  # TP=4 vs TP=1, both float32, in units of rms(TP=1 logits)


@dataclass(frozen=True)
class Traffic:
    """Request mix and pool sizing for one serving phase."""

    prompt_lens: tuple = (128, 256, 384, 512, 640, 768, 896, 1024)
    shared: int = 256  # system prompt shared by every prompt longer than 2x this
    new_tokens: int = NEW_TOKENS
    budget: int = 256  # prefill tokens per step
    block: int = 16
    pool_blocks: int = 2048  # usable blocks (the null block comes on top)
    max_seq: int = 2048
    max_batch: int = 8


class Checks:
    """Collects failed checks; ``report`` prints them and says whether all passed."""

    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)
            print(f"[smoke] FAIL {what}", flush=True)

    def report(self) -> bool:
        for what in self.failed:
            print(f"[smoke] failed: {what}", file=sys.stderr)
        return not self.failed


class CompileClock:
    """Sums backend compile seconds reported through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def device_bytes(key: str = "peak_bytes_in_use") -> list:
    """``key`` of each local device's memory stats (None where unreported)."""
    import jax

    return [(d.memory_stats() or {}).get(key) for d in jax.local_devices()]


def make_prompts(traffic: Traffic, vocab: int, seed: int) -> list[list[int]]:
    """Random prompts (tokens >= 2, never EOS-like ids); every prompt longer
    than twice the shared length starts with one common system prompt."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(2, vocab, traffic.shared).tolist()
    prompts = []
    for n in traffic.prompt_lens:
        if n > 2 * traffic.shared:
            prompts.append(system + rng.integers(2, vocab, n - traffic.shared).tolist())
        else:
            prompts.append(rng.integers(2, vocab, n).tolist())
    return prompts


def make_engine(cfg, params, traffic: Traffic, *, attn_impl: str, fused: bool, mesh=None, cache_dtype=None):
    import jax.numpy as jnp

    from repro.serving import InferenceEngine

    return InferenceEngine(
        cfg,
        params,
        mesh=mesh,
        max_batch=traffic.max_batch,
        max_seq=traffic.max_seq,
        block_size=traffic.block,
        num_blocks=traffic.pool_blocks + 1,
        cache_dtype=cache_dtype or jnp.bfloat16,
        attn_impl=attn_impl,
        fused=fused,
        prefill_budget=traffic.budget,
        eos_token=-1,  # unreachable: random weights emit any id early
    )


def drain(eng, prompts: list[list[int]], traffic: Traffic, checks: Checks, label: str) -> list[list[int]]:
    """Submit the shared-prefix leader first and step until it has its first
    token (so its prompt blocks are indexed), then the rest, and drain.
    Checks exact token counts and zero allocator residue."""
    from repro.serving import RequestState

    lead = next((i for i, p in enumerate(prompts) if len(p) > 2 * traffic.shared), 0)
    reqs = {lead: eng.submit(prompts[lead], max_new_tokens=traffic.new_tokens)}
    for _ in range(len(prompts[lead])):  # one chunk or more per step
        if reqs[lead].generated:
            break
        eng.step()
    else:
        raise RuntimeError(f"{label}: the leading request never got its first token")
    for i, p in enumerate(prompts):
        if i != lead:
            reqs[i] = eng.submit(p, max_new_tokens=traffic.new_tokens)
    eng.run_until_drained()
    out = [reqs[i].generated for i in range(len(prompts))]
    counts = [len(g) for g in out]
    checks.expect(
        all(reqs[i].state == RequestState.DONE for i in reqs) and counts == [traffic.new_tokens] * len(prompts),
        f"{label}: token counts {counts}, want {traffic.new_tokens} each",
    )
    checks.expect(
        eng.allocator.blocks_in_use == 0,
        f"{label}: {eng.allocator.blocks_in_use} blocks still in use after the drain",
    )
    return out


class Reference:
    """The model's own float32 forward at ``highest`` matmul precision, on
    the bf16 weights the engines serve (upcast inside the program)."""

    def __init__(self, cfg, params):
        import jax
        import jax.numpy as jnp

        from repro.models import forward

        self.params = params
        f32 = lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p)
        self._fwd = jax.jit(lambda p, t: forward(cfg, f32(p), {"tokens": t})[0])

    def logits(self, tokens: list[int], bucket: int = 256):
        """(len(tokens), V) float32 logits.  Right-padded to a multiple of
        ``bucket`` (causal: padding never reaches earlier positions) so a
        handful of compiled lengths serve every context."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        n = len(tokens)
        padded = np.zeros((1, -(-n // bucket) * bucket), np.int32)
        padded[0, :n] = tokens
        with jax.default_matmul_precision("highest"):
            out = self._fwd(self.params, jnp.asarray(padded))
        return np.asarray(out[0, :n])


def rel_err(x, ref) -> float:
    """max|x - ref| over a row, in units of the row's rms(ref); worst row."""
    import numpy as np

    x, ref = np.atleast_2d(np.asarray(x, np.float32)), np.atleast_2d(np.asarray(ref, np.float32))
    rms = np.sqrt(np.mean(ref**2, axis=-1))
    return float(np.max(np.max(np.abs(x - ref), axis=-1) / rms))


def paged_logits(cfg, params, prompt: list[int], next_token: int, traffic: Traffic, *, attn_impl: str, mesh=None, cache_dtype=None):
    """Prefill ``prompt`` through the paged chunk path in the engine's
    budgeted chunks, then decode ``next_token``: returns (prefill logits at
    the last prompt token, decode logits, the jitted chunk and decode
    functions with one argument set each, for lowering)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import decode_step, init_paged_cache, prefill_step
    from repro.serving.engine import serving_rules
    from repro.serving.scheduler import binary_chunks

    nb = -(-(len(prompt) + 1) // traffic.block)
    make_cache = lambda: init_paged_cache(
        cfg, nb + 1, traffic.block, 1, nb, cache_dtype or jnp.bfloat16
    )
    if mesh is None:
        cache = make_cache()
    else:
        shardings = serving_rules().paged_cache_shardings(cfg, mesh, jax.eval_shape(make_cache))
        cache = jax.jit(make_cache, out_shardings=shardings)()
    row = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    chunk = jax.jit(lambda p, c, t, s, r: prefill_step(cfg, p, c, t, s, r, attn_impl=attn_impl, mesh=mesh))
    dec = jax.jit(lambda p, c, t, q: decode_step(cfg, p, c, t, q, mesh=mesh))
    toks = jnp.asarray([prompt], jnp.int32)
    pos = 0
    while pos < len(prompt):
        for c in binary_chunks(min(traffic.budget, len(prompt) - pos)):
            chunk_args = (params, cache, toks[:, pos : pos + c], jnp.asarray([pos], jnp.int32), row)
            prefill, cache = chunk(*chunk_args)
            pos += c
    cache = dict(cache)
    cache["tbl"] = jnp.broadcast_to(row[None], cache["tbl"].shape)
    dec_args = (params, cache, jnp.asarray([[next_token]], jnp.int32), jnp.asarray([len(prompt)], jnp.int32))
    decode, _ = dec(*dec_args)
    return np.asarray(prefill[0]), np.asarray(decode[0]), (chunk, chunk_args), (dec, dec_args)


def has_kernel(fn_args) -> bool:
    fn, args = fn_args
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def greedy_agree(label: str, base, other, prompts, ref: Reference, checks: Checks) -> None:
    """``other`` must equal ``base``; a divergence passes only at a near-tie
    of the float32 reference."""
    import numpy as np

    diverged = 0
    for i, (a, b) in enumerate(zip(base, other)):
        if a == b:
            continue
        j = next(k for k in range(len(a)) if a[k] != b[k])
        row = ref.logits(prompts[i] + a[:j])[-1]
        rms = float(np.sqrt(np.mean(row**2)))
        gap = abs(float(row[a[j]] - row[b[j]])) / rms
        ok = gap <= 2 * LOGIT_TOL
        log(f"{label}: request {i} diverges at token {j} ({a[j]} vs {b[j]}), reference gap {gap:.5f} rms ({'near-tie' if ok else 'NOT a near-tie'})")
        checks.expect(ok, f"{label}: request {i} diverges at token {j} with reference gap {gap:.5f} rms > {2 * LOGIT_TOL}")
        diverged += 1
    log(f"{label}: {'identical' if not diverged else f'{diverged} request(s) diverge'}")


def serving_phase(cfg, params, traffic: Traffic, seed: int, checks: Checks, clock: CompileClock) -> None:
    """Phases 1-4 on one device."""
    ref = Reference(cfg, params)
    prompt_sets = [make_prompts(traffic, cfg.vocab_size, seed + k) for k in range(2)]
    runs: dict = {}
    for impl in ("xla", "pallas"):
        for fused in (False, True):
            label = f"{impl}/{'fused' if fused else 'legacy'}"
            eng = make_engine(cfg, params, traffic, attn_impl=impl, fused=fused)
            for k, prompts in enumerate(prompt_sets):
                c0, n0, t0 = clock.seconds, clock.count, time.perf_counter()
                runs[impl, fused, k] = drain(eng, prompts, traffic, checks, f"{label} drain {k}")
                dt = time.perf_counter() - t0
                s = eng.stats()
                log(
                    f"{label} drain {k} ({'cold' if k == 0 else 'warm'}): {dt:.3f} s wall, "
                    f"{clock.count - n0} compiles taking {clock.seconds - c0:.3f} s, "
                    f"{s['tokens_out']} tokens out so far, prefix_hit_tokens={s['prefix_hit_tokens']}, "
                    f"dispatches/step={s['dispatches_per_step']:.3f}"
                )
            del eng
            gc.collect()
    log(f"peak_bytes_in_use after serving drains: {device_bytes()}")

    for k, prompts in enumerate(prompt_sets):
        base = runs["xla", False, k]
        for impl, fused in (("xla", True), ("pallas", False), ("pallas", True)):
            label = f"drain {k} {impl}/{'fused' if fused else 'legacy'} vs xla/legacy"
            greedy_agree(label, base, runs[impl, fused, k], prompts, ref, checks)

    prompt = prompt_sets[0][2]  # 384 tokens: one 256 and one 128 chunk
    want = ref.logits(prompt)[-1]
    next_token = int(want.argmax())
    want_dec = ref.logits(prompt + [next_token])[-1]
    got = {}
    for impl in ("xla", "pallas"):
        pre, dec, chunk_fn, dec_fn = paged_logits(cfg, params, prompt, next_token, traffic, attn_impl=impl)
        got[impl] = (pre, dec)
        e_pre, e_dec = rel_err(pre, want), rel_err(dec, want_dec)
        log(f"logits {impl}: prefill err {e_pre:.6f} rms, first-decode err {e_dec:.6f} rms (tolerance {LOGIT_TOL})")
        checks.expect(e_pre <= LOGIT_TOL, f"{impl} prefill logits err {e_pre:.6f} > {LOGIT_TOL}")
        checks.expect(e_dec <= LOGIT_TOL, f"{impl} first-decode logits err {e_dec:.6f} > {LOGIT_TOL}")
        # decode attention is the kernel on a TPU whatever ``impl`` says;
        # ``impl`` picks the chunk path
        for name, fn_args in (("chunk", chunk_fn), ("decode", dec_fn)):
            if name == "decode" or impl == "pallas":
                found = has_kernel(fn_args)
                log(f"{impl} {name} program contains tpu_custom_call: {found}")
                checks.expect(found, f"{impl} {name} program has no tpu_custom_call")
    log(
        f"logits pallas vs xla: prefill {rel_err(got['pallas'][0], got['xla'][0]):.6f} rms, "
        f"decode {rel_err(got['pallas'][1], got['xla'][1]):.6f} rms"
    )


def training_phase(seed: int, checks: Checks, clock: CompileClock, *, steps: int = 3, batch: int = 8, seq: int = 512) -> None:
    import jax
    import numpy as np

    from repro.launch.train import build_training

    cfg, _run, state, step, batch_fn = build_training(
        "bert-large", full=True, batch=batch, seq=seq, steps=steps, seed=seed
    )
    log(f"training {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, batch {batch} x seq {seq}")
    for i in range(steps):
        c0, t0 = clock.seconds, time.perf_counter()
        state, metrics = step(state, batch_fn(i))
        loss = float(jax.block_until_ready(metrics["loss"]))
        log(f"train step {i}: loss {loss:.6f}, {time.perf_counter() - t0:.3f} s wall, compile {clock.seconds - c0:.3f} s")
        checks.expect(bool(np.isfinite(loss)), f"train step {i}: loss {loss} not finite")
    log(f"peak_bytes_in_use after training: {device_bytes()}")


def four_chip_phase(seed: int, checks: Checks, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import init_serving_params, serving_config

    traffic = Traffic()
    mesh = make_serving_mesh(4)

    # comparison: 4 layers, float32 weights and pools at highest matmul
    # precision, TP=1 vs TP=4: only the order of the sharded sums differs
    cfg = serving_config("mistral-nemo-12b", full=True).replace(num_layers=4)
    prompts = make_prompts(traffic, cfg.vocab_size, seed)
    runs, logits = {}, {}
    for tp, m in ((1, None), (4, mesh)):
        with jax.default_matmul_precision("highest"):
            params = init_serving_params(cfg, seed=seed, dtype=jnp.float32, mesh=m)
            eng = make_engine(cfg, params, traffic, attn_impl="pallas", fused=False, mesh=m, cache_dtype=jnp.float32)
            t0 = time.perf_counter()
            runs[tp] = drain(eng, prompts, traffic, checks, f"mistral-nemo 4L f32 TP={tp}")
            log(f"mistral-nemo 4L f32 TP={tp}: drain {time.perf_counter() - t0:.3f} s wall (cold)")
            del eng
            gc.collect()
            # keep only the logits: the lowering handles hold the weights
            pre, dec = paged_logits(
                cfg, params, prompts[2], 2, traffic, attn_impl="pallas", mesh=m, cache_dtype=jnp.float32
            )[:2]
        logits[tp] = (pre, dec)
        del params
        gc.collect()
    e_pre, e_dec = rel_err(logits[4][0], logits[1][0]), rel_err(logits[4][1], logits[1][1])
    log(f"TP=4 vs TP=1 logits: prefill {e_pre:.3e} rms, decode {e_dec:.3e} rms (tolerance {TP_TOL})")
    checks.expect(e_pre <= TP_TOL and e_dec <= TP_TOL, f"TP=4 vs TP=1 logits differ: {e_pre:.3e}, {e_dec:.3e}")
    same = runs[4] == runs[1]
    log(f"TP=4 vs TP=1 greedy tokens identical: {same}")
    checks.expect(same, "TP=4 greedy tokens differ from TP=1")

    # full run: 40 layers, bf16, TP=4, pallas kernels on each chip's head slice
    cfg = serving_config("mistral-nemo-12b", full=True)
    params = init_serving_params(cfg, seed=seed, dtype=jnp.bfloat16, mesh=mesh)
    log(
        f"mistral-nemo-12b full depth bf16 TP=4 weights placed; bytes_in_use "
        f"{device_bytes('bytes_in_use')}, peak_bytes_in_use {device_bytes()}"
    )
    eng = make_engine(cfg, params, traffic, attn_impl="pallas", fused=False, mesh=mesh)
    for k in range(2):
        t0, c0 = time.perf_counter(), clock.seconds
        drain(eng, make_prompts(traffic, cfg.vocab_size, seed + k), traffic, checks, f"mistral-nemo {cfg.num_layers}L TP=4 drain {k}")
        log(
            f"mistral-nemo {cfg.num_layers}L bf16 TP=4 drain {k} ({'cold' if k == 0 else 'warm'}): "
            f"{time.perf_counter() - t0:.3f} s wall, compile {clock.seconds - c0:.3f} s"
        )
    log(f"per-device peak_bytes_in_use: {device_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true", help="tensor-parallel serving on four chips only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform})", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import init_serving_params, serving_config

    log(f"device {devices[0].device_kind} x {len(devices)}, compile cache {enable_compile_cache()}")
    checks, clock = Checks(), CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed, checks, clock)
    else:
        cfg = serving_config("olmo-1b", full=True)
        params = init_serving_params(cfg, seed=args.seed, dtype=jnp.bfloat16)
        log(f"serving {cfg.name}: {cfg.param_count() / 1e6:.1f}M params bf16, {Traffic()}")
        serving_phase(cfg, params, Traffic(), args.seed, checks, clock)
        del params
        gc.collect()
        training_phase(args.seed, checks, clock)
    log(f"total {time.perf_counter() - t0:.3f} s, {clock.count} compiles taking {clock.seconds:.3f} s")
    if not checks.report():
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
