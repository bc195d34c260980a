"""One run of one cell: build, warm up, drive the window, check, report.

The system under test is ``InferenceEngine`` behind ``AsyncEngine
.submit_stream``, the streaming loop that the HTTP front-end serves, driven in
process. The benchmark contributes the weights (``bench/weights.py``), the
traffic (``bench/traffic/generate.py``), the client that sends requests at
their due times and stamps every streamed token, a timer around the engine's
``step`` (``StepRecorder``), and the check of what was served against the
configuration's plain reference (``bench/references/<config>.py``, found by
``spec.reference``). A configuration whose ``mesh`` has ``model`` > 1 runs as
one engine over the program's ``(data=1, model=tp)`` serving mesh; on one chip
the weights sit on the first device and the engine has no mesh.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import json
import os
import shutil
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec, work
from bench.record import RequestRecord, RunRecord, StepRecord, percentile, token_gaps, ttfts
from bench.traffic.generate import FIRST_TOKEN, schedule, tenant_prompts

DRAIN_CAP_S = 90.0  # open loop: how long after the window requests may finish
TRACE_SPAN = (0.25, 0.75)  # part of the window the trace is reduced over, as fractions of it
SAMPLE_TOKENS = 512  # served tokens the correctness sample reaches at least
SAMPLE_MAX = 8  # requests in the correctness sample at most
REF_GROUP = 4  # sequences per reference call
COUNTERS = ("engine_prefill_tokens_total", "engine_prefix_hit_tokens_total", "engine_tokens_out_total")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def span(name: str, on: bool):
    """A ``jax.profiler.TraceAnnotation`` in traced runs, else nothing."""
    if not on:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


class CompileClock:
    """Backend compiles and persistent-cache hits, from ``jax.monitoring``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1


def check_devices(chips: int, *, require_tpu: bool = True):
    """The devices of the cell. Without a TPU, or with fewer chips than the
    cell asks for, the run ends here with no result."""
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices only; this benchmark never falls back")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def program_config(cfg: dict, ref):
    """The program's registry entry at the configuration file's sizes
    (``ref.program_sizes``); its architecture has to be the one the
    configuration's reference states (``ref.program_fields``)."""
    from repro.configs import get_config

    pc = get_config(cfg["model"]).replace(**ref.program_sizes(cfg))
    differ = {k: (v, getattr(pc, k)) for k, v in ref.program_fields(cfg).items() if getattr(pc, k) != v}
    if differ or pc.padded_vocab != pc.vocab_size:
        raise SystemExit(f"the program's {cfg['model']} is not the stated architecture: {differ}")
    return pc


def serving_mesh(cfg: dict, chips: int):
    """The program's ``(data=1, model=tp)`` serving mesh over the cell's
    chips, or None where the configuration's mesh is one chip."""
    m = cfg["mesh"]
    if m["data"] != 1 or m["model"] != chips:
        raise SystemExit(f"mesh {m} does not lay the cell's {chips} chips out as (data=1, model={chips})")
    if chips == 1:
        return None
    from repro.launch.mesh import make_serving_mesh

    return make_serving_mesh(chips)


class StepRecorder:
    """Wraps the engine's ``step`` on the instance: a host timer (the step
    ends in a host fetch of the sampled tokens), the prompt tokens it computed
    (delta of ``engine_prefill_tokens_total``) and, with ``detail``, the
    positions each computed token attended over. ``annotate`` puts each step
    in a ``jax.profiler.TraceAnnotation``. ``abort_all`` makes the next call
    abort every queued and running request instead of stepping.
    ``saturated`` says whether the last step left no slot prefilling and
    either admitted nothing or left nothing waiting: a backlog's steady state."""

    def __init__(self, eng, *, detail: bool, annotate: bool):
        self.eng = eng
        self.detail = detail
        self.annotate = annotate
        self.steps: list[StepRecord] = []
        self.abort_all = False
        self._orig = eng.step
        self._prefill = eng.metrics.get("engine_prefill_tokens_total")
        self.mismatched = 0  # steps whose per-request prompt tokens disagree with the counter
        self.saturated = False
        eng.step = self.step

    def _snapshot(self):
        return {
            r.req_id: (r.prefilling, r.prefill_pos, len(r.generated))
            for r in self.eng.slots
            if r is not None
        }

    def _account(self, before, done0, rec: StepRecord) -> None:
        eng = self.eng
        touched = [r for r in eng.slots if r is not None] + eng.done[done0:]
        keys, spans, rows = [], [], 0
        for r in touched:
            if r.finish_reason not in (None, "length", "eos"):
                continue  # aborted: computed nothing this step
            b = before.get(r.req_id)
            if b is None:  # admitted this step, at its prefix hit
                was_prefilling, start, gen0 = True, r.prefix_hit_tokens, 0
            else:
                was_prefilling, start, gen0 = b[0], b[1], b[2]
            if was_prefilling:
                end = r.prefill_pos if r.prefilling else len(r.prompt)
                if end > start:
                    spans.append((start, end))
            gen1 = len(r.generated)
            first = 1 if gen0 == 0 and gen1 > 0 else 0
            for _ in range(gen1 - gen0 - first):
                keys.append(len(r.prompt) + gen1 - 1)
            rows += gen1 - gen0
        rec.decode_keys, rec.prefill_spans, rec.logits_rows = keys, spans, rows
        if sum(b - a for a, b in spans) != rec.prefill_tokens:
            self.mismatched += 1

    def step(self) -> int:
        eng = self.eng
        if self.abort_all:
            for r in list(eng.queue) + [s for s in eng.slots if s is not None]:
                eng.abort(r, "window_closed")
            return 0
        before = self._snapshot() if self.detail else None
        held = {r.req_id for r in eng.slots if r is not None}
        done0 = len(eng.done)
        p0 = self._prefill.value
        with span("engine_step", self.annotate):
            t0 = time.perf_counter()
            produced = self._orig()
            t1 = time.perf_counter()
        rec = StepRecord(t0, t1, produced, int(self._prefill.value - p0))
        if self.detail:
            self._account(before, done0, rec)
        self.steps.append(rec)
        running = [r for r in eng.slots if r is not None]
        self.saturated = not any(r.prefilling for r in running) and (
            not eng.queue or all(r.req_id in held for r in running)
        )
        return produced


def warmup_prompts(cell, items, seed: int, vocab: int) -> list:
    """Phases of (prompt, max_new_tokens) that compile every program the
    window runs. The engine's first call sees a cache it has not placed yet
    and every later one a placed cache, and a decode step sees its block table
    freshly put (after a change of tables) or carried from the last step: each
    is a program of its own. So: one token, to place the cache; a prompt whose
    chunks take every power of two up to the prefill budget, decoding three
    tokens; a prompt that hits it in part (the copy-on-write block); then each
    tenant's system prompt, left cached as a running deployment holds it."""
    dep = cell.config["deployment"]
    rng = np.random.default_rng([seed, 4])
    budget, bs = dep["prefill_budget"], dep["block_size"]
    n1 = min(2 * budget - 1, dep["max_seq"] - 4)
    w1 = rng.integers(FIRST_TOKEN, vocab, n1).tolist()
    cut = min(4, n1 // bs - 1) * bs + bs // 2  # inside a full block of w1
    w2 = w1[:cut] + rng.integers(FIRST_TOKEN, vocab, 8).tolist()
    phases = [[(rng.integers(FIRST_TOKEN, vocab, 1).tolist(), 1)], [(w1, 3)], [(w2, 3)]]
    tenants = tenant_prompts(items)
    if tenants:
        phases.append([(list(p) + rng.integers(FIRST_TOKEN, vocab, 1).tolist(), 1) for p in tenants.values()])
    return phases


async def _consume(aeng, prompt, max_new, rec: RequestRecord) -> None:
    rec.submit_t = time.perf_counter()
    async for ev in aeng.submit_stream(list(prompt), max_new_tokens=max_new):
        now = time.perf_counter()
        rec.req_id = ev.req_id
        if ev.kind == "token":
            rec.token_t.extend([now] * len(ev.tokens))
            if ev.t is not None:
                rec.token_delay.extend([time.monotonic() - ev.t] * len(ev.tokens))
            rec.tokens.extend(ev.tokens)
        else:
            rec.finish = ev.reason


async def _sleep_until(t: float) -> None:
    dt = t - time.perf_counter()
    if dt > 0:
        await asyncio.sleep(dt)


async def _drive(aeng, recorder, phases, items, *, seconds, backlog, trace_dir, annotate, clock, counters):
    """Warm-up phases, then the window. Returns the run's timing marks.

    With ``trace_dir`` the profiler runs from just before the window opens
    until every request has ended: starting and stopping it blocks the event
    loop for seconds, so neither may fall where requests arrive or stream.
    The reduction covers the ``bench_window`` span, the middle of the window
    by ``TRACE_SPAN``."""
    for phase in phases:
        recs = [RequestRecord(0.0, tuple(p), n) for p, n in phase]
        await asyncio.gather(*(_consume(aeng, r.prompt, r.target, r) for r in recs))
        bad = [r.finish for r in recs if r.finish != "length" or len(r.tokens) != r.target]
        if bad:
            raise SystemExit(f"warm-up requests ended badly: {bad}")
    if recorder.eng.allocator.blocks_in_use:
        raise SystemExit(f"warm-up left {recorder.eng.allocator.blocks_in_use} blocks in use")
    recorder.steps.clear()
    marks = {"t_warm": time.perf_counter()}

    tasks = []
    if backlog:
        # the whole backlog at once; the window opens when the engine holds
        # all it can take and has prefilled it: the batch job in flight
        t_sub = time.perf_counter()
        requests = [RequestRecord(t_sub + it.due, it.prompt, it.max_new_tokens) for it in items]
        tasks = [asyncio.ensure_future(_consume(aeng, r.prompt, r.target, r)) for r in requests]
        recorder.saturated = False
        while not recorder.saturated:
            await asyncio.sleep(0.005)
        recorder.steps.clear()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    t_open = time.perf_counter()
    marks.update(t_open=t_open, compiles_open=clock.count, compile_s_open=clock.seconds, hits_open=clock.hits)
    marks["counters_open"] = counters()
    t_close = t_open + seconds
    if not backlog:
        requests = [RequestRecord(t_open + it.due, it.prompt, it.max_new_tokens) for it in items]

    async def arrivals():
        if backlog:
            return
        for r in requests:
            with span("await_arrival", annotate):
                await _sleep_until(r.due_t)
            with span("submit", annotate):
                tasks.append(asyncio.ensure_future(_consume(aeng, r.prompt, r.target, r)))

    async def traced():
        lo, hi = (t_open + f * seconds for f in TRACE_SPAN)
        await _sleep_until(lo)
        with span("bench_window", True):
            start = time.perf_counter()
            await _sleep_until(hi)
            marks["trace_span"] = (start, time.perf_counter())

    side = [asyncio.ensure_future(arrivals())]
    if trace_dir is not None:
        side.append(asyncio.ensure_future(traced()))
    await _sleep_until(t_close)
    marks["t_close"] = time.perf_counter()
    marks["counters_close"] = counters()
    marks["compiles_close"] = clock.count
    marks["compile_s_close"] = clock.seconds
    if backlog:
        recorder.abort_all = True  # the window alone counts; stop the rest
    else:
        await asyncio.wait(side[:1])
        pending = [t for t in tasks if not t.done()]
        if pending:
            done, pending = await asyncio.wait(pending, timeout=DRAIN_CAP_S)
        if pending:
            recorder.abort_all = True
    await asyncio.gather(*side, *tasks)
    marks["t_drained"] = time.perf_counter()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    marks["requests"] = requests
    return marks


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def choose_sample(requests, seed: int) -> list:
    """The request with the most served tokens, then others drawn from the
    seed, until the sample holds ``SAMPLE_TOKENS`` served tokens. Requests
    that finished; in a backlog cell, whose requests outlast any window, the
    ones the window closed on, with every token they were served."""
    done = [r for r in requests if r.finish in ("length", "window_closed") and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: -len(r.tokens))
    rng = np.random.default_rng([seed, 3])
    order = [done[0]] + [done[1:][i] for i in rng.permutation(len(done) - 1)]
    out, n = [], 0
    for r in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def ref_length(cell) -> int:
    """One padded length for every reference call of the cell (one program):
    the longest request the mix can make."""
    mix = cell.mix
    system = mix["tenants"]["system"]["max"] if mix.get("tenants") else 0
    n = system + mix["prompt"]["max"] + mix["output"]["max"]
    return -(-n // 256) * 256


@partial(jax.jit, static_argnums=0)
def _served_gaps(head, params, ref_hidden, targets):
    """Per position: how far the served token's reference logit lies below
    the reference's best (0 where it is the best). ``targets`` (B, S) holds the
    token served after each position, -1 where none was served."""
    logits = head(params, ref_hidden, None)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(targets >= 0, best - got, 0.0)


@partial(jax.jit, static_argnums=(0, 1))
def _control_gaps(head, quant, params, ref_hidden, ctl_hidden, targets):
    """Per position: the reference gap of the token the control puts first."""
    ref = head(params, ref_hidden, None)
    ctl = head(params, ctl_hidden, quant)
    first = jnp.argmax(ctl, axis=-1)
    got = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
    return jnp.where(targets >= 0, jnp.max(ref, axis=-1) - got, 0.0)


def served_gaps(cell, ref, params, sample, *, control: bool):
    """Per request, the widest reference-logit gap of a served token (and, with
    ``control``, of the token the configuration's lower-precision control,
    ``ref.CONTROL``, puts first). ``ref`` is the configuration's reference
    module (``spec.reference``)."""
    S = ref_length(cell)
    cfg = cell.config
    gaps, ctl = [], []
    for g in range(0, len(sample), REF_GROUP):
        group = sample[g : g + REF_GROUP]
        toks = np.zeros((REF_GROUP, S), np.int32)
        tgt = np.full((REF_GROUP, S), -1, np.int32)
        for i, r in enumerate(group):
            seq = list(r.prompt) + list(r.tokens)
            n_p = len(r.prompt)
            toks[i, : len(seq) - 1] = seq[:-1]
            tgt[i, n_p - 1 : len(seq) - 1] = r.tokens
        t, y = jnp.asarray(toks), jnp.asarray(tgt)
        h = ref.hidden(cfg, None, params, t)
        gap = np.asarray(_served_gaps(ref.head, params, h, y))
        gaps += [float(gap[i].max()) for i in range(len(group))]
        if control:
            hc = ref.hidden(cfg, ref.CONTROL, params, t)
            c = np.asarray(_control_gaps(ref.head, ref.CONTROL, params, h, hc, y))
            ctl += [float(c[i].max()) for i in range(len(group))]
            del hc
        del h
    return gaps, ctl


def checks_of(cell, requests, gaps) -> dict:
    """Each number compared, with its limit (from the configuration file)."""
    limits = cell.config["limits"]
    ended = [r for r in requests if r.finish in ("length", "eos")]
    mismatch = sum(1 for r in ended if r.finish != "length" or len(r.tokens) != r.target)
    out = {"length_mismatches": {"value": mismatch, "limit": limits["length_mismatches"]}}
    out["served_logit_gap"] = {"value": max(gaps) if gaps else None, "limit": limits["served_logit_gap"]}
    return out


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())


def run(
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    cell=None,
    require_tpu: bool = True,
    peaks_table: dict | None = None,
    control: bool = False,
    keep_record: bool = False,
    root: Path = spec.ROOT,
) -> dict:
    """One run; returns the result object. With ``control`` the
    configuration's lower-precision control takes the program's place in the
    comparison: ``correct`` and ``checks`` are the control's, and
    ``_calibration`` holds both sides' readings. ``keep_record`` adds the
    run's ``RunRecord`` under ``_record``. ``root`` holds ``BENCHMARK.json``
    and the files it names."""
    cell = cell or spec.load_cell(workload, root)
    devices = check_devices(cell.chips, require_tpu=require_tpu)
    kind = devices[0].device_kind
    peak = (peaks_table or {}).get(kind) or spec.peaks(kind, cell.root)
    clock = CompileClock()
    cache_dir = jax.config.jax_compilation_cache_dir
    from repro.serving import InferenceEngine
    from repro.serving.async_engine import AsyncEngine

    t_import = time.perf_counter()
    cfg = cell.config
    ref = spec.reference(cell)
    pcfg = program_config(cfg, ref)
    from repro.models import abstract_params

    from bench import weights

    abstract = abstract_params(pcfg, jnp.bfloat16)
    layout = ref.layout(cfg)
    weights.check_layout(layout, abstract)
    mesh = serving_mesh(cfg, cell.chips)
    if mesh is None:
        shardings = jax.tree.map(lambda _: jax.sharding.SingleDeviceSharding(devices[0]), abstract)
    else:
        from repro.serving.engine import serving_rules

        shardings = serving_rules().param_shardings(pcfg, mesh, abstract)
    params = weights.make(layout, seed, shardings)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()

    dep = cfg["deployment"]
    eng = InferenceEngine(
        pcfg,
        params,
        max_batch=dep["max_batch"],
        max_seq=dep["max_seq"],
        block_size=dep["block_size"],
        num_blocks=dep["num_blocks"],
        prefill_budget=dep["prefill_budget"],
        cache_dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dep["cache_dtype"]],
        eos_token=-1,  # unreachable: every request emits the length its traffic drew
        seed=seed & 0x7FFFFFFF,
        mesh=mesh,
    )
    recorder = StepRecorder(eng, detail=trace, annotate=trace)
    aeng = AsyncEngine(eng)
    t_engine = time.perf_counter()

    items = schedule(cell.mix, seed=seed, seconds=seconds, vocab=cfg["vocab_size"])
    backlog = cell.mix["arrival"]["kind"] == "backlog"
    phases = warmup_prompts(cell, items, seed, cfg["vocab_size"])
    compiles0, compile_s0, hits0 = clock.count, clock.seconds, clock.hits
    trace_dir = None
    if trace:
        trace_dir = str(spec.ROOT / ".bench_run" / "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    counters = lambda: {n: eng.metrics.get(n).value for n in COUNTERS}

    async def main():
        try:
            return await _drive(
                aeng, recorder, phases, items, seconds=seconds, backlog=backlog,
                trace_dir=trace_dir, annotate=trace, clock=clock, counters=counters,
            )
        finally:
            await aeng.stop()

    marks = asyncio.run(main())
    t_open = marks["t_open"]
    setup_s = t_open - t_start
    log(
        f"setup {setup_s:.3f} s: import {t_import - t_start:.3f} s, weights {t_weights - t_import:.3f} s, "
        f"engine {t_engine - t_weights:.3f} s, warm-up {marks['t_warm'] - t_engine:.3f} s, "
        f"backlog fill {t_open - marks['t_warm']:.3f} s "
        f"({marks['compiles_open'] - compiles0} compiles taking {marks['compile_s_open'] - compile_s0:.3f} s, "
        f"{marks['hits_open'] - hits0} persistent-cache hits; before the engine: {compiles0} compiles "
        f"taking {compile_s0:.3f} s); compile cache {cache_dir}"
    )
    in_window = marks["compiles_close"] - marks["compiles_open"]
    log(f"compiles inside the window: {in_window} taking {marks['compile_s_close'] - marks['compile_s_open']:.3f} s")

    by_id = {q.req_id: q for q in eng.done}
    for r in marks["requests"]:
        q = by_id.get(r.req_id)
        if q is not None and q.admit_t is not None:
            r.admit_wait = q.admit_t - q.submit_t
    peak_bytes = memory_peak(devices)
    requests = marks["requests"]

    rec = RunRecord(
        seconds=seconds,
        t_open=t_open,
        t_close=marks["t_close"],
        t_drained=marks["t_drained"],
        chips=cell.chips,
        max_batch=dep["max_batch"],
        peak=peak,
        shapes=work.Shapes.of(cfg),
        setup_s=setup_s,
        requests=requests,
        steps=recorder.steps,
        counters_open=marks["counters_open"],
        counters_close=marks["counters_close"],
        trace_span=marks.get("trace_span"),
    )
    if trace:
        from bench import program_trace, trace_reduce

        t_red = time.perf_counter()
        path = trace_reduce.find_xplane(trace_dir)
        rec.trace = trace_reduce.reduce(trace_reduce.read_xplane(path))
        rec.program = program_trace.read(path, host_names=trace_reduce.HOST_SPANS + (trace_reduce.WINDOW_SPAN,))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for key, value in program_trace.readings(rec.program).items():
            log(f"program {key}: {value}")
        log(
            f"trace: busy {rec.trace['busy_s']:.6f} s of {rec.trace['window_s']:.6f} s, idle by host span "
            f"{rec.trace['idle_by_span']}; read and reduced in {time.perf_counter() - t_red:.3f} s"
        )
        if recorder.mismatched:
            log(f"WARNING: {recorder.mismatched} steps' per-request prompt tokens disagree with the engine counter")

    # what the window measured, before the program's state is freed
    metric_defs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metric_defs:
        v = spec.metric_reader(m["name"], cell.root)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    lateness = [r.submit_t - r.due_t for r in requests if r.submit_t is not None]
    ttft, gaps_t = ttfts(rec), token_gaps(rec)
    log(
        f"{len(requests)} requests due, {sum(1 for r in requests if r.finish == 'length')} finished; "
        f"ttft p50 {percentile(ttft, 50)} p95 {percentile(ttft, 95)} s over {len(ttft)}; "
        f"token gaps p50 {percentile(gaps_t, 50)} p95 {percentile(gaps_t, 95)} s over {len(gaps_t)}; "
        f"generator lateness p95 {percentile(lateness, 95)} s; {len(rec.steps)} steps"
    )
    attempted = len(requests)
    # a backlog's window closes on requests in flight; an open-loop request
    # still unfinished when the drain is cut has failed
    ended = ("length", "window_closed") if backlog else ("length",)
    failed = sum(1 for r in requests if r.finish not in ended)

    # free the program's state, then run the reference
    eng.step = recorder._orig
    del aeng, recorder, eng
    gc.collect()
    sample = choose_sample(requests, seed)
    t_ref = time.perf_counter()
    gaps, ctl = served_gaps(cell, ref, params, sample, control=control)
    checks = checks_of(cell, requests, ctl if control else gaps)
    log(f"reference over {len(sample)} requests, {sum(len(r.tokens) for r in sample)} served tokens, {time.perf_counter() - t_ref:.3f} s")

    result = {
        "correct": passed(checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak_bytes,
        },
    }
    if trace:
        result["device"]["busy_s"] = rec.trace["busy_s"]
        result["device"]["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"], "idle_gaps": rec.trace["idle_gaps"]}
    result["checks"] = checks
    if keep_record:
        result["_record"] = rec
    if control:
        result["_calibration"] = {
            "gaps": gaps,
            "control_gaps": ctl,
            "program_correct": passed(checks_of(cell, requests, gaps)),
            "sample_tokens": [len(r.tokens) for r in sample],
        }
    return result


def print_result(result: dict) -> None:
    """Each compared number beside its limit as the last lines on stderr, then
    the result as the last line on stdout."""
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps({k: v for k, v in result.items() if not k.startswith("_")}), flush=True)
