"""Always-on serving: SLO scheduling, preemption correctness, async streaming.

The contract under test has three layers:

* **SchedulerCore policy** — ``slo`` orders the queue by
  ``(-priority, offline, deadline, arrival)`` and preempts strictly
  lower-priority running work under slot/block pressure; ``fcfs`` is the
  historical online-first arrival order and never preempts.
* **Preemption correctness** — a preempted-and-resumed request must produce
  *exactly* the tokens it would have produced with ample resources
  (greedy determinism), with and without the prefix cache recovering the
  committed context.
* **Async front-end** — ``AsyncEngine.submit_stream`` must deliver the same
  tokens as a closed-loop ``run_until_drained``, incrementally, and the
  stdlib HTTP/SSE front-end must round-trip them over a socket.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import (
    AsyncEngine,
    HttpFrontend,
    InferenceEngine,
    ManualClock,
    RequestState,
)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def ample_engine(cfg, params, **kw):
    """Reference engine: enough slots and blocks that nothing ever waits."""
    return InferenceEngine(
        cfg, params, max_batch=8, max_seq=64, cache_kind="paged", block_size=4, **kw
    )


# ---- submit() validation --------------------------------------------------


@pytest.mark.parametrize(
    "kw",
    [
        {"max_new_tokens": 0},
        {"max_new_tokens": -3},
        {"priority": -1},
        {"deadline_s": 0.0},
        {"deadline_s": -2.5},
    ],
)
def test_submit_rejects_bad_knobs(setup, kw):
    cfg, params = setup
    eng = ample_engine(cfg, params)
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], **kw)
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=4)
    assert not eng.has_work, "rejected submissions must not enqueue"


# ---- queue ordering -------------------------------------------------------


def test_slo_queue_orders_priority_then_deadline(setup):
    cfg, params = setup
    clock = ManualClock()  # tick=0: every submit_t is 0, deadline_t = deadline_s
    eng = ample_engine(cfg, params, policy="slo", clock=clock)
    lo = eng.submit([1, 2], max_new_tokens=2)
    late = eng.submit([3, 4], max_new_tokens=2, priority=2, deadline_s=5.0)
    soon = eng.submit([5, 6], max_new_tokens=2, priority=2, deadline_s=1.0)
    hi = eng.submit([7, 8], max_new_tokens=2, priority=9)
    offline = eng.submit([9, 10], max_new_tokens=2, priority=9, online=False)
    order = [r.req_id for r in eng.queue]
    # priority desc, then online before offline, then earliest deadline
    assert order == [hi.req_id, offline.req_id, soon.req_id, late.req_id, lo.req_id]


def test_fcfs_queue_ignores_slo_knobs(setup):
    cfg, params = setup
    eng = ample_engine(cfg, params, policy="fcfs")
    first = eng.submit([1, 2], max_new_tokens=2)
    urgent = eng.submit([3, 4], max_new_tokens=2, priority=9, deadline_s=0.001)
    offline = eng.submit([5, 6], max_new_tokens=2, online=False, priority=9)
    order = [r.req_id for r in eng.queue]
    assert order == [first.req_id, urgent.req_id, offline.req_id]


def test_unknown_policy_rejected(setup):
    cfg, params = setup
    with pytest.raises(ValueError):
        ample_engine(cfg, params, policy="edf")


# ---- preemption correctness ----------------------------------------------


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_preempted_request_is_token_identical(setup, prefix_cache):
    """Force a mid-decode preemption via slot pressure; the victim's final
    output must match an ample-resource greedy run exactly — the committed
    context is either recovered from the prefix cache or re-prefilled."""
    cfg, params = setup
    lo_prompt, hi_prompt = [5, 9, 12, 7, 3, 20], [21, 22, 23]

    ref = ample_engine(cfg, params)
    ref_lo = ref.submit(lo_prompt, max_new_tokens=10)
    ref_hi = ref.submit(hi_prompt, max_new_tokens=4)
    ref.run_until_drained()

    eng = InferenceEngine(
        cfg,
        params,
        max_batch=1,  # hi can only run by evicting lo
        max_seq=64,
        cache_kind="paged",
        block_size=4,
        prefix_cache=prefix_cache,
        prefill_budget=8,  # chunked path: preemption requires it
        policy="slo",
    )
    lo = eng.submit(lo_prompt, max_new_tokens=10)
    for _ in range(4):  # lo is mid-decode with committed generated tokens
        eng.step()
    assert lo.state == RequestState.ACTIVE and len(lo.generated) >= 2
    hi = eng.submit(hi_prompt, max_new_tokens=4, priority=2)
    eng.run_until_drained()

    assert lo.preemptions >= 1
    assert hi.preemptions == 0
    assert lo.generated == ref_lo.generated
    assert hi.generated == ref_hi.generated
    assert hi.done_t <= lo.done_t, "high priority must finish first"
    s = eng.stats()
    assert s["preemptions"] >= 1
    assert s["requests_preempted"] == 1
    if prefix_cache:
        assert lo.prefix_hit_tokens > 0, "resume must recover committed blocks"
    names = [e.name for e in eng.tracer.events_for(lo.req_id)]
    assert "preempt" in names and "resume" in names
    assert names.index("preempt") < names.index("resume")
    assert "engine_preemptions_total 1" in eng.metrics.render_text()


def test_preemption_under_block_pressure(setup):
    """Free slots but an exhausted block pool: admission of the
    high-priority request must evict a lower-priority one for its blocks."""
    cfg, params = setup
    # EOS unreachable: lo must still hold every block when hi arrives
    ref = ample_engine(cfg, params, eos_token=-1)
    lo_prompt, hi_prompt = [4, 4, 8, 6, 2, 11, 13, 9], [30, 31]
    ref_lo = ref.submit(lo_prompt, max_new_tokens=8)
    ref_hi = ref.submit(hi_prompt, max_new_tokens=3)
    ref.run_until_drained()

    eng = InferenceEngine(
        cfg,
        params,
        max_batch=2,  # a slot is free; only blocks are scarce
        max_seq=64,
        cache_kind="paged",
        block_size=4,
        num_blocks=5,  # 1 null + 4 usable: lo holds all of them
        prefix_cache=False,
        prefill_budget=8,
        policy="slo",
        eos_token=-1,
    )
    lo = eng.submit(lo_prompt, max_new_tokens=8)
    for _ in range(3):
        eng.step()
    assert lo.state == RequestState.ACTIVE
    hi = eng.submit(hi_prompt, max_new_tokens=3, priority=5)
    eng.run_until_drained()
    assert lo.preemptions >= 1
    assert lo.generated == ref_lo.generated
    assert hi.generated == ref_hi.generated


def test_fcfs_never_preempts(setup):
    cfg, params = setup
    eng = InferenceEngine(
        cfg,
        params,
        max_batch=1,
        max_seq=64,
        cache_kind="paged",
        block_size=4,
        prefill_budget=8,
        policy="fcfs",
    )
    lo = eng.submit([5, 9, 12, 7], max_new_tokens=8)
    for _ in range(3):
        eng.step()
    hi = eng.submit([21, 22], max_new_tokens=3, priority=9)
    eng.run_until_drained()
    assert eng.stats()["preemptions"] == 0
    assert lo.preemptions == hi.preemptions == 0
    assert lo.done_t <= hi.done_t, "fcfs runs strictly in arrival order"


def test_deadline_violation_aborts_pre_first_token(setup):
    """An unservable TTFT deadline now *aborts* the request (finish_reason
    "deadline_exceeded") instead of letting it finish late — finishing a
    missed interactive request only delays everyone else."""
    cfg, params = setup
    clock = ManualClock(tick=0.05)  # every clock read advances 50ms
    eng = ample_engine(cfg, params, clock=clock)
    req = eng.submit([1, 2, 3], max_new_tokens=2, deadline_s=0.001)
    eng.run_until_drained()
    assert req.finish_reason == "deadline_exceeded" and req.generated == []
    assert eng.deadline_violations == 1
    s = eng.stats()
    assert s["deadline_violations"] == 1 and s["requests_aborted"] == 1


# ---- async engine ---------------------------------------------------------


def test_async_stream_matches_drained_tokens(setup):
    cfg, params = setup
    prompt = [5, 9, 12, 7]
    ref = ample_engine(cfg, params)
    ref_req = ref.submit(prompt, max_new_tokens=8)
    ref.run_until_drained()

    async def go():
        async with AsyncEngine(ample_engine(cfg, params)) as aeng:
            events = []
            async for ev in aeng.submit_stream(prompt, max_new_tokens=8):
                events.append(ev)
            return events

    events = asyncio.run(go())
    token_events = [e for e in events if e.kind == "token"]
    assert len(token_events) >= 2, "tokens must stream incrementally, not in one batch"
    streamed = [t for e in token_events for t in e.tokens]
    assert streamed == ref_req.generated
    finish = events[-1]
    assert finish.kind == "finish"
    assert finish.reason == "length" and finish.n_tokens == 8
    assert finish.ttft_s is not None


def test_async_concurrent_streams(setup):
    cfg, params = setup
    prompts = [[5, 9, 12], [7, 3], [20, 21, 22, 23]]
    ref = ample_engine(cfg, params)
    ref_reqs = [ref.submit(p, max_new_tokens=5) for p in prompts]
    ref.run_until_drained()

    async def go():
        async with AsyncEngine(ample_engine(cfg, params)) as aeng:
            outs = await asyncio.gather(
                *(aeng.generate(p, max_new_tokens=5) for p in prompts)
            )
            return [toks for _, toks in outs]

    outs = asyncio.run(go())
    for got, ref_req in zip(outs, ref_reqs):
        assert got == ref_req.generated


def test_async_submit_validation_raises_in_caller(setup):
    cfg, params = setup

    async def go():
        async with AsyncEngine(ample_engine(cfg, params)) as aeng:
            with pytest.raises(ValueError):
                async for _ in aeng.submit_stream([1, 2], max_new_tokens=-1):
                    pass  # pragma: no cover

    asyncio.run(go())


# ---- HTTP/SSE front-end ---------------------------------------------------


async def _http_roundtrip(port: int, payload: dict):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    writer.write(
        b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode()
        + body
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    return raw.decode()


def _parse_sse(raw: str) -> list[tuple[str, dict]]:
    head, _, stream = raw.partition("\r\n\r\n")
    assert "200" in head.split("\r\n")[0], head
    frames = []
    for block in stream.strip().split("\n\n"):
        lines = dict(ln.split(": ", 1) for ln in block.split("\n") if ": " in ln)
        frames.append((lines["event"], json.loads(lines["data"])))
    return frames


def test_http_sse_roundtrip(setup):
    cfg, params = setup
    prompt = [5, 9, 12, 7]
    ref = ample_engine(cfg, params)
    ref_req = ref.submit(prompt, max_new_tokens=6)
    ref.run_until_drained()

    async def go():
        front = HttpFrontend(AsyncEngine(ample_engine(cfg, params)), port=0)
        await front.start()
        try:
            raw = await _http_roundtrip(
                front.port, {"prompt": prompt, "max_new_tokens": 6}
            )
            # metrics + stats endpoints over the same acceptor
            r, w = await asyncio.open_connection("127.0.0.1", front.port)
            w.write(b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
            await w.drain()
            metrics = (await r.read()).decode()
            w.close()
            await w.wait_closed()
            bad = await _http_roundtrip(
                front.port, {"prompt": prompt, "max_new_tokens": -1}
            )
            return raw, metrics, bad
        finally:
            await front.stop()

    raw, metrics, bad = asyncio.run(go())
    frames = _parse_sse(raw)
    kinds = [k for k, _ in frames]
    assert kinds[-1] == "done" and all(k == "token" for k in kinds[:-1])
    streamed = [t for k, d in frames if k == "token" for t in d["tokens"]]
    assert streamed == ref_req.generated
    assert frames[-1][1]["reason"] == "length"
    assert "engine_tokens_out_total" in metrics
    assert "400" in bad.split("\r\n")[0]


# ---- HTTP hardening / lifecycle -------------------------------------------


async def _http_get(port: int, path: str) -> str:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    return raw.decode()


def test_http_rejects_malformed_framing(setup):
    """A hostile client must get a structured 400, never crash the
    acceptor: bad Content-Length, oversized declared body, non-JSON body,
    non-object JSON body."""
    cfg, params = setup

    async def go():
        front = HttpFrontend(AsyncEngine(ample_engine(cfg, params)), port=0)
        await front.start()
        results = {}
        try:
            for name, req in {
                "bad_length": b"POST /v1/generate HTTP/1.1\r\nHost: t\r\nContent-Length: nope\r\n\r\n",
                "huge_body": b"POST /v1/generate HTTP/1.1\r\nHost: t\r\nContent-Length: 999999999\r\n\r\n",
                "not_json": b"POST /v1/generate HTTP/1.1\r\nHost: t\r\nContent-Length: 4\r\n\r\nabcd",
                "not_object": b"POST /v1/generate HTTP/1.1\r\nHost: t\r\nContent-Length: 7\r\n\r\n[1,2,3]",
                "bad_prompt": b'POST /v1/generate HTTP/1.1\r\nHost: t\r\nContent-Length: 21\r\n\r\n{"prompt": "strings"}',
            }.items():
                reader, writer = await asyncio.open_connection("127.0.0.1", front.port)
                writer.write(req)
                await writer.drain()
                results[name] = (await reader.read()).decode()
                writer.close()
                await writer.wait_closed()
            # the acceptor survived all of it and still serves health
            results["healthz"] = await _http_get(front.port, "/healthz")
        finally:
            await front.stop()
        return results

    results = asyncio.run(go())
    for name in ("bad_length", "huge_body", "not_json", "not_object", "bad_prompt"):
        assert "400" in results[name].split("\r\n")[0], (name, results[name])
    assert "200" in results["healthz"].split("\r\n")[0]


def test_http_client_disconnect_aborts_request(setup):
    """A client that opens a stream and drops the socket mid-generation
    must not keep decoding into the void: the SSE write path tears the
    stream generator down, which cancels the engine request."""
    cfg, params = setup
    eng = ample_engine(cfg, params)

    async def go():
        aeng = AsyncEngine(eng)
        front = HttpFrontend(aeng, port=0)
        await front.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", front.port)
            body = json.dumps({"prompt": [5, 9, 12, 7], "max_new_tokens": 48}).encode()
            writer.write(
                b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                + f"Content-Length: {len(body)}\r\n\r\n".encode()
                + body
            )
            await writer.drain()
            await reader.readuntil(b"event: token")  # first token is out
            writer.close()  # client vanishes mid-stream
            await writer.wait_closed()
            await aeng.drain()
        finally:
            await front.stop()

    asyncio.run(go())
    s = eng.stats()
    assert s["requests_aborted"] == 1, "disconnected client's request must abort"
    assert s["requests_active"] == 0 and s["requests_prefilling"] == 0
    assert eng.allocator.num_free == eng.allocator.capacity


def test_healthz_reports_draining_and_replicas(setup):
    """/healthz is the readiness probe: 200 while accepting, 503 + reason
    while draining; under a router it carries per-replica states."""
    cfg, params = setup

    async def go():
        aeng = AsyncEngine(ample_engine(cfg, params))
        front = HttpFrontend(aeng, port=0)
        await front.start()
        try:
            ready = await _http_get(front.port, "/healthz")
            aeng._draining = True  # what shutdown() flips first
            draining = await _http_get(front.port, "/healthz")
        finally:
            await front.stop()
        return ready, draining

    ready, draining = asyncio.run(go())
    assert "200" in ready.split("\r\n")[0]
    assert json.loads(ready.partition("\r\n\r\n")[2])["ok"] is True
    assert "503" in draining.split("\r\n")[0]
    body = json.loads(draining.partition("\r\n\r\n")[2])
    assert body == {"ok": False, "draining": True}


def test_submission_during_drain_gets_503(setup):
    cfg, params = setup

    async def go():
        aeng = AsyncEngine(ample_engine(cfg, params))
        front = HttpFrontend(aeng, port=0)
        await front.start()
        port = front.port
        try:
            aeng._draining = True
            return await _http_roundtrip(port, {"prompt": [5, 9], "max_new_tokens": 2})
        finally:
            await front.stop()

    raw = asyncio.run(go())
    assert "503" in raw.split("\r\n")[0]
    assert "draining" in json.loads(raw.partition("\r\n\r\n")[2])["error"]


def test_serve_http_sigterm_drains_and_flushes(setup, tmp_path):
    """The full production shutdown path: serve_http installs a SIGTERM
    handler; the signal triggers a graceful drain (in-flight requests
    finish) and the metrics/trace artifacts flush before exit."""
    import os
    import signal as _signal

    from repro.serving.http import serve_http

    cfg, params = setup
    metrics_path = tmp_path / "metrics.json"
    trace_path = tmp_path / "trace.json"
    eng = ample_engine(cfg, params)

    async def go():
        ready = asyncio.Queue()

        async def client():
            front = await ready.get()
            raw = await _http_roundtrip(
                front.port, {"prompt": [5, 9, 12, 7], "max_new_tokens": 6}
            )
            os.kill(os.getpid(), _signal.SIGTERM)
            return raw

        server = serve_http(
            eng,
            port=0,
            metrics_json=str(metrics_path),
            trace_out=str(trace_path),
            drain_timeout_s=30.0,
            on_ready=ready.put_nowait,
        )
        _, raw = await asyncio.wait_for(asyncio.gather(server, client()), timeout=60)
        return raw

    raw = asyncio.run(go())
    frames = _parse_sse(raw)
    assert frames[-1][0] == "done" and frames[-1][1]["reason"] == "length"
    snap = json.loads(metrics_path.read_text())
    assert snap["counters"]["engine_tokens_out_total"]["value"] >= 6
    trace = json.loads(trace_path.read_text())
    assert any(e.get("name") == "finish" for e in trace["traceEvents"])
