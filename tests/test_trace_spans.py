"""The engine's phase spans on the profiler's clock, the stable names of its
device programs, the model's named scopes and the server-side token stamps.

* a tiny engine stepped under ``jax.profiler.trace`` leaves the span tree
  docs/observability.md documents, nested, for the legacy and the fused
  step, and the async loop's ``async.*`` spans on its own thread;
* the decode, chunk and sample programs lower under their own module names,
  with the model's scopes in their op_name metadata;
* every streamed token carries the engine's emission stamp.
"""

import asyncio
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config.model import reduce_for_smoke
from repro.configs import get_config
from repro.models import init_params
from repro.serving import InferenceEngine
from repro.serving.async_engine import AsyncEngine
from repro.serving.trace import PHASE_PREFIXES


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("olmo-1b"))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def engine(setup, **kw):
    cfg, params = setup
    return InferenceEngine(cfg, params, max_batch=3, max_seq=64, block_size=8, prefill_budget=16, eos_token=-1, **kw)


def spans_of(trace_dir) -> dict:
    """``{thread: [(start, end, name)]}`` of every program span in the trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):  # threads may share a name
            mine = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events if e.name.startswith(PHASE_PREFIXES)]
            if mine:
                out[f"{plane.name}/{i}:{line.name}"] = sorted(mine, key=lambda e: (e[0], -e[1]))
    return out


def parents(spans: list) -> list:
    """``(name, parent name or None)`` per span, by time containment."""
    out, stack = [], []
    for start, end, name in spans:
        while stack and stack[-1][1] <= start:
            stack.pop()
        out.append((name, stack[-1][2] if stack else None))
        stack.append((start, end, name))
    return out


STEP_CHILDREN = {
    False: {"engine.schedule", "engine.sync_tables", "engine.prepare", "engine.dispatch.decode",
            "engine.dispatch.sample", "engine.host_fetch", "engine.emit", "engine.note_step"},
    True: {"engine.plan", "engine.sync_tables", "engine.dispatch.fused_decode", "engine.dispatch.fused_step",
           "engine.host_fetch", "engine.emit", "engine.note_step"},
}


@pytest.mark.parametrize("fused", [False, True], ids=["legacy", "fused"])
def test_step_span_tree(setup, tmp_path, fused):
    eng = engine(setup, fused=fused)
    for n in (5, 21, 3):  # the 21-token prompt takes two prefill budgets
        eng.submit(list(range(2, 2 + n)), max_new_tokens=3)
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_drained()
    (thread,) = spans_of(tmp_path).values()
    tree = parents(thread)
    assert all(name.startswith("engine.") for name, _ in tree)
    steps = [p for name, p in tree if name == "engine.step"]
    assert steps and all(p is None for p in steps)  # every step is a root ...
    assert all(p is not None for name, p in tree if name != "engine.step")  # ... and holds the rest
    children = {name for name, p in tree if p == "engine.step"}
    assert children <= STEP_CHILDREN[fused]
    required = {"engine.host_fetch", "engine.emit", "engine.note_step"}
    required |= {"engine.plan"} if fused else {"engine.schedule", "engine.prepare", "engine.dispatch.decode", "engine.dispatch.sample"}
    assert required <= children
    planner = "engine.plan" if fused else "engine.schedule"
    assert {p for name, p in tree if name == "engine.admit"} == {planner}
    assert sum(1 for name, _ in tree if name == "engine.admit") == 3
    if fused:
        assert not {"engine.schedule", "engine.prepare", "engine.prefill_chunk"} & {n for n, _ in tree}
    else:
        assert {p for name, p in tree if name == "engine.prefill_chunk"} == {"engine.schedule"}
        assert {p for name, p in tree if name == "engine.dispatch.prefill_chunk"} == {"engine.prefill_chunk"}
        # the first token of each prompt is fetched inside the scheduling pass
        assert sum(1 for name, p in tree if name == "engine.host_fetch" and p == "engine.schedule") == 3
    # spans never enter the ring buffer
    assert not any(e.name.startswith(PHASE_PREFIXES) for e in eng.tracer.events)


def test_async_loop_spans_on_their_own_thread(setup, tmp_path):
    eng = engine(setup)

    async def main():
        async with AsyncEngine(eng) as aeng:
            await aeng.generate([5, 6, 7], max_new_tokens=2)
            await asyncio.sleep(0.05)  # the loop goes idle and waits
            await aeng.generate([8, 9], max_new_tokens=2)

    with jax.profiler.trace(str(tmp_path)):
        asyncio.run(main())
    by_thread = {t: {n for _, _, n in spans} for t, spans in spans_of(tmp_path).items()}
    loop = [t for t, names in by_thread.items() if "async.wait" in names]
    worker = [t for t, names in by_thread.items() if "engine.step" in names]
    assert len(loop) == 1 and worker and loop[0] not in worker
    assert by_thread[loop[0]] <= {"async.submit", "async.wait"} and "async.submit" in by_thread[loop[0]]


def lowered(eng, program):
    B = eng.max_batch
    if program == "decode_step":
        return eng._decode.lower(eng.params, eng.cache, jnp.zeros((B, 1), jnp.int32), jnp.zeros((B,), jnp.int32))
    if program == "chunk_step":
        return eng._chunk_step.lower(
            eng.params, eng.cache, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, eng.max_blocks_per_seq), jnp.int32),
        )
    logits = jnp.zeros((B, eng.cfg.padded_vocab), jnp.float32)
    return eng._sample.lower(logits, jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32), jax.random.PRNGKey(0))


@pytest.mark.parametrize(
    "program, scopes",
    [
        ("decode_step", {"attention", "mlp", "qkv", "out_proj", "norm", "head", "cache_write"}),
        ("chunk_step", {"attention", "mlp", "qkv", "out_proj", "norm", "head", "cache_write"}),
        ("sample", set()),
    ],
)
def test_programs_lower_under_their_names_with_scopes(setup, program, scopes):
    text = lowered(engine(setup), program).as_text(debug_info=True)
    assert re.search(rf"module @jit_{program}\b", text)
    assert "lambda" not in text.split("\n", 1)[0]
    found = {part for name in re.findall(r'loc\("([^"]*)"', text) for part in name.split("/")}
    assert scopes <= found


def test_token_events_carry_the_emission_stamp(setup):
    eng = engine(setup)
    stamps = []

    async def main():
        async with AsyncEngine(eng) as aeng:
            async for ev in aeng.submit_stream([5, 6, 7, 8], max_new_tokens=4):
                if ev.kind == "token":
                    stamps.append((ev.index, ev.t))
        return eng.done[-1]

    req = asyncio.run(main())
    assert [i for i, _ in stamps] == [0, 1, 2, 3]
    assert stamps[0][1] == req.first_token_t
    ts = [t for _, t in stamps]
    assert all(t is not None for t in ts) and ts == sorted(ts) and ts[-1] <= req.done_t
