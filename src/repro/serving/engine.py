"""Continuous-batching inference engine with a prefix-cached paged KV cache.

The paper's inference QoS class served as a real engine: a fixed-size decode
batch whose slots are continuously refilled as requests finish (Orca-style
iteration-level scheduling).  Every ``step()`` advances ALL active slots one
token through the jitted ``decode_step``; prompt processing is **incremental
and shared** for the paged attention families:

* **Prefix caching** (``serving.prefix``): every full token-aligned block of
  a prefilled prompt is indexed by a content chain hash.  Admission matches
  the longest cached prefix, bumps refcounts on the shared blocks (a partial
  tail hit is copied-on-write into a private block) and schedules only the
  *suffix* for prefill — a fleet of requests sharing a system prompt
  computes it once.  Finished requests park their indexed blocks in an LRU
  pool that is evicted on demand, not freed eagerly.
* **Chunked prefill**: instead of a blocking batch=1 prefill at admission,
  prompts are processed in per-``step()`` budgeted chunks
  (``prefill_budget`` tokens per step, binary-decomposed into power-of-two
  chunk sizes for a bounded trace count) interleaved with decode — one long
  prompt no longer stalls every decoding request.  Suffix chunks attend over
  the request's already-grafted paged history via the multi-query-token
  ``kernels.paged_prefill_attention`` path; a mid-prefill slot keeps a null
  row in the engine block table so interleaved decode steps can't touch its
  blocks.

Two cache layouts:

* ``cache_kind="paged"`` (default for dense/moe/hybrid) — a global block
  pool + per-request block tables (``serving.paged.BlockAllocator``).
  Admission is gated on **free blocks** (cached refcount-0 blocks count:
  they are evictable on demand): a request reserves
  ``ceil((prompt + max_new_tokens) / block_size)`` blocks minus whatever the
  prefix cache already holds, so concurrency is bounded by actual cache
  *bytes in use* and shared prefixes admit for the price of their suffix.
* ``cache_kind="dense"`` — the original slot-granular ring-buffer cache
  (still used by ssm/vlm families, and as the A/B baseline in benchmarks).

**Tiered KV cache** (``spill_bytes=``, ``spill_dtype=``): the paged pool is
backed by a host-RAM spill tier (``serving.spill.SpillPool``).  An LRU
eviction of a prefix-indexed block demotes its K/V rows to host memory
(optionally int8/fp8-compressed at rest) instead of destroying them; the
index entry stays matchable under a spill handle, and a later prefix hit
admits as a cheap *re-prefill*: fresh device blocks are allocated, the
entry promotes onto them, and the row swap-ins run through the scheduler's
per-step ``restore_budget`` — double-buffered against decode, never
blocking admission.  Greedy outputs are token-identical to both the
drop-on-evict baseline and the dense-cache oracle
(``tests/test_tiered_kv.py``).

Hybrid (attention+SSM) archs page their K/V but their recurrent states
absorb the whole prompt in one pass, so they keep the blocking
prefill+graft admission (no prefix sharing / chunking); dense/moe take the
incremental path.  Window archs reclaim blocks that slide out of the window
mid-decode (shared blocks just drop a reference).  ``quantize_kv="int8"``
(or ``True``) stores paged pools int8, ``"fp8"`` stores e4m3 — both with
per-(token, head) scales (``serving.kvquant``).

**Speculative decoding** (``spec_decode="ngram"|"draft"``, dense/moe paged
only): each step drafts up to ``spec_k`` candidate tokens per slot
(``serving.spec_decode`` — n-gram prompt lookup, or a reduced-depth draft
model) and scores the whole window in ONE multi-query-token verify pass
through the chunked-prefill machinery (``models.verify_step``).
``sampler.spec_accept`` keeps the longest prefix the target distribution
agrees with plus a correction/bonus token — exactly target-distributed,
greedy-mode token-identical to plain decode — so a slot advances by 1 to
``spec_k + 1`` tokens per step while paying one cache sweep.  Rejected
tail writes are rolled back (rows zeroed, position reset); admission
reserves ``spec_k`` positions of headroom per request so speculative writes
always land inside the request's own blocks.

Per-step sampling is one jitted whole-batch dispatch
(``sampler.sample_tokens``) with per-slot temperature/top-k carried as data.
The allocator's free list is auto-defragmented when ``fragmentation()``
exceeds ``defrag_threshold`` after frees (``defrag_triggers`` in stats).

**Fused one-dispatch step** (``fused=True``, chunked families only): the
scheduler emits a typed ``StepPlan`` instead of walking phases, and each
tick lowers to ONE jitted dispatch over a unified (rows, width) batch —
decode rows, prefill chunks and spec-verify windows together through
``models.unified_step``, with sampling (``sampler.fused_sample_accept``)
and the speculative rollback (``kvcache.truncate_block_rows``) folded into
the same graph.  The host sees one sync of (new_tokens, accept_counts,
cut, done_flags) per step; ``stats()`` reports ``dispatches_per_step`` /
``host_syncs_per_step``.  Greedy outputs are token-identical to the legacy
walk (``tests/test_fused_step.py``).

Scheduling (``serving.scheduler.SchedulerCore``): queue ordering, admission,
chunked-prefill budgeting, spec-decode windows and SLO-aware **preemption**
live in an extracted scheduler core that drives this engine through a narrow
ops surface (``try_admit`` / ``run_chunk`` / ``finish_prefill`` /
``preempt`` / ...).  The default ``policy="slo"`` orders by (priority desc,
online first, earliest deadline, FCFS) — with default knobs exactly the
paper §IV.F online-ahead-of-offline-backfill order — and under pool/slot
pressure evicts a strictly-lower-priority running request (its blocks are
registered into the prefix index and parked in the LRU pool, so the resumed
request recovers its committed context as a prefix hit instead of
recomputing it).  ``policy="fcfs"`` ignores SLO knobs and never preempts.

**Tensor parallelism** (``mesh=``, ``parallel=``): one engine instance can
span the devices of a ``(data=1, model=tp)`` mesh (the paper's 4-way
Grace-Hopper node).  Params shard with the standard
``ShardingRules.param_shardings`` rule table (heads / FFN hidden / experts /
vocab over "model"); the paged K/V pools partition along the **kv-head**
axis (``ShardingRules.paged_cache_shardings``) so each device holds its head
slice of EVERY physical block — block ids are device-invariant, which keeps
the ``BlockAllocator``, ``PrefixIndex``, block tables and the scheduler
plain replicated host-side logic.  Decode / chunked-prefill / verify run as
one SPMD program with explicit ``NamedSharding`` out-specs (Pallas paged
kernels execute per-shard under ``shard_map`` on their local head slice;
head counts that don't divide the mesh are refused for ``attn_impl="pallas"``
and keep the jnp decode),
and the sampler/spec-accept dispatches consume the vocab-sharded
logits directly.  TP=n greedy decode is token-identical to TP=1 (asserted
in ``tests/test_sharded_serving.py``).

**Observability** (``serving.metrics`` + ``serving.trace``, see
docs/observability.md): every timestamp routes through one injectable
``clock``; latencies (queue wait, TTFT, TPOT, step, prefill chunk) land in
fixed-bucket histograms and throughputs in counters on ``self.metrics``;
request-lifecycle events (submit/admit/chunk/first-token/spec/finish/evict)
record into ``self.tracer``'s bounded ring buffer, exportable as
Chrome-trace JSON with one track per slot plus a scheduler track.
Each step's phases (``engine.schedule``, ``engine.dispatch.<phase>``,
``engine.host_fetch``, ``engine.emit``, ...) are ``tracer.phase`` spans on
the profiler's clock, and every jitted program carries a stable name
(``jit_decode_step``, ``jit_chunk_step``, ``jit_sample``, ...), so a
``jax.profiler`` trace lines the host's phases up with the device's
programs at no cost when no profiler runs.  An ``EnergyBridge`` charges
each step's chip-seconds into the seed ``core.telemetry.EnergyLedger``,
attributed per request as joules/token.
"""

from __future__ import annotations

import inspect
import itertools
import time
import warnings
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import live_blocks
from repro.models import (
    decode_step,
    init_paged_cache,
    init_params,
    prefill,
    prefill_step,
    supports_chunked_prefill,
    supports_paged,
    unified_step,
    verify_step,
)
from repro.serving.kvcache import (
    clear_block_row,
    clear_slot,
    copy_block_rows,
    decode_cache_from_prefill,
    gather_block_rows,
    graft_prefill_into_blocks,
    make_engine_cache,
    make_table_row,
    restore_block_rows,
    truncate_block_rows,
    write_request_into_slot,
)
from repro.serving.metrics import EnergyBridge, MetricsRegistry
from repro.serving.paged import BlockAllocator, blocks_needed, truncate_blocks
from repro.serving.prefix import PrefixIndex, is_spilled
from repro.serving.spill import SPILL_MODES, SpillPool, warn_if_fp8_over_int8
from repro.serving.sampler import (
    fused_sample_accept,
    sample_token,
    sample_tokens,
    spec_accept,
)
from repro.serving.scheduler import (  # re-exported for back-compat
    Request,
    RequestState,
    SchedulerCore,
    binary_chunks,
)
from repro.serving.spec_decode import DraftModel, make_draft_config, ngram_draft
from repro.serving.trace import SCHEDULER_TRACK, Tracer, slot_track

# families whose prefill is exact under right-padding (causal attention:
# pad positions can never influence earlier K/V or the last-real-token
# logits).  ssm/hybrid recurrent states WOULD absorb pad tokens, so those
# families prefill at exact prompt length (one trace per length).
BUCKETED_FAMILIES = ("dense", "moe", "vlm")
MIN_PREFILL_BUCKET = 8


def _jit(name: str, fn, **kw):
    """``jax.jit`` of ``fn`` under a stable program name: the profiler's
    ``XLA Modules`` line reads ``jit_<name>`` instead of ``jit__lambda``."""

    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    program.__signature__ = inspect.signature(fn)  # argument names in the HLO metadata
    return jax.jit(program, **kw)


def _sample(logits, temps=None, top_ks=None, key=None):
    """The step's sampling program: argmax for an all-greedy batch (no
    per-slot arrays passed), else ``sample_tokens``."""
    if temps is None:
        return jnp.argmax(logits, axis=-1)
    return sample_tokens(logits, temps, top_ks, key)


def serving_rules(parallel=None):
    """Sharding rule table for serving: TP only by default (no FSDP —
    decode wants weights stationary, replicated over the size-1 data axis)."""
    from repro.config import MeshConfig, ParallelConfig
    from repro.parallel import make_rules

    return make_rules(MeshConfig(), parallel or ParallelConfig(fsdp=False, tensor_parallel=True))


@dataclass
class _RestoreTask:
    """One pending spill swap-in: ``payload`` rows destined for device block
    ``dst``.  ``cow`` marks a partial-tail restore whose canonical entry
    stays in the pool (cancel must not demote the private copy back)."""

    dst: int
    payload: dict
    cow: bool
    t0: float


class InferenceEngine:
    def __init__(
        self,
        cfg,
        params,
        *,
        max_batch: int = 4,
        max_seq: int = 512,
        eos_token: int = 1,
        seed: int = 0,
        cache_kind: str = "paged",
        block_size: int = 32,
        num_blocks: Optional[int] = None,
        cache_dtype=jnp.bfloat16,
        quantize_kv: bool | str = False,
        attn_impl: str = "xla",
        fused: bool = False,
        prefix_cache: Optional[bool] = None,
        prefill_budget: int = 0,
        policy: str = "slo",
        defrag_threshold: float = 0.5,
        spill_bytes: int = 0,
        spill_dtype: str = "cache",
        restore_budget: int = 4,
        spec_decode: str = "off",
        spec_k: int = 4,
        draft_cfg=None,
        draft_params=None,
        mesh=None,
        parallel=None,
        clock=None,
        metrics: Optional[MetricsRegistry] = None,
        tracer: Optional[Tracer] = None,
        trace_capacity: int = 4096,
        energy: Optional[EnergyBridge] = None,
    ):
        if cfg.is_encoder_only:
            raise ValueError(f"{cfg.name} is encoder-only; no decode serving")
        if cache_kind not in ("paged", "dense"):
            raise ValueError(f"cache_kind={cache_kind!r}")
        if cache_kind == "paged" and not supports_paged(cfg):
            # ssm states are O(1) per slot (nothing to page); vlm keeps the
            # grouped dense layout
            cache_kind = "dense"
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos = eos_token
        self.cache_kind = cache_kind
        self.cache_dtype = cache_dtype
        from repro.serving.kvquant import normalize_kv_quant

        quantize_kv = normalize_kv_quant(quantize_kv)  # "int8" | "fp8" | None
        if quantize_kv and cache_kind != "paged":
            warnings.warn(
                f"quantize_kv only applies to paged block pools; ignored for "
                f"cache_kind={cache_kind!r} ({cfg.name})",
                RuntimeWarning,
                stacklevel=2,
            )
        self.quantize_kv = quantize_kv if cache_kind == "paged" else None
        if self.quantize_kv and attn_impl == "pallas":
            warnings.warn(
                f"{self.quantize_kv} block pools have no Pallas kernel yet; decode and "
                "prefill chunks run the dequantizing jnp reference despite attn_impl='pallas'",
                RuntimeWarning,
                stacklevel=2,
            )
        self.attn_impl = attn_impl

        # ---- observability: one injectable clock feeds every timestamp
        # (request lifecycle, tracer), one registry collects every
        # counter/gauge/histogram, one bounded ring buffer records the
        # request-lifecycle events, and the step's phase spans go to the
        # profiler.  All host-side scalar work: no device syncs.
        self._clock = clock if clock is not None else time.monotonic
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(self._clock, trace_capacity)
        # engine-clock stamp of the step's emitted tokens (StreamEvent.t),
        # read once at the start of its emit phase; a request's first token
        # carries its first_token_t instead
        self.emit_t: Optional[float] = None
        M = self.metrics
        self._c_submitted = M.counter("engine_requests_submitted_total", "requests accepted by submit()")
        self._c_admitted = M.counter("engine_requests_admitted_total", "requests admitted into a batch slot")
        self._c_finished = M.counter("engine_requests_finished_total", "requests finished (EOS or max_new_tokens)")
        self._c_tokens = M.counter("engine_tokens_out_total", "generated tokens emitted")
        self._c_prefill_tokens = M.counter("engine_prefill_tokens_total", "prompt tokens computed (prefix hits excluded)")
        self._c_prefix_hit = M.counter("engine_prefix_hit_tokens_total", "prompt tokens served from the prefix cache")
        self._c_drafted = M.counter("engine_spec_drafted_total", "speculative candidate tokens proposed")
        self._c_accepted = M.counter("engine_spec_accepted_total", "speculative candidate tokens committed")
        self._c_energy = M.counter("engine_energy_joules_total", "IT-side joules charged to serving steps")
        self._c_preempted = M.counter("engine_preemptions_total", "scheduler evictions of running requests")
        self._c_deadline_miss = M.counter("engine_deadline_violations_total", "finished requests whose TTFT missed deadline_s")
        self._c_aborted = M.counter("engine_requests_aborted_total", "requests aborted (client cancel, deadline, migration)")
        self._c_spill_hit = M.counter("engine_spill_hit_tokens_total", "prompt tokens served from the host spill tier")
        self._c_restored = M.counter("engine_restores_total", "spilled blocks swapped back into device blocks")
        self._c_restore_cancel = M.counter("engine_restores_cancelled_total", "queued swap-ins cancelled by preempt/abort")
        self._h_queue_wait = M.histogram("engine_queue_wait_seconds", "submit to admission")
        self._h_restore_wait = M.histogram("engine_restore_wait_seconds", "swap-in queued to rows scattered on device")
        self._h_ttft = M.histogram("engine_ttft_seconds", "submit to first generated token")
        self._h_admit_first = M.histogram("engine_admit_to_first_token_seconds", "admission to first generated token")
        self._h_tpot = M.histogram("engine_tpot_seconds", "mean inter-token time per finished request")
        self._h_step = M.histogram("engine_step_seconds", "wall time of one engine step()")
        self._h_prefill_chunk = M.histogram("engine_prefill_chunk_seconds", "one chunked-prefill dispatch")
        self._g_queue = M.gauge("engine_queue_depth", "requests waiting for admission")
        self._g_active = M.gauge("engine_active_slots", "slots decoding")
        self._g_prefilling = M.gauge("engine_prefilling_slots", "slots mid chunked prefill")

        # ---- tensor parallelism: shard params over the mesh's model axis;
        # cache shardings are attached after the cache is built below.  The
        # rule tables come from parallel/sharding.py — serving defaults to
        # TP-only (no FSDP: decode wants weights stationary and replicated
        # over the size-1 data axis).
        self.mesh = mesh
        self._rules = None
        self._cache_shardings = None
        if mesh is not None:
            missing = {"data", "model"} - set(mesh.axis_names)
            if missing:
                raise ValueError(
                    f"serving mesh needs ('data', 'model') axes "
                    f"(launch.mesh.make_serving_mesh); got {mesh.axis_names}"
                )
            self._rules = serving_rules(parallel)
            self.params = params = jax.device_put(
                params, self._rules.param_shardings(cfg, mesh, params)
            )
            from repro.kernels.paged_attention_ops import kernel_shardable, model_axis_size

            if (
                attn_impl == "pallas"
                and model_axis_size(mesh) > 1
                and not kernel_shardable(mesh, cfg.num_heads, cfg.num_kv_heads)
            ):
                raise ValueError(
                    f"{cfg.name}: head counts ({cfg.num_heads}/{cfg.num_kv_heads}) "
                    f"don't divide the model axis ({model_axis_size(mesh)}); the "
                    f"Pallas paged kernels can't take a local head slice "
                    f"(use attn_impl='xla')"
                )

        # DCIM bridge (paper §IV.A): each step charges chip-seconds at an
        # occupancy-derived utilization into the seed EnergyLedger; the
        # engine then attributes the joules to the requests that did work
        self.energy = (
            energy if energy is not None else EnergyBridge(chips=mesh.size if mesh is not None else 1)
        )

        # chunked prefill (and with it prefix caching) needs a paged cache
        # and a family whose chunk state is fully captured by written K/V
        self._chunked = cache_kind == "paged" and supports_chunked_prefill(cfg)
        if prefix_cache and not self._chunked:
            warnings.warn(
                f"prefix_cache needs a paged cache and a chunk-resumable "
                f"family (dense/moe); disabled for {cfg.name} "
                f"({cache_kind}/{cfg.family})",
                RuntimeWarning,
                stacklevel=2,
            )
        if prefill_budget > 0 and not self._chunked:
            warnings.warn(
                f"prefill_budget requires chunked prefill (paged cache + "
                f"dense/moe family); {cfg.name} ({cache_kind}/{cfg.family}) "
                f"keeps the blocking admission prefill",
                RuntimeWarning,
                stacklevel=2,
            )
        # fused one-dispatch step: the scheduler emits a StepPlan of typed
        # rows and the engine lowers the whole tick (decode + prefill chunks
        # + spec verify + sampling/accept + rollback) into one jitted call.
        # It rides the chunked machinery, so it has the same family gate.
        if fused and not self._chunked:
            raise ValueError(
                f"fused=True needs a paged cache and a chunk-resumable family "
                f"(dense/moe); got {cfg.name} ({cache_kind}/{cfg.family})"
            )
        self.fused = bool(fused)
        # scheduling brain: queue ordering (SLO/FCFS), admission, preemption
        # decisions and the chunked-prefill budget live in the extracted
        # SchedulerCore; the engine provides the execution primitives
        # (try_admit / run_chunk / finish_prefill / preempt / ...) below
        if restore_budget < 1:
            raise ValueError(f"restore_budget={restore_budget} (need >= 1)")
        self.scheduler = SchedulerCore(
            self, policy=policy, prefill_budget=prefill_budget, restore_budget=restore_budget
        )
        self.defrag_threshold = defrag_threshold

        # speculative decoding rides on the chunked verify path: the k drafted
        # tokens are scored in one multi-query-token pass through the paged
        # prefill-attention machinery, so it needs a paged cache + a
        # chunk-resumable family (recurrent states can't be rolled back)
        if spec_decode not in ("off", "ngram", "draft"):
            raise ValueError(f"spec_decode={spec_decode!r}")
        if spec_decode != "off" and not self._chunked:
            warnings.warn(
                f"spec_decode needs a paged cache and a chunk-resumable "
                f"family (dense/moe); disabled for {cfg.name} "
                f"({cache_kind}/{cfg.family})",
                RuntimeWarning,
                stacklevel=2,
            )
            spec_decode = "off"
        if spec_k < 1:
            raise ValueError(f"spec_k={spec_k} (need >= 1)")
        self.spec_mode = spec_decode
        self.spec_k = spec_k
        # a verify pass writes up to spec_k positions past the committed
        # sequence; admission reserves that headroom so speculative writes
        # always land in the request's own blocks, never past its table row
        self._spec_extra = spec_k if spec_decode != "off" else 0
        self._draft: Optional[DraftModel] = None
        if self.spec_mode == "draft":
            dcfg = draft_cfg if draft_cfg is not None else make_draft_config(cfg)
            if dcfg.padded_vocab != cfg.padded_vocab:
                raise ValueError(
                    f"draft model vocab {dcfg.padded_vocab} != target {cfg.padded_vocab}"
                )
            if draft_params is None:
                draft_params = init_params(dcfg, jax.random.PRNGKey(seed + 1), jnp.float32)
            self._draft = DraftModel(
                dcfg,
                draft_params,
                max_batch=max_batch,
                max_seq=max_seq,
                seed=seed,
                metrics=self.metrics,
            )

        if cache_kind == "paged":
            self.block_size = block_size
            self.max_blocks_per_seq = -(-max_seq // block_size)
            if num_blocks is None:
                # default: same position capacity as the dense layout (+ null)
                num_blocks = max_batch * self.max_blocks_per_seq + 1
            self.num_blocks = num_blocks
            self.allocator = BlockAllocator(num_blocks)
            self.prefix = (
                PrefixIndex(self.allocator, block_size)
                if (self._chunked if prefix_cache is None else prefix_cache and self._chunked)
                else None
            )
            # allocator publishes pool occupancy into the shared registry;
            # the engine wraps the eviction callback (the prefix index set
            # its unmap hook in __post_init__) so LRU reclaims surface as
            # trace events too
            self.allocator.attach_metrics(self.metrics)
            if self.prefix is not None:
                self.prefix.attach_metrics(self.metrics)
            self._g_frag = self.metrics.gauge(
                "pool_fragmentation", "allocator free-list fragmentation"
            )
            inner_evict = self.allocator.on_evict
            def _evict_hook(block, _inner=inner_evict):
                # propagate the tier tag: the prefix index returns "spilled"
                # when the block's content was demoted to the host pool, and
                # the allocator accounts the two outcomes separately
                tier = _inner(block) if _inner is not None else None
                self.tracer.instant(
                    "spill" if tier == "spilled" else "evict",
                    track=SCHEDULER_TRACK,
                    block=block,
                )
                return tier
            self.allocator.on_evict = _evict_hook
            # host spill tier: evicted prefix blocks park in host RAM and
            # swap back in on a later hit instead of re-prefilling
            if spill_dtype not in SPILL_MODES:
                raise ValueError(f"spill_dtype={spill_dtype!r} (choose from {SPILL_MODES})")
            self.spill = None
            if spill_bytes > 0:
                if self.prefix is None:
                    warnings.warn(
                        f"spill_bytes needs the prefix cache (paged cache + "
                        f"dense/moe family); disabled for {cfg.name}",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                else:
                    spill_dtype = warn_if_fp8_over_int8(self.quantize_kv, spill_dtype)
                    self.spill = SpillPool(spill_bytes, mode=spill_dtype)
                    self.prefix.attach_spill(self.spill, self._fetch_block_rows)
                    self.spill.attach_metrics(self.metrics)
            self.tbl = np.zeros((max_batch, self.max_blocks_per_seq), np.int32)
            self._tbl_dirty = True
            make_cache = lambda: init_paged_cache(
                cfg,
                num_blocks,
                block_size,
                max_batch,
                self.max_blocks_per_seq,
                cache_dtype,
                quantized=self.quantize_kv,
            )
        else:
            self.allocator = None
            self.prefix = None
            self.spill = None
            if spill_bytes > 0:
                warnings.warn(
                    f"spill_bytes only applies to paged caches; ignored for "
                    f"cache_kind={cache_kind!r} ({cfg.name})",
                    RuntimeWarning,
                    stacklevel=2,
                )
            make_cache = lambda: make_engine_cache(cfg, max_batch, max_seq, cache_dtype)

        if mesh is None:
            self.cache = make_cache()
        else:
            # pools: head-sharded; tables / recurrent states: replicated.
            # Placing the cache up front (instead of letting the first jit
            # decide) pins every later dispatch to the same layout, and
            # building it under jit with those out-shardings means each
            # device only ever allocates its own slice of the pool.
            abstract = jax.eval_shape(make_cache)
            if cache_kind == "paged":
                self._cache_shardings = self._rules.paged_cache_shardings(cfg, mesh, abstract)
            else:
                self._cache_shardings = self._rules.cache_shardings(cfg, mesh, abstract)
            self.cache = _jit("init_cache", make_cache, out_shardings=self._cache_shardings)()

        # paged decode attention is chosen where it runs (the Pallas kernel on
        # a TPU for an unquantized pool, the jnp oracle elsewhere); the engine
        # records the choice and, per decode step, the K/V blocks it reads
        # against the table the oracle walks
        self._decode_kernel = False
        if cache_kind == "paged":
            from repro.kernels.paged_attention_ops import decode_uses_kernel
            from repro.serving.kvquant import kv_storage_dtype

            pool_dtype = kv_storage_dtype(self.quantize_kv) if self.quantize_kv else cache_dtype
            self._decode_kernel = decode_uses_kernel(pool_dtype, mesh, cfg.num_heads, cfg.num_kv_heads)
        M.gauge(
            "engine_decode_attention_kernel",
            "1 when paged decode attention runs the Pallas kernel, 0 for the jnp reference",
        ).set(float(self._decode_kernel))
        self._c_kv_blocks_read = M.counter(
            "engine_decode_kv_blocks_read_total",
            "K/V blocks decode attention reads for the decoding slots: the kernel's live blocks, or the whole table",
        )
        self._c_kv_blocks_table = M.counter(
            "engine_decode_kv_blocks_table_total", "block-table entries of the decoding slots (slots x table width)"
        )
        self.pos = np.full((max_batch,), 0, np.int32)  # next position per slot
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.done: list[Request] = []
        self._preempted_ids: set[int] = set()  # distinct requests ever evicted
        self.deadline_violations = 0  # finished with ttft > deadline_s
        self.aborts = 0  # requests aborted (cancel / deadline / migration)
        # streaming hooks (serving.async_engine): called synchronously on the
        # stepping thread — on_token(req, new_tokens) per emission batch,
        # on_finish(req) when a request completes
        self.on_token = None
        self.on_finish = None
        self._ids = itertools.count()
        self._key = jax.random.PRNGKey(seed)
        # explicit NamedSharding out-specs under a mesh: the cache tree keeps
        # its pinned layout across every dispatch (head-sharded pools,
        # replicated tables) and logits come back vocab-sharded, which the
        # jitted sampler / spec-accept consume without a gather
        if mesh is not None:
            logits2 = self._rules.logits_sharding(cfg, mesh, 2)
            logits3 = self._rules.logits_sharding(cfg, mesh, 3)
            lc_out = dict(out_shardings=(logits2, self._cache_shardings))
            lc3_out = dict(out_shardings=(logits3, self._cache_shardings))
            c_out = dict(out_shardings=self._cache_shardings)
        else:
            lc_out = lc3_out = c_out = {}
        # every cache-writing jit donates the cache, so a step or an
        # admission update touches it in place instead of holding two copies
        # of a pool sized to fill HBM.  Each program is named (``_jit``), so
        # a profiler trace reads ``jit_decode_step``, not ``jit__lambda``.
        self._decode = _jit(
            "decode_step",
            lambda p, c, t, q: decode_step(cfg, p, c, t, q, mesh=mesh),
            donate_argnums=(1,),
            **lc_out,
        )
        self._sample = _jit("sample", _sample)
        self._prefill = _jit("prefill", lambda p, b: prefill(cfg, p, b))
        self._graft = _jit(
            "graft",
            lambda c, raw, blocks, n, slot: graft_prefill_into_blocks(cfg, c, raw, blocks, n, slot),
            donate_argnums=(0,),
            **(c_out if cache_kind == "paged" else {}),
        )
        if self._chunked:
            self._chunk_step = _jit(
                "chunk_step",
                lambda p, c, t, s, row: prefill_step(
                    cfg, p, c, t, s, row, attn_impl=attn_impl, mesh=mesh
                ),
                donate_argnums=(1,),
                **lc_out,
            )
            self._copy_block = _jit("copy_block", copy_block_rows, donate_argnums=(0,), **c_out)
            # spill tier data movement: the gather is dispatched at evict
            # time (the immutable result pins the rows while the pool block
            # is reused); the scatter batches every task of one restore pass
            self._gather_rows = _jit("gather_rows", gather_block_rows)
            self._restore_rows = _jit("restore_rows", restore_block_rows, donate_argnums=(0,), **c_out)
        if self.spec_mode != "off":
            self._verify = _jit(
                "verify_step",
                lambda p, c, t, s, row: verify_step(
                    cfg, p, c, t, s, row, attn_impl=attn_impl, mesh=mesh
                ),
                donate_argnums=(1,),
                **lc3_out,
            )
            self._trunc_rows = _jit(
                "truncate_rows",
                lambda c, tbl, s, e: truncate_block_rows(c, tbl, s, e, span=spec_k + 1),
                donate_argnums=(0,),
                **c_out,
            )
        if self.fused:
            # one-dispatch step graphs.  The host sees only the per-row
            # (new_tokens, accept_counts, cut, done_flags) once per tick;
            # sampling, speculative accept and the rejected-tail rollback all
            # live inside the compiled graph.  Shapes (R, W) vary per tick
            # but are drawn from bounded bucketed sets, so jax.jit's shape
            # cache holds one compiled program per (row-bucket, width).
            eos = self.eos

            def _fused_decode_fn(p, c, tokens, pos, temps, top_ks, room, key):
                # pure-decode ticks keep decode_step's exact graph (bit-
                # identical logits to the unfused engine), sampling folded in
                logits, c = decode_step(cfg, p, c, tokens, pos, mesh=mesh)
                toks = sample_tokens(logits, temps, top_ks, key)
                done = (toks == eos) | (room <= 1)
                return toks, done, c

            def _make_fused_mixed(spec: bool):
                def fn(p, c, tokens, start, widths, tbl, drafts, valid, temps,
                       top_ks, sample_lane, room, roll_end, key, qprobs):
                    logits, c = unified_step(
                        cfg, p, c, tokens, start, widths, tbl, attn_impl=attn_impl, mesh=mesh
                    )
                    n_acc, final = fused_sample_accept(
                        logits, drafts, qprobs, valid, temps, top_ks, sample_lane, key
                    )
                    # committed emission length: first EOS inside the window,
                    # clamped by the remaining generation budget (``room``)
                    W = tokens.shape[1]
                    lanes = jnp.arange(W, dtype=jnp.int32)
                    emitted = jnp.where(
                        lanes[None, :] == n_acc[:, None],
                        final[:, None],
                        jnp.pad(drafts, ((0, 0), (0, 1))),
                    )
                    is_eos = (lanes[None, :] <= n_acc[:, None]) & (emitted == eos)
                    eos_cut = jnp.where(
                        is_eos.any(axis=1),
                        jnp.argmax(is_eos, axis=1).astype(jnp.int32) + 1,
                        jnp.int32(W + 1),
                    )
                    cut = jnp.minimum(jnp.minimum(eos_cut, room), n_acc + 1).astype(jnp.int32)
                    done = (eos_cut <= cut) | (cut >= room)
                    if spec:
                        # in-graph rollback: zero verify rows' rejected tail
                        # lanes [start+cut, roll_end) — roll_end <= start+cut
                        # makes a row a no-op (decode/chunk rows)
                        c = truncate_block_rows(c, tbl, start + cut, roll_end, span=W)
                    return final, n_acc, cut, done, c

                return fn

            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                repl = NamedSharding(mesh, PartitionSpec())
                fd_out = dict(out_shardings=(repl, repl, self._cache_shardings))
                fm_out = dict(out_shardings=(repl, repl, repl, repl, self._cache_shardings))
            else:
                fd_out = fm_out = {}
            self._fused_decode = _jit("fused_decode", _fused_decode_fn, donate_argnums=(1,), **fd_out)
            self._fused_plain = _jit("fused_step", _make_fused_mixed(False), donate_argnums=(1,), **fm_out)
            self._fused_spec = _jit("fused_spec_step", _make_fused_mixed(True), donate_argnums=(1,), **fm_out)
        self._bucketed = cfg.family in BUCKETED_FAMILIES
        self.steps = 0
        self.tokens_out = 0
        self.peak_active = 0
        self.prefill_chunks = 0
        self.prefill_tokens = 0  # prompt tokens actually run through the model
        # verify-window tokens are counted SEPARATELY: the speculative verify
        # pass rides the chunked-prefill machinery but its fed tokens are
        # decode work, not prompt work — folding them into prefill_tokens
        # would deflate prefix_hit_rate whenever spec_decode is on
        self.verify_tokens = 0
        self.prefix_hits = 0
        self.prefix_partial_hits = 0
        self.prefix_hit_tokens = 0  # prompt tokens served from cached blocks
        self.defrag_triggers = 0
        self._frees_seen = 0  # auto-defrag: only re-check after new frees
        self.spill_hits = 0  # admissions that matched >= 1 spilled block
        self.spill_hit_tokens = 0  # prompt tokens served from the host tier
        self.restores = 0  # spilled blocks swapped back onto the device
        self.restores_cancelled = 0  # queued swap-ins cancelled (preempt/abort)
        self._restore_q: list[_RestoreTask] = []  # FIFO, drained per step
        self._restoring: set[int] = set()  # dst blocks with a queued task
        self.spec_steps = 0  # verify dispatches
        self.spec_slot_steps = 0  # per-slot verify passes (spec stats denominator)
        self.spec_drafted = 0  # candidate tokens proposed (valid lanes only)
        self.spec_accepted = 0  # drafted tokens committed
        self.spec_emitted = 0  # tokens emitted via the speculative path
        # dispatch/sync accounting (the fused step's raison d'être): every
        # jitted call through the _dispatch seam and every device->host sync
        # (_host_fetch) increments these, so
        # stats() can report dispatches/syncs per step for A/B comparison
        self.dispatches_total = 0
        self.host_syncs_total = 0
        self._g_dispatches = M.gauge(
            "engine_dispatches_per_step", "jitted dispatches per engine step"
        )
        self._g_host_syncs = M.gauge(
            "engine_host_syncs_per_step", "device->host syncs per engine step"
        )

    # ------------------------------------------------------------------
    @property
    def queue(self) -> list[Request]:
        """Waiting requests in policy order — owned by the scheduler core."""
        return self.scheduler.queue

    @property
    def _prefilling(self) -> list[Request]:
        return self.scheduler.prefilling

    @property
    def prefill_budget(self) -> int:
        return self.scheduler.prefill_budget

    @property
    def has_work(self) -> bool:
        """True while any request is waiting, prefilling or decoding."""
        return bool(self.scheduler.queue) or any(s is not None for s in self.slots)

    def submit(
        self,
        prompt: list[int],
        *,
        max_new_tokens: int = 32,
        online: bool = True,
        temperature: float = 0.0,
        top_k: int = 0,
        priority: int = 0,
        deadline_s: Optional[float] = None,
    ) -> Request:
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} (need >= 1)")
        if priority < 0:
            raise ValueError(f"priority={priority} (need >= 0)")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s={deadline_s} (need > 0, or None)")
        total = len(prompt) + max_new_tokens
        if self.cache_kind == "paged":
            span = total + self._spec_extra  # worst case + speculative headroom
            if span > self.max_seq:
                headroom = f" (+{self._spec_extra} spec_k headroom)" if self._spec_extra else ""
                raise ValueError(
                    f"prompt+max_new_tokens={total}{headroom} exceeds max_seq={self.max_seq}"
                )
            if blocks_needed(span, self.block_size) > self.allocator.capacity:
                raise ValueError(
                    f"request needs {blocks_needed(span, self.block_size)} blocks, "
                    f"pool capacity is {self.allocator.capacity}"
                )
        elif self.cfg.has_attention and self.cfg.sliding_window == 0 and total > self.max_seq:
            # full-attention dense cache: positions past max_seq would wrap the
            # ring buffer and silently corrupt the oldest entries
            raise ValueError(f"prompt+max_new_tokens={total} exceeds max_seq={self.max_seq}")
        req = Request(
            req_id=next(self._ids),
            prompt=list(prompt),
            max_new_tokens=max_new_tokens,
            online=online,
            priority=priority,
            deadline_s=deadline_s,
            temperature=temperature,
            top_k=top_k,
            submit_t=self._clock(),
        )
        self.scheduler.enqueue(req)
        self._c_submitted.inc()
        self._g_queue.set(len(self.queue))
        self.tracer.instant(
            "submit",
            track=SCHEDULER_TRACK,
            req_id=req.req_id,
            prompt_len=len(req.prompt),
            online=online,
            priority=priority,
        )
        return req

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.slots) if r is None]

    # ---- scheduler ops surface (see SchedulerCore's table) -----------
    def free_slots(self) -> list[int]:
        return self._free_slots()

    def running(self) -> list[Request]:
        """Requests holding a slot (decoding or mid-prefill)."""
        return [r for r in self.slots if r is not None]

    def chunked(self) -> bool:
        return self._chunked

    def can_preempt(self) -> bool:
        # eviction+resume rides the chunk-resumable paged path: the resumed
        # context re-prefills in chunks (recurrent states can't)
        return self._chunked

    def try_admit(self, req: Request, slot: int) -> bool:
        admit = self._admit_chunked if self._chunked else self._admit_blocking
        with self.tracer.phase("engine.admit"):
            return admit(req, slot)

    def preempt(self, req: Request) -> None:
        """Evict a running request: park its committed K/V in the prefix
        cache (LRU pool), free everything else, clear its slot and mark it
        WAITING so the scheduler can requeue it.

        The cache holds K/V for positions ``[0, written)`` — for a decoding
        request ``written = len(ctx) - 1`` (the trailing generated token is
        not yet fed), for a mid-prefill one ``written = prefill_pos``.  Full
        blocks of that span are registered into the prefix index before
        release, so re-admission recovers them as a prefix hit; the partial
        tail block and unused reserve free eagerly and are recomputed on
        resume.
        """
        slot = req.slot
        # cancel in-flight spill swap-ins FIRST: cancelled entries demote
        # back to the pool (re-keyed off the device blocks), so the
        # register call below skips their chain positions and the release
        # plain-frees the never-written destination blocks
        self._cancel_restores(req)
        written = int(req.prefill_pos if req.prefilling else self.pos[slot])
        if self.prefix is not None and req.freed_blocks == 0:
            # index the committed context (prompt + generated) up to the
            # written position — sliding-window requests skip this: their
            # leading blocks are gone, the chain can't start at the root
            req.reg_block, req.reg_parent = self.prefix.register(
                req.context(),
                req.blocks,
                written,
                start_block=req.reg_block,
                parent=req.reg_parent,
            )
        kept, tail = truncate_blocks(req.blocks, written, self.block_size)
        if tail:
            self.allocator.free(tail)
        self._release_blocks(kept[req.freed_blocks :])
        req.blocks = []
        req.freed_blocks = 0
        req.prefill_pos = 0
        req.prefilling = False
        req.reg_block = 0
        req.reg_parent = 0
        req.state = RequestState.WAITING
        req.slot = None
        req.preemptions += 1
        self._preempted_ids.add(req.req_id)
        self._c_preempted.inc()
        self.slots[slot] = None
        self.pos[slot] = 0
        self.tbl[slot] = 0  # null block
        self._tbl_dirty = True
        self.cache = clear_block_row(self.cfg, self.cache, slot)
        if self._draft is not None:
            self._draft.reset(slot)
        self.tracer.instant(
            "preempt",
            track=slot_track(slot),
            req_id=req.req_id,
            committed_tokens=written,
            generated=len(req.generated),
            priority=req.priority,
        )

    # ------------------------------------------------------------------
    def find_request(self, req_id: int) -> Optional[Request]:
        """A live (waiting or active) request by id, or None."""
        for r in self.queue:
            if r.req_id == req_id:
                return r
        for r in self.slots:
            if r is not None and r.req_id == req_id:
                return r
        return None

    def abort(self, req, reason: str = "aborted") -> bool:
        """Abort a queued, prefilling or decoding request.

        Every resource the request holds is released: its slot and draft
        state clear, its tail blocks free eagerly, and the committed span
        routes through the prefix index (indexed blocks park in the LRU
        cached pool, still matchable — an aborted request's prefix work is
        not thrown away).  The request finishes with
        ``finish_reason=reason`` and ``on_finish`` fires so streams
        unblock.  Accepts a ``Request`` or a request id; returns False when
        the request is unknown or already finished (abort/finish races are
        benign).
        """
        if isinstance(req, int):
            req = self.find_request(req)
        if req is None or req.state == RequestState.DONE:
            return False
        slot = req.slot
        if req.state == RequestState.WAITING:
            if not self.scheduler.dequeue(req):
                return False
        else:  # ACTIVE: mid-prefill or decoding, holds a slot
            self.scheduler.drop_prefilling(req)
            if self.cache_kind == "paged":
                self._cancel_restores(req)
                written = int(req.prefill_pos if req.prefilling else self.pos[slot])
                kept, tail = truncate_blocks(req.blocks, written, self.block_size)
                if tail:
                    self.allocator.free(tail)
                self._release_blocks(kept[req.freed_blocks :])
                req.blocks = []
                req.freed_blocks = 0
                self.tbl[slot] = 0  # null block
                self._tbl_dirty = True
                self.cache = clear_block_row(self.cfg, self.cache, slot)
            else:
                self.cache = clear_slot(self.cfg, self.cache, slot)
            self.pos[slot] = 0
            self.slots[slot] = None
            req.prefilling = False
            req.slot = None
            if self._draft is not None:
                self._draft.reset(slot)
        req.state = RequestState.DONE
        req.finish_reason = reason
        req.done_t = self._clock()
        self.aborts += 1
        self._c_aborted.inc()
        if reason == "deadline_exceeded":
            self.deadline_violations += 1
            self._c_deadline_miss.inc()
        self.tracer.instant(
            "abort",
            track=SCHEDULER_TRACK if slot is None else slot_track(slot),
            req_id=req.req_id,
            reason=reason,
            generated=len(req.generated),
        )
        self._g_queue.set(len(self.queue))
        self.done.append(req)
        if self.on_finish is not None:
            self.on_finish(req)
        return True

    def _enforce_deadlines(self) -> None:
        """Abort requests whose TTFT deadline passed with no first token.

        ``deadline_s`` is a time-to-first-token SLO: a request that missed
        it is worthless to its (interactive) caller, so burning pool blocks
        and batch slots to finish it anyway only delays everyone else.
        Runs at the top of every ``step()``; requests that got their first
        token in time run to completion (a post-first-token overrun still
        counts into ``deadline_violations`` at finish, but never aborts).
        """
        now = self._clock()
        at_risk = [
            r
            for r in list(self.queue) + [s for s in self.slots if s is not None]
            if r.first_token_t is None and now > r.deadline_t
        ]
        for r in at_risk:
            self.abort(r, reason="deadline_exceeded")

    # ------------------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        """Power-of-two prefill length bucket (bounded trace count)."""
        if not self._bucketed:
            return n
        p = MIN_PREFILL_BUCKET
        while p < n:
            p *= 2
        return min(p, self.max_seq)

    def _run_prefill(self, req: Request):
        n = len(req.prompt)
        P = self._bucket_len(n)
        toks = req.prompt + [0] * (P - n)
        batch = {
            "tokens": jnp.asarray(toks, jnp.int32)[None, :],
            "last_index": jnp.asarray([n - 1], jnp.int32),
        }
        if self.cfg.family == "vlm":
            batch["vision_tokens"] = jnp.zeros(
                (1, self.cfg.vision.num_image_tokens, self.cfg.d_model), jnp.float32
            )
        return self._prefill(self.params, batch)

    # ------------------------------------------------------------------
    def _dispatch(self, phase: str, fn, *args):
        """Run one jitted dispatch inside an ``engine.dispatch.<phase>``
        span.  The span times the host's dispatch, not the device's work
        (no sync); every call counts one jitted dispatch (the fused-step A/B
        metric)."""
        self.dispatches_total += 1
        with self.tracer.phase(f"engine.dispatch.{phase}"):
            return fn(*args)

    def _count_decode_blocks(self, active: list[Request]) -> None:
        """The K/V blocks a decode step over ``active`` reads, from the host's
        own positions: the kernel's live blocks of each slot (its own rule,
        sliding window included), or the whole table where the oracle runs."""
        if self.cache_kind != "paged":
            return
        table = len(active) * self.max_blocks_per_seq
        read = table
        if self._decode_kernel:
            lens = self.pos[[r.slot for r in active]] + 1
            read = int(live_blocks(lens, self.block_size, self.cfg.sliding_window)[1].sum())
        self._c_kv_blocks_read.inc(read)
        self._c_kv_blocks_table.inc(table)

    def _host_fetch(self, *arrays):
        """Bring device results to the host as ONE counted sync event (the
        arrays are fetched together at the jit-call seam; per-step stats
        report the count as ``host_syncs_per_step``).  The
        ``engine.host_fetch`` span times the wait for the device."""
        self.host_syncs_total += 1
        with self.tracer.phase("engine.host_fetch"):
            return tuple(np.asarray(a) for a in arrays)

    def _note_admit(self, req: Request, slot: int) -> None:
        req.admit_t = self._clock()
        self._c_admitted.inc()
        self._h_queue_wait.observe(req.admit_t - req.submit_t)
        self.tracer.instant(
            "admit",
            track=slot_track(slot),
            req_id=req.req_id,
            prompt_len=len(req.prompt),
            prefix_hit_tokens=req.prefix_hit_tokens,
            blocks=len(req.blocks),
        )
        if req.preemptions:
            # re-admission of a previously evicted request: its committed
            # context streams back in (mostly from the prefix cache) and
            # decode continues without re-emitting the first token
            self.tracer.instant(
                "resume",
                track=slot_track(slot),
                req_id=req.req_id,
                preemptions=req.preemptions,
                generated=len(req.generated),
                recovered_tokens=req.prefill_pos,
            )

    def _release_blocks(self, blocks: list[int]) -> None:
        """Drop this request's references; the prefix index parks indexed
        blocks in the LRU cached pool, everything else frees eagerly."""
        if not blocks:
            return
        if self.prefix is not None:
            self.prefix.release(blocks)
        else:
            self.allocator.free(blocks)

    # ---- spill tier: gather / swap-in machinery ----------------------
    def _fetch_block_rows(self, block: int) -> dict:
        """One block's K/V rows off the device pool (the prefix index calls
        this at evict time, before the allocator reuses the block).  The
        jitted gather returns fresh immutable arrays, so the value stays
        pinned in the ``SpillPool`` staging ring even after the pool block
        is overwritten."""
        return self._gather_rows(self.cache, jnp.asarray(block, jnp.int32))

    def _queue_restore(self, dst: int, payload: dict, *, cow: bool, req: Request) -> None:
        self._restore_q.append(_RestoreTask(dst, payload, cow, self._clock()))
        self._restoring.add(dst)
        req.pending_restores.add(dst)

    def restoring(self, req: Request) -> bool:
        """Scheduler gate: the request's block table points at rows the
        restore pass has not scattered yet — no prefill chunk (or table
        publish) may run until the swap-ins land."""
        return bool(req.pending_restores)

    def run_restores(self, budget: int) -> int:
        """Execute up to ``budget`` queued swap-ins as ONE jitted scatter
        (rows stacked along a new block axis), then unblock every admitted
        request that was waiting on them.  Called by the scheduler between
        admission and the prefill budget each step, so restores overlap
        with the decode work of other slots instead of serializing admission."""
        if budget <= 0 or not self._restore_q:
            return 0
        tasks = self._restore_q[:budget]
        del self._restore_q[: len(tasks)]
        t0 = self._clock()
        rows = {
            name: jnp.stack([jnp.asarray(t.payload[name]) for t in tasks], axis=1)
            for name in tasks[0].payload
        }
        blocks = jnp.asarray([t.dst for t in tasks], jnp.int32)
        self.cache = self._dispatch("restore", self._restore_rows, self.cache, blocks, rows)
        now = self._clock()
        done = {t.dst for t in tasks}
        self._restoring -= done
        for r in self.slots:
            if r is not None and r.pending_restores:
                r.pending_restores -= done
        for t in tasks:
            self._h_restore_wait.observe(max(now - t.t0, 0.0))
        n = len(tasks)
        self.restores += n
        self._c_restored.inc(n)
        if self.spill is not None:
            self.spill.restores += n
        self.tracer.span(
            "restore", t0, track=SCHEDULER_TRACK, blocks=n, queued=len(self._restore_q)
        )
        return n

    def _cancel_restores(self, req: Request) -> None:
        """Drop the request's pending swap-ins (preempt/abort mid-restore).
        A task another admitted request also waits on stays queued; an
        exclusive full-block task is removed and its entry *demoted* back to
        the spill pool — the destination block was never written, so the
        rows only exist in the un-copied payload.  COW tasks just drop (the
        canonical entry never left the pool)."""
        if not req.pending_restores:
            return
        for b in sorted(req.pending_restores):
            req.pending_restores.discard(b)
            if any(
                r is not None and r is not req and b in r.pending_restores
                for r in self.slots
            ):
                continue
            task = next((t for t in self._restore_q if t.dst == b), None)
            if task is None:
                continue  # already scattered this step
            self._restore_q.remove(task)
            self._restoring.discard(b)
            self.restores_cancelled += 1
            self._c_restore_cancel.inc()
            if not task.cow and self.prefix is not None:
                self.prefix.demote(b, task.payload)
            self.tracer.instant(
                "restore_cancel", track=SCHEDULER_TRACK, block=b, req_id=req.req_id
            )

    def _admit_chunked(self, req: Request, slot: int) -> bool:
        """Prefix-matched, block-budgeted admission (no model call: context
        chunks run inside subsequent ``step()`` prefill budgets).  Returns
        False when the pool can't cover the request's unshared blocks.

        A resumed (previously preempted) request admits through the same
        path with its committed context ``prompt + generated`` in place of
        the prompt: the blocks its eviction parked in the prefix LRU match
        here, so the preempted work is mostly recovered rather than
        recomputed.

        Matched blocks may live on either tier: device entries pin by
        refcount as before; **spilled** entries (negative handles) admit as
        a cheap re-prefill — their payloads are popped from the host pool
        *before* ``alloc`` (eviction churn inside alloc can spill new
        entries and must never LRU-drop rows about to swap back in), the
        entries are ``promote``d onto freshly-allocated device blocks, and
        the actual row scatter is queued for the scheduler's budgeted
        restore pass.  A spilled partial tail copies-on-write from the
        pool's decompressed rows while the canonical entry stays put."""
        needed = blocks_needed(
            len(req.prompt) + req.max_new_tokens + self._spec_extra, self.block_size
        )
        ctx = req.context()
        full, partial = self.prefix.match(ctx) if self.prefix else ([], None)
        dev_full = [b for b in full if not is_spilled(b)]
        spilled = [b for b in full if is_spilled(b)]
        partial_spilled = partial is not None and is_spilled(partial.block)
        # spilled hits need a fresh device block each; device hits are shared
        need_new = needed - len(dev_full)
        if self.prefix is not None:
            # pin matched device blocks first so the free-count check below
            # can't hand them out as eviction victims
            self.prefix.acquire(dev_full)
            if partial is not None and not partial_spilled:
                self.prefix.acquire([partial.block])
        if need_new > self.allocator.num_free:
            if self.prefix is not None:
                self.prefix.release(dev_full)
                if partial is not None and not partial_spilled:
                    self.prefix.release([partial.block])
            return False  # out of blocks: backpressure until frees
        payloads = {h: self.spill.pop(h) for h in spilled}
        cow_payload = self.spill.get(partial.block) if partial_spilled else None
        # chain state must be read while the handles are still in the index
        # (promote re-keys them)
        reg_parent = self.prefix.parent_hash(full) if self.prefix is not None else 0
        new_blocks = self.allocator.alloc(need_new)
        ni = 0
        blocks: list[int] = []
        for b in full:
            if not is_spilled(b):
                if b in self._restoring:
                    # promoted by an earlier admission, rows still in
                    # flight: this sharer waits on the same task
                    req.pending_restores.add(b)
                blocks.append(b)
                continue
            nb = new_blocks[ni]
            ni += 1
            self.prefix.promote(b, nb)
            self._queue_restore(nb, payloads[b], cow=False, req=req)
            blocks.append(nb)
        req.blocks = blocks + new_blocks[ni:]
        matched = len(full) * self.block_size
        if partial is not None:
            # copy-on-write: the partially-shared block's rows move into the
            # request's first private block; its suffix is overwritten by the
            # first prefill chunk while the cached original stays immutable
            # (device tier) or parked in the spill pool (host tier)
            dst = new_blocks[ni]
            if partial_spilled:
                self._queue_restore(dst, cow_payload, cow=True, req=req)
            else:
                self.cache = self._copy_block(
                    self.cache,
                    jnp.asarray(partial.block, jnp.int32),
                    jnp.asarray(dst, jnp.int32),
                )
                self.prefix.release([partial.block])
            matched += partial.tokens
            self.prefix_partial_hits += 1
        if matched:
            self.prefix_hits += 1
            self.prefix_hit_tokens += matched
            req.prefix_hit_tokens += matched  # accumulates across resumes
            self._c_prefix_hit.inc(matched)
        spill_matched = len(spilled) * self.block_size + (
            partial.tokens if partial_spilled else 0
        )
        if spill_matched:
            self.spill_hits += 1
            self.spill_hit_tokens += spill_matched
            self._c_spill_hit.inc(spill_matched)
            self.tracer.instant(
                "spill_hit",
                track=slot_track(slot),
                req_id=req.req_id,
                tokens=spill_matched,
                blocks=len(spilled) + int(partial_spilled),
            )
        if self.prefix is not None:
            # registration resumes after the matched (already indexed) blocks
            req.reg_block = len(full)
            req.reg_parent = reg_parent
        req.prefill_pos = matched
        req.prefilling = True
        req.state = RequestState.ACTIVE
        req.slot = slot
        self.slots[slot] = req
        self.peak_active = max(self.peak_active, sum(r is not None for r in self.slots))
        self.pos[slot] = matched
        if self._draft is not None:
            self._draft.reset(slot)
        self._note_admit(req, slot)
        # the engine table row stays null until the prompt completes, so
        # interleaved decode steps write into the scratch null block, never
        # into a half-prefilled request's memory
        self._prefilling.append(req)
        return True

    def _admit_blocking(self, req: Request, slot: int) -> bool:
        """Legacy one-shot admission: full prefill + cache graft (hybrid's
        recurrent states, and every dense-cache family)."""
        if self.cache_kind == "paged":
            needed = blocks_needed(len(req.prompt) + req.max_new_tokens, self.block_size)
            if needed > self.allocator.num_free:
                return False  # out of blocks: backpressure until frees
        self._note_admit(req, slot)
        t0 = self._clock()
        logits, raw = self._dispatch("prefill", self._run_prefill, req)
        n = len(req.prompt)
        self.prefill_chunks += 1
        self.prefill_tokens += n
        self._c_prefill_tokens.inc(n)
        req.step_work += n
        self._h_prefill_chunk.observe(self._clock() - t0)
        self.tracer.span(
            "prefill", t0, track=slot_track(slot), req_id=req.req_id, tokens=n
        )
        if self.cache_kind == "paged":
            req.blocks = self.allocator.alloc(needed)
            self.cache = self._dispatch(
                "graft", self._graft, self.cache, raw, jnp.asarray(req.blocks, jnp.int32), n, slot
            )
            self.tbl[slot] = make_table_row(req.blocks, self.max_blocks_per_seq)
            self._tbl_dirty = True
        else:
            req_cache = decode_cache_from_prefill(
                self.cfg, raw, seq_filled=n, decode_len=self.max_seq
            )
            self.cache = write_request_into_slot(self.cfg, self.cache, req_cache, slot)
        self.pos[slot] = n
        req.state = RequestState.ACTIVE
        req.slot = slot
        self.slots[slot] = req
        self.peak_active = max(self.peak_active, sum(r is not None for r in self.slots))
        # first generated token comes from the prefill logits
        self._emit_first_token(req, logits[0])
        return True

    def _emit_first_token(self, req: Request, logits) -> None:
        self._key, sub = jax.random.split(self._key)
        self.dispatches_total += 1
        (tok,) = self._host_fetch(sample_token(logits, req.temperature, sub, top_k=req.top_k))
        self._note_first_token(req, int(tok))

    def _note_first_token(self, req: Request, tok: int) -> None:
        """First-token bookkeeping shared by the legacy path (which samples
        host-side from the final chunk's logits) and the fused path (whose
        token comes out of the one-dispatch graph)."""
        req.generated.append(tok)
        req.first_token_t = self._clock()
        self.tokens_out += 1
        self._c_tokens.inc()
        self._h_ttft.observe(req.first_token_t - req.submit_t)
        if req.admit_t is not None:
            self._h_admit_first.observe(req.first_token_t - req.admit_t)
        self.tracer.instant("first_token", track=slot_track(req.slot), req_id=req.req_id)
        if self.on_token is not None:
            self.on_token(req, [tok])
        self._finish_if_done(req)

    # ------------------------------------------------------------------
    def run_chunk(self, req: Request, c: int):
        """Run one c-token context chunk; returns the chunk's last logits."""
        with self.tracer.phase("engine.prefill_chunk"):
            return self._run_chunk(req, c)

    def _run_chunk(self, req: Request, c: int):
        ctx = req.context()
        start = req.prefill_pos
        toks = jnp.asarray(ctx[start : start + c], jnp.int32)[None]
        row = jnp.asarray(
            make_table_row(req.blocks, self.max_blocks_per_seq), jnp.int32
        )[None]
        t0 = self._clock()
        logits, self.cache = self._dispatch(
            "prefill_chunk",
            self._chunk_step,
            self.params,
            self.cache,
            toks,
            jnp.asarray([start], jnp.int32),
            row,
        )
        self._h_prefill_chunk.observe(self._clock() - t0)
        self.tracer.span(
            "prefill_chunk", t0, track=slot_track(req.slot), req_id=req.req_id,
            pos=start, tokens=c,
        )
        req.prefill_pos += c
        req.step_work += c
        self.pos[req.slot] = req.prefill_pos
        self.prefill_chunks += 1
        self.prefill_tokens += c
        self._c_prefill_tokens.inc(c)
        if self.prefix is not None:
            # index the newly-completed full context blocks (written above)
            req.reg_block, req.reg_parent = self.prefix.register(
                ctx,
                req.blocks,
                req.prefill_pos,
                start_block=req.reg_block,
                parent=req.reg_parent,
            )
        return logits

    def finish_prefill(self, req: Request, logits) -> None:
        """Context complete: publish the block table to the decode path.
        A fresh request samples its first token from the last chunk's
        logits; a resumed one already holds its first token — its trailing
        generated token is simply re-fed by the next decode step."""
        self.tbl[req.slot] = make_table_row(req.blocks, self.max_blocks_per_seq)
        self._tbl_dirty = True
        self.pos[req.slot] = req.prefill_target
        req.prefilling = False
        if not req.generated:
            self._emit_first_token(req, logits[0])

    # ------------------------------------------------------------------
    def _spec_step(self, active: list[Request]) -> int:
        """One speculative engine iteration over the decoding slots.

        Per slot: draft up to ``spec_k`` candidates (``ngram`` prompt lookup
        or the draft model), score every candidate in ONE verify pass
        (``models.verify_step`` — the chunked-prefill machinery with
        all-position logits), accept the longest target-agreeing prefix via
        ``sampler.spec_accept``, commit the accepted tokens' already-written
        K/V, and roll back the rejected tail (zero the stale rows, reset the
        position).  Slots with no draftable candidates (no n-gram match,
        one-token budget) degrade to a plain single-token step through the
        same pass.
        """
        K = self.spec_k
        V = self.cfg.padded_vocab
        with self.tracer.phase("engine.prepare"):
            tokens = np.zeros((self.max_batch, K + 1), np.int32)
            drafts = np.zeros((self.max_batch, K), np.int32)
            # draft mode carries the true proposal distributions; the ngram
            # drafter's q is a one-hot of ``drafts`` and is built on-device
            # below instead of materializing a dense (B, K, V) host array
            qprobs = np.zeros((self.max_batch, K, V), np.float32) if self._draft else None
            valid = np.zeros((self.max_batch, K), bool)
            start = np.zeros((self.max_batch,), np.int32)
            temps = np.zeros((self.max_batch,), np.float32)
            top_ks = np.zeros((self.max_batch,), np.int32)
            for r in active:
                s = r.slot
                ctx = r.prompt + r.generated
                kmax = self.scheduler.spec_window(r, K)
                if self.spec_mode == "ngram":
                    d = ngram_draft(ctx, kmax)
                else:
                    d, q = self._draft.draft(
                        s, ctx, kmax, temperature=r.temperature, top_k=r.top_k
                    )
                    if d:
                        qprobs[s, : len(d)] = q
                tokens[s, 0] = r.generated[-1]
                if d:
                    tokens[s, 1 : 1 + len(d)] = d
                    drafts[s, : len(d)] = d
                    valid[s, : len(d)] = True
                start[s] = self.pos[s]
                temps[s] = r.temperature
                top_ks[s] = r.top_k
                self.spec_slot_steps += 1
                self.spec_drafted += len(d)
                self._c_drafted.inc(len(d))
                r.step_work += K + 1  # verify feeds the whole window per slot
                self.verify_tokens += K + 1  # fed window: last committed + K lanes
            verify_args = (jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(self.tbl))
        logits, self.cache = self._dispatch(
            "verify", self._verify, self.params, self.cache, *verify_args
        )
        self.steps += 1
        self.spec_steps += 1
        with self.tracer.phase("engine.prepare"):
            self._key, sub = jax.random.split(self._key)
            drafts_j = jnp.asarray(drafts)
            q_j = (
                jnp.asarray(qprobs)
                if qprobs is not None
                else jax.nn.one_hot(drafts_j, V, dtype=jnp.float32)
            )
            accept_args = (
                logits, drafts_j, q_j, jnp.asarray(valid), jnp.asarray(temps),
                jnp.asarray(top_ks), sub,
            )
        n_acc, final = self._dispatch("sample", spec_accept, *accept_args)
        n_acc, final = self._host_fetch(n_acc, final)
        with self.tracer.phase("engine.emit"):
            self.emit_t = self._clock()
            produced, t_start, t_end = self._spec_emit(active, n_acc, final, drafts, valid, start)
        if np.any(t_end > t_start):
            # one whole-batch dispatch rolls back every slot's tail
            self.cache = self._dispatch(
                "rollback",
                self._trunc_rows,
                self.cache,
                jnp.asarray(self.tbl),
                jnp.asarray(t_start),
                jnp.asarray(t_end),
            )
        return produced

    def _spec_emit(self, active, n_acc, final, drafts, valid, start):
        """Commit each slot's accepted window, stream it, and mark rejected
        tails for rollback; returns (produced, t_start, t_end)."""
        K = self.spec_k
        produced = 0
        t_start = np.zeros((self.max_batch,), np.int32)
        t_end = np.zeros((self.max_batch,), np.int32)  # end <= start: no-op slot
        for r in active:
            s = r.slot
            na = int(n_acc[s])
            emitted = [int(drafts[s, i]) for i in range(na)] + [int(final[s])]
            # stop at the first EOS inside the accepted window
            cut = next((j + 1 for j, t in enumerate(emitted) if t == self.eos), len(emitted))
            cut = min(cut, r.max_new_tokens - len(r.generated))
            emitted = emitted[:cut]
            base = int(start[s])
            clen = len(r.prompt) + len(r.generated)  # committed ctx before this step
            r.generated.extend(emitted)
            self.pos[s] = base + cut
            produced += cut
            self.tokens_out += cut
            self._c_tokens.inc(cut)
            self.spec_accepted += min(na, cut)
            self._c_accepted.inc(min(na, cut))
            self.spec_emitted += cut
            self.tracer.instant(
                "spec_accept",
                track=slot_track(s),
                req_id=r.req_id,
                drafted=int(valid[s].sum()),
                accepted=na,
                emitted=cut,
            )
            if self.on_token is not None and emitted:
                self.on_token(r, emitted)
            if self._draft is not None:
                # the drafter absorbed its own provisional tokens; truncate
                # its view to the committed prefix (divergent feeds are
                # re-fed by the next draft call's catch-up)
                self._draft.rollback(s, clen + min(na, cut))
            self._finish_if_done(r)
            if r.state != RequestState.ACTIVE:
                continue  # blocks already truncated + released at final length
            if cut < K + 1:
                # mark the rejected tail for rollback: its K/V rows are
                # zeroed so the pool never carries live-looking rows past
                # the committed length
                t_start[s], t_end[s] = base + cut, base + K + 1
                self.tracer.instant(
                    "rollback", track=slot_track(s), req_id=r.req_id,
                    tokens=int(K + 1 - cut),
                )
            self._reclaim_window_blocks(r)
        return produced, t_start, t_end

    # ------------------------------------------------------------------
    def _finish_if_done(self, req: Request) -> None:
        if req.state != RequestState.ACTIVE:
            return
        if len(req.generated) >= req.max_new_tokens or (req.generated and req.generated[-1] == self.eos):
            req.state = RequestState.DONE
            req.finish_reason = (
                "eos" if req.generated and req.generated[-1] == self.eos else "length"
            )
            req.done_t = self._clock()
            slot = req.slot
            self._c_finished.inc()
            if req.tpot is not None:
                self._h_tpot.observe(req.tpot)
            if req.deadline_s is not None and req.ttft is not None and req.ttft > req.deadline_s:
                self.deadline_violations += 1
                self._c_deadline_miss.inc()
            self.tracer.instant(
                "finish",
                track=slot_track(slot),
                req_id=req.req_id,
                reason=req.finish_reason,
                tokens=len(req.generated),
            )
            if req.admit_t is not None:
                # one span covering the request's whole residency in its
                # slot — the per-request lane in chrome://tracing
                self.tracer.span(
                    f"req {req.req_id}",
                    req.admit_t,
                    end=req.done_t,
                    track=slot_track(slot),
                    req_id=req.req_id,
                    tokens=len(req.generated),
                    prefix_hit_tokens=req.prefix_hit_tokens,
                )
            self.slots[slot] = None
            if self.cache_kind == "paged":
                # token-level truncate at the final committed length: tail
                # blocks hold only rejected speculative writes or unused
                # reserve (dead content) — plain-freed, never parked in the
                # prefix LRU; the kept span routes through the prefix index
                final_len = len(req.prompt) + len(req.generated)
                kept, tail = truncate_blocks(req.blocks, final_len, self.block_size)
                if tail:
                    self.allocator.free(tail)
                self._release_blocks(kept[req.freed_blocks :])
                req.blocks = []
                req.freed_blocks = 0
                self.tbl[slot] = 0  # null block
                self._tbl_dirty = True
                self.cache = clear_block_row(self.cfg, self.cache, slot)
            else:
                self.cache = clear_slot(self.cfg, self.cache, slot)
            self.pos[slot] = 0
            self.done.append(req)
            if self.on_finish is not None:
                self.on_finish(req)

    # ------------------------------------------------------------------
    def _reclaim_window_blocks(self, req: Request) -> None:
        """Sliding-window archs: free blocks that have slid out of the window.

        The dense layout ring-buffers W positions; the paged layout instead
        writes every position, so without reclamation a window arch would
        hold O(total) blocks where the ring holds O(window).  A block
        covering positions [i*bs, (i+1)*bs) is dead once its last position
        can no longer be attended by any future query (positions only grow):
        (i+1)*bs - 1 <= next_pos - W.  Dead blocks drop this request's
        reference (shared prefix blocks stay alive for their other holders)
        and the table entries point back at the null block (the window mask
        already excludes those positions in both decode impls).
        """
        W = self.cfg.sliding_window
        if W <= 0:
            return
        nxt = int(self.pos[req.slot])
        d = min((nxt - W + 1) // self.block_size, len(req.blocks))
        if d <= req.freed_blocks:
            return
        self._release_blocks(req.blocks[req.freed_blocks : d])
        self.tbl[req.slot, req.freed_blocks : d] = 0
        req.freed_blocks = d
        self._tbl_dirty = True

    def _maybe_defrag(self) -> None:
        """Auto-defrag: sort the free list when scatter exceeds the
        threshold, re-checked only after new frees."""
        if self.allocator is None or self.defrag_threshold >= 1.0:
            return
        if self.allocator.total_frees == self._frees_seen:
            return
        self._frees_seen = self.allocator.total_frees
        if self.allocator.fragmentation() > self.defrag_threshold:
            self.allocator.defrag()
            self.defrag_triggers += 1

    def _sync_tables(self) -> None:
        if self.cache_kind != "paged" or not self._tbl_dirty:
            return
        L = self.cache["tbl"].shape[0]
        tbl = np.broadcast_to(self.tbl[None], (L,) + self.tbl.shape)
        if self.mesh is not None:
            # commit the replicated layout up front so the host-side update
            # never changes the compiled dispatch's input sharding signature
            self.cache["tbl"] = jax.device_put(tbl, self._cache_shardings["tbl"])
        else:
            self.cache["tbl"] = jnp.asarray(tbl)
        self._tbl_dirty = False

    # ---- fused one-dispatch step -------------------------------------
    def _fused_step(self) -> int:
        """One fused engine tick: the scheduler emits a ``StepPlan`` of
        typed rows (decode / prefill-chunk / spec-verify) and the whole
        tick's model work — including sampling, speculative accept and the
        rejected-tail rollback — runs as ONE jitted dispatch, after which
        the host reads (new_tokens, accept_counts, cut, done_flags) in one
        sync.  Pure-decode ticks route through ``decode_step``'s exact graph
        (bit-identical logits to the unfused engine); mixed ticks run every
        row through the unified chunk path (greedy token-identical).

        One scheduling difference vs the legacy walk: a request whose
        prompt completes this tick gets its first token from the fused
        graph but joins decode only NEXT tick (the legacy path runs prefill
        before collecting the decode batch, so it decodes in the same
        step).  Token sequences are unchanged; per-request step counts can
        shift by one."""
        spec = self.spec_mode != "off"
        with self.tracer.phase("engine.plan"):
            self._enforce_deadlines()
            plan = self.scheduler.plan(spec=spec)
            self.peak_active = max(self.peak_active, sum(r is not None for r in self.slots))
            if not plan.rows:
                return 0
            decode_only = not plan.chunk_rows and not spec
            if decode_only:
                active = [pr.req for pr in plan.rows]
                args = self._pack_fused_decode(active)
            else:
                packed = self._pack_fused_mixed(plan)
        if decode_only:
            return self._fused_decode_tick(active, args)
        return self._fused_mixed_tick(plan, packed)

    def _pack_fused_decode(self, active: list[Request]) -> tuple:
        """The fused decode graph's inputs after (params, cache)."""
        B = self.max_batch
        tokens = np.zeros((B, 1), np.int32)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        room = np.ones((B,), np.int32)
        for r in active:
            tokens[r.slot, 0] = r.generated[-1]
            temps[r.slot] = r.temperature
            top_ks[r.slot] = r.top_k
            room[r.slot] = r.max_new_tokens - len(r.generated)
            r.step_work += 1
        self._count_decode_blocks(active)
        self._key, sub = jax.random.split(self._key)
        return (
            jnp.asarray(tokens),
            jnp.asarray(self.pos, jnp.int32),
            jnp.asarray(temps),
            jnp.asarray(top_ks),
            jnp.asarray(room),
            sub,
        )

    def _fused_decode_tick(self, active: list[Request], args: tuple) -> int:
        """All rows are single-token decodes: one dispatch through the
        fused decode graph (``decode_step`` + in-graph ``sample_tokens``)."""
        with self.tracer.phase("engine.sync_tables"):
            self._sync_tables()
        toks, done, self.cache = self._dispatch(
            "fused_decode", self._fused_decode, self.params, self.cache, *args
        )
        toks_h, _done_h = self._host_fetch(toks, done)
        self.steps += 1
        produced = 0
        with self.tracer.phase("engine.emit"):
            self.emit_t = self._clock()
            for r in active:
                tok = int(toks_h[r.slot])
                r.generated.append(tok)
                self.pos[r.slot] += 1
                produced += 1
                self.tokens_out += 1
                self._c_tokens.inc()
                if self.on_token is not None:
                    self.on_token(r, [tok])
                self._reclaim_window_blocks(r)
                self._finish_if_done(r)
        return produced

    def _pack_fused_mixed(self, plan) -> tuple:
        """Lower a mixed ``StepPlan`` into one unified (R, W) row batch.  R
        buckets to a power of two (pad rows carry width 0 and an all-null
        table, so their lanes scatter into the null scratch block); W is the
        widest row — chunk widths are the power-of-two binary decomposition
        and the verify window is ``spec_k + 1``, so the (R, W) compile cache
        stays small.  Returns (program, its inputs after (params, cache),
        drafts, valid, start, has_verify)."""
        K = self.spec_k
        rows = plan.rows
        R0 = len(rows)
        R = 1 << max(R0 - 1, 0).bit_length()
        has_verify = any(pr.kind == "verify" for pr in rows)
        row_width = {
            id(pr): (1 if pr.kind == "decode" else K + 1 if pr.kind == "verify" else pr.take)
            for pr in rows
        }
        W = max(max(row_width.values()), 1)
        nb = self.max_blocks_per_seq
        V = self.cfg.padded_vocab
        tokens = np.zeros((R, W), np.int32)
        start = np.zeros((R,), np.int32)
        widths = np.zeros((R,), np.int32)
        tbl = np.zeros((R, nb), np.int32)  # null-block rows for pad lanes
        drafts = np.zeros((R, W - 1), np.int32)
        valid = np.zeros((R, W - 1), bool)
        qprobs = (
            np.zeros((R, W - 1, V), np.float32)
            if (self._draft is not None and has_verify)
            else None
        )
        temps = np.zeros((R,), np.float32)
        top_ks = np.zeros((R,), np.int32)
        sample_lane = np.zeros((R,), np.int32)
        room = np.full((R,), W + 1, np.int32)  # pad rows: cut never clamps
        roll_end = np.zeros((R,), np.int32)  # 0 = no rollback for this row
        for i, pr in enumerate(rows):
            r = pr.req
            temps[i] = r.temperature
            top_ks[i] = r.top_k
            widths[i] = row_width[id(pr)]
            if pr.kind == "decode":
                s = r.slot
                tokens[i, 0] = r.generated[-1]
                start[i] = self.pos[s]
                tbl[i] = self.tbl[s]
                room[i] = r.max_new_tokens - len(r.generated)
                r.step_work += 1
            elif pr.kind == "verify":
                s = r.slot
                ctx = r.prompt + r.generated
                kmax = self.scheduler.spec_window(r, K)
                if self.spec_mode == "ngram":
                    d = ngram_draft(ctx, kmax)
                else:
                    d, q = self._draft.draft(
                        s, ctx, kmax, temperature=r.temperature, top_k=r.top_k
                    )
                    if d:
                        qprobs[i, : len(d)] = q
                tokens[i, 0] = r.generated[-1]
                if d:
                    tokens[i, 1 : 1 + len(d)] = d
                    drafts[i, : len(d)] = d
                    valid[i, : len(d)] = True
                start[i] = self.pos[s]
                tbl[i] = self.tbl[s]
                room[i] = r.max_new_tokens - len(r.generated)
                roll_end[i] = int(start[i]) + K + 1
                self.spec_slot_steps += 1
                self.spec_drafted += len(d)
                self._c_drafted.inc(len(d))
                r.step_work += K + 1
                self.verify_tokens += K + 1
            else:  # prefill chunk
                c = pr.take
                if c:
                    ctx = r.prompt + r.generated
                    tokens[i, :c] = ctx[pr.start : pr.start + c]
                start[i] = pr.start
                tbl[i, : len(r.blocks)] = r.blocks
                sample_lane[i] = max(c - 1, 0)
                if pr.final:
                    room[i] = max(r.max_new_tokens - len(r.generated), 1)
        self._key, sub = jax.random.split(self._key)
        fn = self._fused_spec if has_verify else self._fused_plain
        args = (
            jnp.asarray(tokens),
            jnp.asarray(start),
            jnp.asarray(widths),
            jnp.asarray(tbl),
            jnp.asarray(drafts),
            jnp.asarray(valid),
            jnp.asarray(temps),
            jnp.asarray(top_ks),
            jnp.asarray(sample_lane),
            jnp.asarray(room),
            jnp.asarray(roll_end),
            sub,
            jnp.asarray(qprobs) if qprobs is not None else None,
        )
        return fn, args, drafts, valid, start, has_verify

    def _fused_mixed_tick(self, plan, packed: tuple) -> int:
        """Dispatch a packed mixed plan once, then apply each row's result."""
        fn, args, drafts, valid, start, has_verify = packed
        rows = plan.rows
        final, n_acc, cut, done, self.cache = self._dispatch(
            "fused_step", fn, self.params, self.cache, *args
        )
        final_h, n_acc_h, cut_h, _done_h = self._host_fetch(final, n_acc, cut, done)
        if any(pr.kind != "chunk" for pr in rows):
            self.steps += 1
        if has_verify:
            self.spec_steps += 1
        with self.tracer.phase("engine.emit"):
            self.emit_t = self._clock()
            return self._fused_mixed_emit(rows, final_h, n_acc_h, cut_h, drafts, valid, start)

    def _fused_mixed_emit(self, rows, final_h, n_acc_h, cut_h, drafts, valid, start) -> int:
        produced = 0
        for i, pr in enumerate(rows):
            r = pr.req
            if pr.kind == "chunk":
                c = pr.take
                r.prefill_pos = pr.start + c
                r.step_work += c
                self.pos[r.slot] = r.prefill_pos
                if c:
                    self.prefill_chunks += 1
                    self.prefill_tokens += c
                    self._c_prefill_tokens.inc(c)
                    self.tracer.instant(
                        "prefill_chunk", track=slot_track(r.slot), req_id=r.req_id,
                        pos=pr.start, tokens=c,
                    )
                    if self.prefix is not None:
                        ctx = r.prompt + r.generated
                        r.reg_block, r.reg_parent = self.prefix.register(
                            ctx, r.blocks, r.prefill_pos,
                            start_block=r.reg_block, parent=r.reg_parent,
                        )
                if pr.final:
                    self.scheduler.drop_prefilling(r)
                    self.tbl[r.slot] = make_table_row(r.blocks, self.max_blocks_per_seq)
                    self._tbl_dirty = True
                    self.pos[r.slot] = r.prefill_target
                    r.prefilling = False
                    if not r.generated:
                        self._note_first_token(r, int(final_h[i]))
            elif pr.kind == "decode":
                tok = int(final_h[i])
                r.generated.append(tok)
                self.pos[r.slot] += 1
                produced += 1
                self.tokens_out += 1
                self._c_tokens.inc()
                if self.on_token is not None:
                    self.on_token(r, [tok])
                self._reclaim_window_blocks(r)
                self._finish_if_done(r)
            else:  # verify
                s = r.slot
                na = int(n_acc_h[i])
                cut_i = int(cut_h[i])
                emitted = [int(drafts[i, j]) for j in range(na)] + [int(final_h[i])]
                emitted = emitted[:cut_i]
                base = int(start[i])
                clen = len(r.prompt) + len(r.generated)
                r.generated.extend(emitted)
                self.pos[s] = base + cut_i
                produced += cut_i
                self.tokens_out += cut_i
                self._c_tokens.inc(cut_i)
                self.spec_accepted += min(na, cut_i)
                self._c_accepted.inc(min(na, cut_i))
                self.spec_emitted += cut_i
                self.tracer.instant(
                    "spec_accept", track=slot_track(s), req_id=r.req_id,
                    drafted=int(valid[i].sum()), accepted=na, emitted=cut_i,
                )
                if self.on_token is not None and emitted:
                    self.on_token(r, emitted)
                if self._draft is not None:
                    self._draft.rollback(s, clen + min(na, cut_i))
                self._finish_if_done(r)
                if r.state == RequestState.ACTIVE:
                    self._reclaim_window_blocks(r)
        return produced

    def step(self) -> int:
        """One engine iteration: one scheduling pass (admission with SLO
        preemption, then the chunked-prefill budget — see
        ``scheduler.SchedulerCore``), then advance all decoding slots.
        The whole step is the ``engine.step`` span."""
        with self.tracer.phase("engine.step"):
            t0 = self._clock()
            done0 = len(self.done)
            produced = self._fused_step() if self.fused else self._legacy_step()
            with self.tracer.phase("engine.note_step"):
                self._maybe_defrag()
                self._note_step(t0, done0, produced)
            return produced

    def _legacy_step(self) -> int:
        with self.tracer.phase("engine.schedule"):
            self._enforce_deadlines()
            self.scheduler.schedule()
            self.peak_active = max(self.peak_active, sum(r is not None for r in self.slots))
            active = [r for r in self.slots if r is not None and not r.prefilling]
        if not active:
            return 0
        with self.tracer.phase("engine.sync_tables"):
            self._sync_tables()
        if self.spec_mode != "off":
            return self._spec_step(active)
        greedy = all(r.temperature <= 0.0 for r in active)
        with self.tracer.phase("engine.prepare"):
            tokens = np.zeros((self.max_batch, 1), np.int32)
            temps = np.zeros((self.max_batch,), np.float32)
            top_ks = np.zeros((self.max_batch,), np.int32)
            for r in active:
                tokens[r.slot, 0] = r.generated[-1]
                temps[r.slot] = r.temperature
                top_ks[r.slot] = r.top_k
            args = (jnp.asarray(tokens), jnp.asarray(self.pos, jnp.int32))
            self._count_decode_blocks(active)
            # the all-greedy batch (the common serving default) skips the
            # sort/categorical work: the sampling program is an argmax
            sample_args = ()
            if not greedy:
                self._key, sub = jax.random.split(self._key)
                sample_args = (jnp.asarray(temps), jnp.asarray(top_ks), sub)
        logits, self.cache = self._dispatch(
            "decode", self._decode, self.params, self.cache, *args
        )
        self.steps += 1
        sampled = self._dispatch("sample", self._sample, logits, *sample_args)
        (sampled,) = self._host_fetch(sampled)
        produced = 0
        with self.tracer.phase("engine.emit"):
            self.emit_t = self._clock()
            for r in active:
                tok = int(sampled[r.slot])
                r.generated.append(tok)
                self.pos[r.slot] += 1
                produced += 1
                self.tokens_out += 1
                self._c_tokens.inc()
                r.step_work += 1
                if self.on_token is not None:
                    self.on_token(r, [tok])
                if self.cache_kind == "paged":
                    self._reclaim_window_blocks(r)
                self._finish_if_done(r)
        return produced

    def _note_step(self, t0: float, done0: int, produced: int) -> None:
        """Per-step observability tail: step latency + span, gauges, and
        energy attribution to the requests that did work this step."""
        dt = max(self._clock() - t0, 0.0)
        self._h_step.observe(dt)
        self.tracer.span("step", t0, end=t0 + dt, track=SCHEDULER_TRACK, produced=produced)
        self._g_queue.set(len(self.queue))
        self._g_active.set(sum(r is not None and not r.prefilling for r in self.slots))
        self._g_prefilling.set(len(self._prefilling))
        if self.steps:
            self._g_dispatches.set(self.dispatches_total / self.steps)
            self._g_host_syncs.set(self.host_syncs_total / self.steps)
        if self.allocator is not None:
            self._g_frag.set(self.allocator.fragmentation())
        if self.energy is None:
            return
        # requests that computed tokens this step: still in a slot, or
        # finished during the step.  The step's IT-side joules split
        # proportional to tokens computed (prefill chunks, decode tokens,
        # verify windows)
        workers = [r for r in self.slots if r is not None and r.step_work > 0]
        workers += [r for r in self.done[done0:] if r.step_work > 0]
        occupancy = min(len(workers) / self.max_batch, 1.0)
        joules = self.energy.record_step(dt, occupancy=occupancy)
        self._c_energy.inc(joules)
        total_work = sum(r.step_work for r in workers)
        for r in workers:
            r.energy_j += joules * r.step_work / total_work
            r.step_work = 0

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        """Closed-loop drain: step the scheduler core until no request is
        waiting, prefilling or decoding.  A thin wrapper over the same
        ``step()`` the always-on ``serving.async_engine`` loop drives —
        batch drains and streaming service exercise one code path."""
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        else:
            n_queued = len(self.queue)
            n_active = sum(r is not None for r in self.slots)
            if n_queued or n_active:
                spec = ""
                if self.spec_mode != "off":
                    # surface acceptance so a drafting regression (fewer
                    # tokens/step -> more steps to drain) is visible in logs
                    rate = self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0
                    per = self.spec_emitted / self.spec_slot_steps if self.spec_slot_steps else 0.0
                    spec = (
                        f" (spec_decode={self.spec_mode}: acceptance_rate={rate:.2f}, "
                        f"accepted_per_step={per:.2f})"
                    )
                warnings.warn(
                    f"run_until_drained exhausted max_steps={max_steps} with "
                    f"{n_queued} queued and {n_active} active requests unfinished{spec}",
                    RuntimeWarning,
                    stacklevel=2,
                )
        return self.done

    # ------------------------------------------------------------------
    def cache_bytes(self, *, per_device: bool = False) -> int:
        """Device bytes held by the engine's KV cache (pools + tables).

        Global (logical) bytes by default — mesh-size invariant, so capacity
        planning reads the same number under TP=1 and TP=n.  ``per_device``
        instead sums each leaf's addressable shard: head-sharded pools count
        ``global / tp``, replicated tables count in full."""
        total = 0
        for l in jax.tree.leaves(self.cache):
            shape = l.sharding.shard_shape(l.shape) if per_device else l.shape
            total += int(np.prod(shape, dtype=np.int64)) * l.dtype.itemsize
        return total

    def stats(self) -> dict:
        """Engine counters (see docs/serving.md for the glossary and
        docs/observability.md for the histogram/trace layer).

        Returns a **defensive snapshot**: every value is a scalar or a
        freshly-built dict — mutating the result can never corrupt engine
        state, and every derived rate is division-by-zero-guarded so an
        empty or truncated drain still snapshots cleanly.

        ``mean_ttft_s`` is computed over FINISHED requests only and
        ``requests_queued`` / ``requests_active`` / ``requests_prefilling``
        report the population still in flight — a drained-with-truncation run
        (``run_until_drained`` hit ``max_steps``) is distinguishable from a
        finished one without parsing warnings.  The four populations
        PARTITION the submitted requests (``requests_active`` counts
        decoding slots only; a mid-prefill slot counts under
        ``requests_prefilling``), so ``done + queued + active + prefilling``
        equals every request ever submitted.
        """
        ttfts = [r.ttft for r in self.done if r.ttft is not None]
        s = {
            "cache_kind": self.cache_kind,
            "scheduler_policy": self.scheduler.policy,
            "preemptions": self.scheduler.preemptions,
            "requests_preempted": len(self._preempted_ids),
            "deadline_violations": self.deadline_violations,
            "requests_aborted": self.aborts,
            "requests_done": len(self.done),
            "requests_queued": len(self.queue),
            "requests_active": sum(r is not None and not r.prefilling for r in self.slots),
            "requests_prefilling": len(self._prefilling),
            "decode_steps": self.steps,
            "tokens_out": self.tokens_out,
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else None,
            "ttft_p50_s": self._h_ttft.percentile(50),
            "ttft_p99_s": self._h_ttft.percentile(99),
            "tpot_p50_s": self._h_tpot.percentile(50),
            "tpot_p99_s": self._h_tpot.percentile(99),
            "slot_utilization": (
                1.0 - len(self._free_slots()) / self.max_batch if self.max_batch else 0.0
            ),
            "peak_active": self.peak_active,
            "cache_bytes": self.cache_bytes(),
            "prefill_chunks": self.prefill_chunks,
            "prefill_tokens": self.prefill_tokens,
            "fused": self.fused,
            "dispatches_total": self.dispatches_total,
            "host_syncs_total": self.host_syncs_total,
            "dispatches_per_step": (
                self.dispatches_total / self.steps if self.steps else 0.0
            ),
            "host_syncs_per_step": (
                self.host_syncs_total / self.steps if self.steps else 0.0
            ),
        }
        if self.energy is not None:
            s["energy_joules"] = self.energy.joules
            s["joules_per_token"] = (
                self.energy.joules / self.tokens_out if self.tokens_out else 0.0
            )
        if self.mesh is not None:
            s["tp"] = int(self.mesh.shape.get("model", 1))
            s["cache_bytes_per_device"] = self.cache_bytes(per_device=True)
        if self.spec_mode != "off":
            s["spec_decode"] = self.spec_mode
            s["spec_k"] = self.spec_k
            s["spec_steps"] = self.spec_steps
            s["verify_tokens"] = self.verify_tokens
            s["drafted_tokens"] = self.spec_drafted
            s["accepted_tokens"] = self.spec_accepted
            s["acceptance_rate"] = (
                self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0
            )
            s["accepted_per_step"] = (
                self.spec_emitted / self.spec_slot_steps if self.spec_slot_steps else 0.0
            )
        if self.cache_kind == "paged":
            s["block_size"] = self.block_size
            s["defrag_triggers"] = self.defrag_triggers
            s["evictions"] = self.allocator.evictions
            s.update({f"alloc_{k}": v for k, v in self.allocator.stats().items()})
            if self.prefix is not None:
                # denominator = prompt tokens only: `prefill_tokens` is
                # incremented solely by prompt chunks / blocking prefills,
                # never by spec-decode verify windows (those accrue to
                # `verify_tokens`), so the hit rate is invariant to
                # spec_decode — regression-tested in tests/test_spec_decode.py
                served = self.prefix_hit_tokens + self.prefill_tokens
                s["prefix_hits"] = self.prefix_hits
                s["prefix_partial_hits"] = self.prefix_partial_hits
                s["prefix_hit_tokens"] = self.prefix_hit_tokens
                s["prefix_hit_rate"] = self.prefix_hit_tokens / served if served else 0.0
                s.update({f"prefix_{k}": v for k, v in self.prefix.stats().items()})
            if self.spill is not None:
                s["spill_hits"] = self.spill_hits
                s["spill_hit_tokens"] = self.spill_hit_tokens
                s["restores"] = self.restores
                s["restores_cancelled"] = self.restores_cancelled
                s["restores_pending"] = len(self._restore_q)
                s.update({f"spill_{k}": v for k, v in self.spill.stats().items()})
        return s
