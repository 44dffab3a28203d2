"""EdgeLoRA serving engine: continuous batching across heterogeneous
adapters (paper §3/§4), plus the llama.cpp-style baseline policy.

Architecture mirrors the paper: a **Server Manager** (slot state machine +
adaptive adapter selection + heterogeneous memory manager, host-side
Python) drives a **Computing Backend** (jit'd JAX prefill/decode steps over
static shapes). *All* batch-shaped compute is gathered, batched, and
scattered — not just decode:

* **SELECTING** (gather→batch): every slot whose router ``costs_forward``
  is collected, grouped by prompt bucket, and scored in one
  ``scores_batch`` call per group; per-slot scores are cached on the slot
  so pool-exhausted deferral retries never re-score.
* **PREFILL** (gather→batch→scatter): PREFILL slots are grouped by
  (prompt bucket, merged-ness), each group runs one jit'd ``[B, bucket]``
  prefill with per-row lengths and per-row adapter pool ids, and all B
  fresh KV slices land in the global cache through one vectorized
  scatter write (``_write_slots``) instead of B host-roundtrip writes.
* **GENERATE**: the decode step batches *all* slots regardless of which
  adapter each uses — Batch LoRA Inference — with per-slot adapter pool
  ids flowing into ``LoRAMode('batched', ...)``.

Groups are padded to power-of-two occupancy (rows replicate a real
request, whose duplicate scatter write is idempotent), so the jit cache
holds at most #buckets × log2(n_slots) prefill shapes.

Timing model: the engine advances a virtual clock by *measured* wall-times
of the jit'd steps, keyed by ``(kind, bucket, B)`` and charged once per
group (each unique shape is warmed uncharged on first use, so compile
never pollutes the timeline). Two simulation cost-model knobs cover the
traffic that compute steps don't measure (DESIGN.md §8):

* ``disk_bandwidth`` (bytes/s) — adapter swap-in: every pool miss costs
  ``adapter_bytes / disk_bandwidth`` sim-seconds of host→HBM transfer
  (the paper's disk→RAM swap), serialized on one transfer channel.
* ``mem_bandwidth`` (bytes/s) — weight-sized merge/unmerge traffic: the
  llamacpp and dlora-merged policies charge ``2 · adapter_bytes /
  mem_bandwidth`` per merge and per unmerge (read + write of the touched
  weight rows).

Asynchronous adapter swap-in (``EngineConfig.async_swap``, on by
default): a pool miss no longer stalls the global clock. The manager
returns a reservation whose ``ready_time`` accounts for channel
serialization; the slot parks in LOADING while every *other* slot keeps
prefilling and decoding — the clock only stalls (``load_stall_seconds``)
when all runnable slots are load-blocked. A queue-ahead prefetcher warms
the pool for waiting/requeued requests whose adapter is already known
(explicit ``adapter_id``, the edgelora_no_aas / dlora policies) or
cheaply predictable (a bookkeeping-only router scores waiting requests
for free; a preempted request's prior selection is reused), bounded by
``prefetch_depth`` and by free+evictable blocks so speculation never
evicts a pinned or sooner-needed adapter. ``async_swap=False`` reverts
to the synchronous model — each load charged to the clock at acquire.
Whenever the request→adapter mapping is residency-independent (explicit
``adapter_id``, edgelora_no_aas, llamacpp, dlora, and AAS with
``top_k=1``), token streams are bit-identical between the two modes
(only timing moves; regression-tested). Cache-aware AAS at top_k>1
consults what is resident *at selection time* by design — the paper's
quality↔latency trade — so there timing shifts can legitimately steer
selection (this is inherent to the policy, not to async swap: any
timing-shifting knob moves it).

Batched-LoRA compute backend: ``EngineConfig.lora_backend`` ('auto' by
default, falling back to ``ModelConfig.lora_backend``) selects how the
batched prefill/decode steps compute the per-request LoRA delta —
'sgmv' routes through the grouped Pallas kernels (``kernels/ops.py``,
the TPU serving path; interpret mode off-TPU), 'einsum' through the
gather-einsum reference (the CPU default). Numerics agree across
backends; ``benchmarks/batched_lora_micro.py`` reports the deltas.

KV memory backend: ``EngineConfig.kv_backend`` ('dense' | 'paged',
``None`` falling back to ``ModelConfig.kv_backend``) selects the KV
cache layout. 'dense' reserves a ``max_ctx`` ring per slot — simple,
but short-context tenants strand the memory long-context tenants need.
'paged' unifies the slots over one ``serving/kvpool.py`` block arena
(``kv_arena_blocks`` pages of ``kv_block_size`` tokens; default: the
dense-equivalent capacity): sequences hold exactly the pages their
lengths need, block tables route every jit'd gather/scatter
(``models.Model.decode_step_paged``), an exhausted arena defers
admissions and, mid-decode, LIFO-preempts the youngest slot
(restart-recompute) instead of crashing, and completions return their
pages. Token streams are bit-identical between the two backends under
every policy — the paged view reconstructs exactly the dense ring
layout — so 'paged' is purely a capacity/scheduling change
(``benchmarks/paged_kv.py`` measures the concurrency win at fixed
arena bytes; ``ServingSummary.kv_stats`` reports arena accounting).

Scheduler policies:

* ``edgelora``          — full system (adaptive adapter selection ON)
* ``edgelora_no_aas``   — adapters pinned by the request (paper's w/o-AAS)
* ``llamacpp``          — baseline: all adapters preloaded (OOM-checked
  against a memory budget), only same-adapter requests batch together,
  adapter switches merge/unmerge weights (paper §2.2, §5 baseline)
* ``dlora``             — dLoRA-style baseline (OSDI'24, paper related
  work): dynamically switches between MERGED execution (the hot adapter
  folded into W: zero LoRA overhead but same-adapter batching only) and
  UNMERGED batched execution, driven by recent queue adapter diversity
"""
from __future__ import annotations

import functools
import heapq
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.adapter_cache import (AdapterMemoryManager, PoolExhaustedError,
                                      Reservation)
from repro.core.lora import LoRAMode, resolve_lora_exec
from repro.core.router import OracleRouter, select_adapter
from repro.core.slots import Request, Slot, SlotManager, SlotState
from repro.models import build_model
from repro.serving import kvpool as kvlib
from repro.serving.kvpool import PagedKVPool
from repro.serving.metrics import ServingSummary, summarize
from repro.serving.prefix_cache import PrefixCache
from repro.serving.trace import (NO_SPAN, EngineTracer, JitRecompileError,
                                 jit_cache_report)


class OutOfMemoryError(RuntimeError):
    """Adapter working set exceeds the device memory budget (the paper's
    OOM cells in Tables 4-6)."""


@dataclass
class EngineConfig:
    n_slots: int = 8                 # γ
    top_k: int = 3                   # k (Algorithm 1)
    policy: str = "edgelora"         # edgelora | edgelora_no_aas | llamacpp
    max_ctx: int = 512               # KV capacity per slot
    # prompt padding buckets; normalized at engine init so the largest
    # bucket always covers max_ctx (no silent prompt truncation)
    prompt_buckets: Tuple[int, ...] = (32, 64, 128, 256)
    # batched-LoRA backend: 'einsum' | 'sgmv' | 'auto' | None
    # (None defers to ModelConfig.lora_backend; 'auto' → sgmv on TPU)
    lora_backend: Optional[str] = None
    # batch prompt-shaped compute across the continuous batch (False
    # reverts to one B=1 call per slot — the pre-batching baseline the
    # prefill_batching benchmark and determinism tests compare against)
    prefill_batching: bool = True
    router_batching: bool = True
    # KV memory layout: 'dense' keeps a max_ctx ring per slot (reference
    # path), 'paged' shares one block arena across slots with
    # per-sequence block tables; None defers to ModelConfig.kv_backend.
    # Token streams are bit-identical across the two — paged only changes
    # *capacity*: short contexts stop reserving max_ctx of KV, and an
    # exhausted arena defers admissions / preempts the youngest slot
    # (LIFO, restart-recompute) instead of crashing.
    kv_backend: Optional[str] = None
    kv_block_size: int = 16          # tokens per KV page
    # arena pages; None → dense-equivalent capacity (n_slots rings'
    # worth), the setting under which paged must reproduce dense exactly.
    # Smaller values overcommit: more slots than the worst case fits.
    kv_arena_blocks: Optional[int] = None
    # route the paged page-fetch through kernels/ops.paged_gather
    # (None → only where it pays: real TPU; True forces interpret mode
    # off-TPU for parity testing)
    kv_gather_kernel: Optional[bool] = None
    # shared-prefix radix KV cache (requires kv_backend='paged'): admitted
    # prompts whose block-aligned prefix matches a previously prefilled
    # prompt of the same (adapter, merged-ness) execution identity splice
    # the cached pages into their block table and prefill only the
    # suffix. Pages are ref-counted with copy-on-write on partial-block
    # append; unreferenced cached pages form an LRU pool reclaimed before
    # the deferral/preemption machinery engages. Token streams are
    # bit-identical to prefix_cache=False (regression-tested); only
    # prefill compute and arena footprint change. Unsupported model
    # families (window-local rings, int8 KV, SSM/cross state) raise at
    # engine init — see kvpool.prefix_unsupported_reason.
    prefix_cache: bool = False
    # asynchronous adapter swap-in: a pool miss books a transfer on the
    # serialized host→HBM channel and the slot waits in LOADING while
    # other slots keep running; the clock only stalls when every
    # runnable slot is load-blocked. False reverts to the synchronous
    # model (every load charged straight to the global clock at acquire
    # — the pre-async baseline the adapter_swap benchmark compares
    # against). Token streams are identical either way whenever the
    # request→adapter mapping is residency-independent (explicit
    # adapters, no_aas, llamacpp, dlora, AAS with top_k=1); cache-aware
    # AAS at top_k>1 reads residency at selection time by design, so
    # timing shifts can steer *selection* there (see module docstring).
    async_swap: bool = True
    # queue-ahead prefetch (async_swap only): warm the pool for up to
    # this many waiting/requeued requests whose adapter is already known
    # or predictable from cached router scores; 0 disables. Bounded by
    # free+evictable pool blocks — prefetch never evicts a pinned or
    # sooner-needed adapter.
    prefetch_depth: int = 4
    disk_bandwidth: float = 1.0e9    # adapter swap-in bytes/s (host->HBM)
    mem_bandwidth: float = 60.0e9    # merge/unmerge traffic (llama.cpp mode)
    memory_budget: float = 6.0e9     # adapter memory budget (llamacpp preload)
    # dlora policy: switch to merged execution when the last
    # `dlora_window` admissions used ≤ `dlora_merge_uniques` adapters
    dlora_window: int = 8
    dlora_merge_uniques: int = 2
    cache_policy: str = "lru"
    # chunked prefill: bound every prefill call to at most this many
    # prompt tokens, interleaving the remaining chunks with decode steps
    # across scheduler iterations (the head-of-line fix: a burst of long
    # prompts no longer monopolizes the step loop while GENERATE slots
    # starve). A chunk is a suffix prefill over the previously written
    # KV — the same machinery the shared-prefix cache uses — so it needs
    # the same cache shape guarantees (attention-only, full-length,
    # unquantized rings: kvpool.prefix_unsupported_reason gates at
    # init). None (default) = off: prefill paths and token streams are
    # exactly the pre-chunking engine, bit for bit. Note chunked
    # *streams* are not guaranteed bit-identical to unchunked: attention
    # over a different total key width may reassociate float sums (the
    # prefix cache deliberately keeps key widths equal to avoid this;
    # chunking trades that guarantee for bounded step times).
    prefill_chunk: Optional[int] = None
    # SLO admission control: when a queued request carries a ttft_slo
    # and its deadline already passed, reject it as 'timeout' at the
    # head of the queue; when the projected TTFT (wait so far + an EWMA
    # of recent admit→first-token times at its bucket) exceeds the
    # deadline, shed it (429-style). Rejections are recorded on the
    # request (Request.rejected), never silently dropped. Requests
    # without a ttft_slo are never rejected, so traces with no SLO
    # knobs behave exactly as before regardless of this flag.
    admission_control: bool = True
    slo_seconds: float = 6.0
    router_accuracy: float = 0.95
    time_scale: float = 1.0          # measured-seconds -> sim-seconds
    seed: int = 0


class EdgeLoRAEngine:
    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig,
                 router: Any = None, params: Any = None,
                 tracer: Optional[EngineTracer] = None) -> None:
        self.cfg = cfg
        self.ecfg = engine_cfg
        # opt-in observability (serving/trace.py): every instrumentation
        # site below guards on `tracer is not None`, so the default path
        # records nothing and stays bit-identical to an untraced engine
        self.tracer = tracer
        # concrete batched-LoRA backend for this process ('einsum'|'sgmv')
        self.lora_backend, self._sgmv_interpret = resolve_lora_exec(
            engine_cfg.lora_backend or cfg.lora_backend)
        # KV layout: EngineConfig overrides ModelConfig (same contract as
        # lora_backend); 'paged' swaps per-slot rings for the block arena
        self.kv_backend = engine_cfg.kv_backend or cfg.kv_backend
        if self.kv_backend not in ("dense", "paged"):
            raise ValueError(f"unknown kv_backend {self.kv_backend!r} "
                             "(expected 'dense' or 'paged')")
        self.paged = self.kv_backend == "paged"
        # buckets cover max_ctx so no prompt that fits the KV capacity is
        # ever silently truncated by _padded_prompt
        self._buckets = tuple(sorted(
            {min(b, engine_cfg.max_ctx) for b in engine_cfg.prompt_buckets
             if b > 0} | {engine_cfg.max_ctx}))
        self.model = build_model(cfg)
        rng = jax.random.PRNGKey(engine_cfg.seed)
        self.params = params if params is not None else self.model.init(rng)
        self.n_pool = cfg.lora.max_resident
        self.lora_pool = self.model.init_lora(
            jax.random.PRNGKey(engine_cfg.seed + 1), n_slots=self.n_pool)
        self.adapter_bytes = cfg.lora_adapter_bytes()
        self.router = router or OracleRouter(
            cfg.lora.n_adapters, accuracy=engine_cfg.router_accuracy,
            seed=engine_cfg.seed)

        if engine_cfg.policy == "llamacpp":
            total = cfg.lora.n_adapters * self.adapter_bytes
            if total > engine_cfg.memory_budget:
                raise OutOfMemoryError(
                    f"llama.cpp preloads all adapters: "
                    f"{cfg.lora.n_adapters} × {self.adapter_bytes/1e6:.2f}MB "
                    f"= {total/1e6:.2f}MB > budget "
                    f"{engine_cfg.memory_budget/1e6:.2f}MB")

        self.manager = AdapterMemoryManager(
            self.n_pool, load_fn=self._load_adapter,
            policy=engine_cfg.cache_policy,
            load_seconds=self.adapter_bytes / engine_cfg.disk_bandwidth)
        self.slots = SlotManager(engine_cfg.n_slots)
        self._build_steps()
        self._durations: Dict[Any, float] = {}
        self.busy_time = 0.0
        # init prefill is free (server start): prefill_random books no
        # transfer-channel time
        self.manager.prefill_random(list(range(
            min(cfg.lora.n_adapters, self.n_pool))))

    # ------------------------------------------------------------------
    # device-side adapter pool (heterogeneous memory manager, device face)
    # ------------------------------------------------------------------

    _LEAD_AXIS = {"layers": 1, "shared_attn": 0, "encoder": 1,
                  "decoder": 1, "cross": 1}

    def _adapter_host(self, adapter_id: int) -> Any:
        """'Disk' fetch: adapters are deterministic functions of their id
        (stand-in for real checkpoint files; same bytes, same latency)."""
        return self.model.init_lora(jax.random.PRNGKey(10_000 + adapter_id))

    def _load_adapter(self, adapter_id: int, slot: int) -> None:
        """Device-side pool write. The *cost* of the transfer is not
        charged here: the manager books it on its transfer channel and
        returns it on the reservation (the old ``_pending_load_cost``
        side-channel, retired). Traced, the host side of the write (fetch
        and dispatch) is the ``adapter.load`` span: ``bytes`` is the
        adapter's payload, ``pool_bytes`` the pool arrays the write
        replaces."""
        tr = self.tracer
        if tr is not None:
            tr.metrics.counter("load.bytes").inc(self.adapter_bytes)
        with (tr.span("adapter.load", adapter=adapter_id, slot=slot,
                      bytes=self.adapter_bytes,
                      pool_bytes=sum(p.nbytes for p in
                                     jax.tree.leaves(self.lora_pool)))
              if tr is not None else NO_SPAN):
            adapter = self._adapter_host(adapter_id)
            new_pool = {}
            for key, sub in self.lora_pool.items():
                ax = self._LEAD_AXIS[key]
                new_pool[key] = jax.tree.map(
                    lambda p, a, ax=ax: jax.lax.dynamic_update_index_in_dim(
                        p, a.astype(p.dtype), slot, axis=ax), sub,
                    adapter[key])
            self.lora_pool = new_pool

    # ------------------------------------------------------------------
    # jit'd compute steps
    # ------------------------------------------------------------------

    def _build_steps(self) -> None:
        model, cfg = self.model, self.cfg
        scale = cfg.lora.scale
        backend, interpret = self.lora_backend, self._sgmv_interpret
        self.prefix_enabled = False
        self.prefix_cache: Optional[PrefixCache] = None
        # the paged decode's page fetch (None: the jnp gather)
        self.page_gather: Optional[Callable[..., Any]] = None
        chunk = self.ecfg.prefill_chunk
        if chunk is not None and chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1 (or None to "
                             f"disable), got {chunk}")
        self.chunked = chunk is not None

        def prefill_fn(params: Any, pool: Any, tokens: Any, cache1: Any,
                       slot_id: Any, length: Any) -> Any:
            mode = LoRAMode("batched", slot_id, scale, backend, interpret)
            logits, cache1 = model.prefill(params, {"tokens": tokens},
                                           cache1, pool, mode,
                                           lengths=length)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache1

        def decode_fn(params: Any, pool: Any, tokens: Any, cache: Any,
                      pos: Any, slot_ids: Any) -> Any:
            mode = LoRAMode("batched", slot_ids, scale, backend, interpret)
            logits, cache = model.decode_step(params, tokens, cache, pos,
                                              pool, mode)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        # merged-execution variants (dlora policy): the adapter lives
        # folded into W, so the step skips LoRA math entirely
        def prefill_merged(params: Any, tokens: Any, cache1: Any,
                           length: Any) -> Any:
            logits, cache1 = model.prefill(params, {"tokens": tokens},
                                           cache1, lengths=length)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache1

        def decode_merged(params: Any, tokens: Any, cache: Any,
                          pos: Any) -> Any:
            logits, cache = model.decode_step(params, tokens, cache, pos)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        # no donation: the _timed warmup re-invokes with the same buffers
        # (donation is a TPU-memory optimization, irrelevant on the CPU path)
        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn)
        self._prefill_merged = jax.jit(prefill_merged)
        self._decode_merged = jax.jit(decode_merged)

        def write_slots(gcache: Any, bcache: Any, slot_idx: Any) -> Any:
            # every cache leaf carries batch at axis 1 (stack/group dim
            # leading); one scatter lands all B fresh KV slices at their
            # slot indices — duplicate indices (power-of-two padding rows
            # replicating a real request) write identical data, so the
            # scatter is idempotent regardless of execution order
            return jax.tree.map(
                lambda g, l: g.at[:, slot_idx].set(l.astype(g.dtype)),
                gcache, bcache)

        self._write_slots = jax.jit(write_slots)
        if not self.paged:
            if self.ecfg.prefix_cache:
                raise ValueError(
                    "prefix_cache=True requires kv_backend='paged' — the "
                    "shared pages live in the block arena")
            self.cache = self.model.init_cache(self.ecfg.n_slots,
                                               self.ecfg.max_ctx)
            if not self.chunked:
                return
            # chunked prefill shares the suffix-over-cached-prefix cache
            # contract with the prefix cache (ring index == position, no
            # quantized or recurrent state), so the same gate applies
            reason = kvlib.prefix_unsupported_reason(self.cache,
                                                     self.ecfg.max_ctx)
            if reason is not None:
                raise ValueError(
                    f"prefill_chunk unsupported for {cfg.name}: {reason}")

            def prefill_sfx_dense_fn(params: Any, pool: Any, tokens: Any,
                                     cache1: Any, gcache: Any, slot_idx: Any,
                                     sids: Any, length: Any, *,
                                     prefix_len: Any) -> Any:
                mode = LoRAMode("batched", sids, scale, backend, interpret)
                logits, cache1 = model.prefill_suffix_dense(
                    params, tokens, cache1, gcache, slot_idx, length,
                    prefix_len, pool, mode)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache1

            def prefill_sfx_dense_merged_fn(params: Any, tokens: Any,
                                            cache1: Any, gcache: Any,
                                            slot_idx: Any, length: Any, *,
                                            prefix_len: Any) -> Any:
                logits, cache1 = model.prefill_suffix_dense(
                    params, tokens, cache1, gcache, slot_idx, length,
                    prefix_len)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache1

            def dense_scatter_suffix_fn(gcache: Any, bcache: Any,
                                        slot_idx: Any, lengths: Any, *,
                                        prefix_len: Any,
                                        suffix_len: Any) -> Any:
                # land mini-ring positions [prefix_len, prefix_len+sfx)
                # into the global per-slot rings (ring index == position
                # — chunking is gated to full-length rings). K/V copy
                # unconditionally; the pos leaf masks right-pad columns
                # beyond each row's real prompt to -1, exactly like
                # _invalidate_past does for the whole-bucket path.
                # Duplicate slot_idx rows (power-of-two padding) write
                # identical data — idempotent like every group scatter.
                positions = prefix_len + jnp.arange(suffix_len,
                                                    dtype=jnp.int32)
                lb = jnp.asarray(lengths, jnp.int32)[:, None]
                valid = jnp.where(positions[None, :] < lb,
                                  positions[None, :], -1)     # [B, sfx]
                sl = slice(prefix_len, prefix_len + suffix_len)

                def walk(gnode: Any, bnode: Any) -> Any:
                    if isinstance(gnode, dict) and "k" in gnode \
                            and "pos" in gnode:
                        new = {}
                        for key, gleaf in gnode.items():
                            if key == "pos":
                                new[key] = gleaf.at[:, slot_idx, sl].set(
                                    valid.astype(gleaf.dtype))
                            else:
                                new[key] = gleaf.at[:, slot_idx, sl].set(
                                    bnode[key][:, :, sl].astype(gleaf.dtype))
                        return new
                    return {k: walk(gnode[k], bnode[k]) for k in gnode}

                return walk(gcache, bcache)

            self._prefill_sfx_dense = jax.jit(
                prefill_sfx_dense_fn, static_argnames=("prefix_len",))
            self._prefill_sfx_dense_merged = jax.jit(
                prefill_sfx_dense_merged_fn, static_argnames=("prefix_len",))
            self._dense_scatter_suffix = jax.jit(
                dense_scatter_suffix_fn,
                static_argnames=("prefix_len", "suffix_len"))
            return

        # ---- paged KV: shared page arena + per-sequence block tables --
        ecfg = self.ecfg
        bs = ecfg.kv_block_size
        template = self.model.init_cache(ecfg.n_slots, ecfg.max_ctx)
        per_seq = -(-(ecfg.max_ctx + 1) // bs)  # worst-case one-seq pages
        n_blocks = (ecfg.kv_arena_blocks if ecfg.kv_arena_blocks
                    else ecfg.n_slots * per_seq)
        if n_blocks < per_seq:
            raise ValueError(
                f"kv_arena_blocks={n_blocks} cannot hold one max_ctx="
                f"{ecfg.max_ctx} sequence ({per_seq} blocks of {bs}): "
                "a lone request could never complete")
        meta = kvlib.paged_meta(template, n_blocks, bs, ecfg.max_ctx)
        self._kv_meta = meta
        self.kvpool = PagedKVPool(n_blocks, bs)
        self.cache = kvlib.build_arena(template, meta)
        use_kernel = ecfg.kv_gather_kernel
        if use_kernel is None:  # only where it pays: real TPU
            use_kernel = jax.default_backend() == "tpu"
        if use_kernel:
            from repro.kernels.ops import paged_gather
            self.page_gather = functools.partial(
                paged_gather, interpret=jax.default_backend() != "tpu",
                use_kernel=True)
        page_gather = self.page_gather

        def paged_decode_fn(params: Any, pool: Any, tokens: Any, cache: Any,
                            tables: Any, lengths: Any, prompt_lens: Any,
                            pad_lens: Any, pos: Any, slot_ids: Any) -> Any:
            mode = LoRAMode("batched", slot_ids, scale, backend, interpret)
            logits, cache = model.decode_step_paged(
                params, tokens, cache, tables, lengths, prompt_lens,
                pad_lens, pos, pool, mode,
                meta=meta, page_gather=page_gather)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        def paged_decode_merged(params: Any, tokens: Any, cache: Any,
                                tables: Any, lengths: Any, prompt_lens: Any,
                                pad_lens: Any, pos: Any) -> Any:
            logits, cache = model.decode_step_paged(
                params, tokens, cache, tables, lengths, prompt_lens,
                pad_lens, pos,
                meta=meta, page_gather=page_gather)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        def paged_write(gcache: Any, bcache: Any, tables: Any, lengths: Any,
                        pad_lens: Any, slot_idx: Any) -> Any:
            # the paged analogue of write_slots: attention leaves land in
            # their sequences' pages, per-slot leaves (SSM state) keep
            # the dense slot scatter
            return kvlib.scatter_prefill(gcache, bcache, tables, lengths,
                                         pad_lens, slot_idx, meta)

        self._decode_paged = jax.jit(paged_decode_fn)
        self._decode_merged_paged = jax.jit(paged_decode_merged)
        self._paged_write = jax.jit(paged_write)

        # ---- shared-prefix radix cache over the arena -----------------
        # (its suffix-prefill steps double as chunked prefill's backbone,
        # so they are built whenever either feature is on)
        self.prefix_enabled = bool(ecfg.prefix_cache)
        self.prefix_cache = None
        if not (self.prefix_enabled or self.chunked):
            return
        reason = kvlib.prefix_unsupported_reason(template, ecfg.max_ctx)
        if reason is not None:
            feature = ("prefix_cache" if self.prefix_enabled
                       else "prefill_chunk")
            raise ValueError(
                f"{feature} unsupported for {cfg.name}: {reason}")
        if self.prefix_enabled:
            # PrefixCache self-wires as the pool's reclaimer (its
            # memoized reclaimable() depends on the pool's
            # refcount-change hook)
            self.prefix_cache = PrefixCache(self.kvpool, bs)

        def prefill_suffix_fn(params: Any, pool: Any, tokens: Any,
                              cache1: Any, arena: Any, tables: Any,
                              slot_id: Any, length: Any, *,
                              prefix_len: Any) -> Any:
            mode = LoRAMode("batched", slot_id, scale, backend, interpret)
            logits, cache1 = model.prefill_suffix(
                params, tokens, cache1, arena, tables, length, prefix_len,
                pool, mode, meta=meta)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache1

        def prefill_suffix_merged_fn(params: Any, tokens: Any, cache1: Any,
                                     arena: Any, tables: Any, length: Any, *,
                                     prefix_len: Any) -> Any:
            logits, cache1 = model.prefill_suffix(
                params, tokens, cache1, arena, tables, length, prefix_len,
                meta=meta)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache1

        def scatter_suffix_fn(arena: Any, mini: Any, tables: Any,
                              lengths: Any, *, prefix_len: Any,
                              suffix_len: Any) -> Any:
            return kvlib.scatter_suffix(arena, mini, tables, lengths,
                                        prefix_len, suffix_len, meta)

        def copy_block_fn(arena: Any, src: Any, dst: Any) -> Any:
            return kvlib.copy_block(arena, src, dst, meta)

        self._prefill_suffix = jax.jit(prefill_suffix_fn,
                                       static_argnames=("prefix_len",))
        self._prefill_suffix_merged = jax.jit(
            prefill_suffix_merged_fn, static_argnames=("prefix_len",))
        self._scatter_suffix = jax.jit(
            scatter_suffix_fn, static_argnames=("prefix_len", "suffix_len"))
        self._copy_block = jax.jit(copy_block_fn)

    def _fresh_cache(self, batch: int) -> Any:
        """Zeroed prefill cache for one batch group (no persistent
        per-shape templates: a template would be copied per call anyway,
        so caching it only retains dead memory)."""
        return self.model.init_cache(batch, self.ecfg.max_ctx)

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        # unreachable for admitted requests (serve() validates prompt_len
        # <= max_ctx and the largest bucket == max_ctx); never clamp —
        # clamping truncated the prompt while slot.pos advanced past it,
        # leaving decode attending to KV positions that were never written
        raise ValueError(
            f"prompt length {n} exceeds the largest bucket "
            f"{self._buckets[-1]} (max_ctx={self.ecfg.max_ctx})")

    def _slot_prompt(self, slot: Slot) -> jax.Array:
        """Bucket + right-pad the slot's prompt once; the router forward,
        batch grouping, and prefill all reuse the cached copy (the prompt
        used to be padded twice for router-forward requests)."""
        if slot.padded_prompt is None:
            slot.bucket = self._bucket(slot.request.prompt_len)
            slot.padded_prompt = self._padded_prompt(slot.request,
                                                     slot.bucket)
        return slot.padded_prompt

    def _pad_group(self, group: List[Slot]) -> List[Slot]:
        """Pad a batch group to power-of-two occupancy (capped at
        n_slots — a group can never hold more) by replicating its first
        slot: one jit shape per (bucket, 2^i) instead of per exact
        occupancy, bounding jit-cache growth. Replica rows compute the
        same values as the real row, so their scatter writes (same slot
        index, same data) are idempotent."""
        k = len(group)
        padded = min(1 << (k - 1).bit_length(), self.ecfg.n_slots)
        return group + [group[0]] * (padded - k)

    def _timed(self, key: Tuple, fn: Callable, *args: Any,
               now: Optional[float] = None,
               requests: Optional[List[Request]] = None
               ) -> Tuple[Any, float]:
        """Run fn; charge its measured duration (first call per key warms
        the jit cache and is *not* charged). With a tracer attached and
        ``now`` given, the charge lands on the trace as a compute span
        (first call per key also as a jit-compile event — the recompile
        watchdog's raw signal); ``requests`` names the real group members
        the span served (padding replicas excluded)."""
        tr = self.tracer
        warm = key not in self._durations
        if warm:
            out = fn(*args)  # compile + run (warmup, uncharged)
            with tr.span("wait", key=key) if tr is not None else NO_SPAN:
                jax.block_until_ready(out)
            t0 = time.perf_counter()  # el: allow[clock] -- _timed measures
            out = fn(*args)
            with tr.span("wait", key=key) if tr is not None else NO_SPAN:
                jax.block_until_ready(out)
            self._durations[key] = (
                time.perf_counter() - t0)  # el: allow[clock] -- _timed
        else:
            t0 = time.perf_counter()  # el: allow[clock] -- _timed measures
            out = fn(*args)
            with tr.span("wait", key=key) if tr is not None else NO_SPAN:
                jax.block_until_ready(out)
            self._durations[key] = 0.5 * self._durations[key] + 0.5 * (
                time.perf_counter() - t0)  # el: allow[clock] -- _timed
        dt = self._durations[key] * self.ecfg.time_scale
        self.busy_time += dt
        if tr is not None and now is not None:
            if warm:
                tr.compile(now, key)
            tr.compute(now, dt, key, requests)
        return out, dt

    # ------------------------------------------------------------------
    # serving loop
    # ------------------------------------------------------------------

    def serve(self, trace: List[Request],
              max_sim_time: Optional[float] = None) -> ServingSummary:
        tr = self.tracer
        with (tr.span("serve", n_requests=len(trace))
              if tr is not None else NO_SPAN):
            return self._serve(trace, max_sim_time)

    def _serve(self, trace: List[Request],
               max_sim_time: Optional[float]) -> ServingSummary:
        ecfg = self.ecfg
        for r in trace:
            if r.prompt_len > ecfg.max_ctx:
                raise ValueError(
                    f"request {r.request_id}: prompt_len {r.prompt_len} "
                    f"exceeds max_ctx {ecfg.max_ctx}; truncate the prompt "
                    f"explicitly or raise max_ctx")
        now = 0.0
        queue = sorted(trace, key=lambda r: r.arrival_time)
        completed: List[Request] = []
        rejected: List[Request] = []
        # per-phase step invocation counts + prefill group-size histogram
        # (ServingSummary surfaces them; batching makes prefill_steps +
        # router_steps drop below the number of requests served)
        self.prefill_steps = 0
        self.decode_steps = 0
        self.router_steps = 0
        self.prefill_batch_hist: Dict[int, int] = {}
        # admission state: arrivals the clock has passed sit in a
        # priority heap of (priority, class, seq, request) — class 0 for
        # requeued (KV-preempted) work, class 1 for fresh arrivals, seq
        # a monotone push counter so ties never compare Request objects.
        # With all-equal priorities the heap pops requeue-first FIFO —
        # exactly the old two-list order, so SLO-free traces admit (and
        # stream) identically to the pre-priority engine.
        self._queue = queue
        self._qi = 0
        self._ready: List[Tuple[int, int, int, Request]] = []
        self._push_seq = 0
        self._admit_counter = 0
        # SLO machinery: per-bucket EWMA of admit→first-token times (the
        # admission controller's TTFT projection) + per-scheduler-
        # iteration busy-time histogram (bounded-step-time evidence for
        # chunked prefill)
        self._ttft_ewma: Dict[int, float] = {}
        self._step_hist: Dict[str, int] = {}
        self.max_step_seconds = 0.0
        self.kv_deferrals = 0
        self.kv_preemptions = 0
        self.peak_active_slots = 0
        # adapter swap-in accounting: clock time spent waiting on the
        # transfer channel (sync charges every load here; async only the
        # jumps where all runnable slots were load-blocked), plus the
        # serve-relative load count for total-transfer-time bookkeeping.
        # The channel restarts with the clock — a previous serve()'s
        # channel_free_at must not charge phantom queueing at now=0.
        self.load_stall_seconds = 0.0
        self._serve_loads0 = self.manager.stats.loads
        self.manager.reset_channel()
        # tracing (opt-in): open the run, then wire the channel/arena
        # event hooks onto the manager and pool for the duration of this
        # serve — the hooks are read-only observers, unwired in the
        # finally below even when the loop raises mid-run
        tr = self.tracer
        try:
            if tr is not None:
                tr.begin(now, ecfg.n_slots, meta={
                    "policy": ecfg.policy, "kv_backend": self.kv_backend,
                    "lora_backend": self.lora_backend,
                    "async_swap": ecfg.async_swap,
                    "prefill_chunk": ecfg.prefill_chunk,
                    "prefix_cache": self.prefix_enabled,
                    "buckets": list(self._buckets),
                    "n_requests": len(queue)})
                self.manager.on_event = tr.channel_hook
                if self.paged:
                    self.kvpool.on_event = tr.arena_hook
            active_adapter: Optional[int] = None  # llamacpp single-active mode
            dlora_mode = "unmerged"               # dlora dynamic mode
            dlora_merged_adapter: Optional[int] = None

            def dlora_desired() -> Optional[int]:
                """Look ahead over the next window of pending requests: merge
                when the queue is dominated by few adapters (dLoRA §3).
                Requeued (KV-preempted) work re-admits first, so it leads the
                window — otherwise a drained queue could leave merged mode
                folded on an adapter the requeue can never match."""
                ahead = [r.true_adapter for r in
                         self._upcoming(ecfg.dlora_window)]
                if not ahead:
                    return dlora_mode, dlora_merged_adapter
                uniq = set(ahead)
                # merge on the HEAD's adapter only (FIFO stays serviceable)
                if len(uniq) <= ecfg.dlora_merge_uniques \
                        and ahead.count(ahead[0]) * 2 >= len(ahead):
                    return "merged", ahead[0]
                return "unmerged", None

            def arrivals_ready() -> bool:
                self._ingest(now)
                return bool(self._ready)

            while len(completed) + len(rejected) < len(queue):
                with tr.span("iter") if tr is not None else NO_SPAN:
                    if max_sim_time is not None and now > max_sim_time:
                        break
                    progressed = False
                    busy0 = self.busy_time

                    # ---- admission -------------------------------------------
                    admitted: Optional[List[int]] = ([] if tr is not None
                                                      else None)
                    with (tr.span("admit", requests=admitted)
                          if tr is not None else NO_SPAN):
                        idle = self.slots.idle()
                        if ecfg.policy == "dlora" and idle and arrivals_ready():
                            want_mode, want_adapter = dlora_desired()
                            if (want_mode, want_adapter) != (dlora_mode,
                                                             dlora_merged_adapter):
                                if self.slots.any_active:
                                    idle = []  # drain before switching modes
                                else:
                                    # unmerge old and/or merge new: weight-sized traffic
                                    cost = 0.0
                                    if dlora_merged_adapter is not None:
                                        cost += (2 * self.adapter_bytes
                                                 / ecfg.mem_bandwidth)
                                    if want_adapter is not None:
                                        cost += (2 * self.adapter_bytes
                                                 / ecfg.mem_bandwidth)
                                    now += cost
                                    dlora_mode, dlora_merged_adapter = (want_mode,
                                                                        want_adapter)
                                    if tr is not None:
                                        tr.sched(now, "merge_switch", mode=want_mode,
                                                 adapter=want_adapter, cost=cost)
                        while idle and arrivals_ready():
                            req = self._ready[0][3]
                            if ecfg.admission_control and req.ttft_slo is not None \
                                    and self._reject_expired(req, now, rejected):
                                progressed = True
                                continue  # next heap head (rejection IS progress)
                            if ecfg.policy == "dlora" and dlora_mode == "merged" \
                                    and req.true_adapter != dlora_merged_adapter:
                                break  # merged mode serves only the folded adapter
                            if self.paged and not self.kvpool.can_allocate(
                                    req.prompt_len + 1):
                                # KV arena exhausted: OutOfBlocks feeds the same
                                # deferral discipline as adapter-pool exhaustion —
                                # leave the request queued and retry once a
                                # completion (or preemption) frees pages. Checked
                                # *before* any merge-cost accounting so a deferred
                                # admission charges nothing. +1: the first decode
                                # write must never OOM right after admission.
                                self.kv_deferrals += 1
                                if tr is not None:
                                    tr.sched(now, "defer_kv", request=req)
                                break
                            if ecfg.policy == "llamacpp":
                                want = req.true_adapter
                                if active_adapter is None:
                                    active_adapter = want
                                    # merge the adapter into the base weights
                                    now += 2 * self.adapter_bytes / ecfg.mem_bandwidth
                                    if tr is not None:
                                        tr.sched(now, "merge_switch", adapter=want)
                                if want != active_adapter:
                                    if self.slots.any_active:
                                        break  # must drain before switching adapters
                                    # unmerge old + merge new
                                    now += 4 * self.adapter_bytes / ecfg.mem_bandwidth
                                    active_adapter = want
                                    if tr is not None:
                                        tr.sched(now, "merge_switch", adapter=want)
                            heapq.heappop(self._ready)
                            slot = idle.pop()
                            slot.assign(req)
                            req.admit_time = now
                            slot.admit_seq = self._admit_counter
                            self._admit_counter += 1
                            if tr is not None:
                                tr.sched(now, "admit", request=req, slot=slot.index)
                                tr.transition(now, slot.index, "idle", "selecting",
                                              req)
                            if admitted is not None:
                                admitted.append(req.request_id)
                            if self.paged:
                                self.kvpool.register(req.request_id)
                                key = (self._admission_exec_key(req, dlora_mode)
                                       if self.prefix_enabled else None)
                                if key is not None:
                                    # execution identity known at admission: splice
                                    # cached prefix pages now and allocate only the
                                    # suffix (the +1 gate headroom covers the COW
                                    # page, so this cannot OOM)
                                    slot.prefix_len = self._admit_prefix(req, key)
                                else:
                                    # AAS-routed request: adapter unknown until
                                    # selection — reserve the full prompt and swap
                                    # in shared pages at SELECTING→PREFILL
                                    self.kvpool.append_tokens(req.request_id,
                                                              req.prompt_len)
                            progressed = True
                    self.peak_active_slots = max(
                        self.peak_active_slots,
                        sum(s.state != SlotState.IDLE for s in self.slots.slots))

                    # ---- adapter selection (Algorithm 1) ---------------------
                    # batched router scoring: every SELECTING slot that needs a
                    # learned-router forward is scored in one scores_batch call
                    # per prompt bucket (same gather→batch trick as prefill);
                    # scores land in slot.sel_scores exactly as the solo path
                    # caches them, so pool-exhausted deferral semantics below are
                    # unchanged
                    with tr.span("select") if tr is not None else NO_SPAN:
                        if (ecfg.router_batching
                                and ecfg.policy not in ("dlora", "llamacpp",
                                                        "edgelora_no_aas")
                                and getattr(self.router, "costs_forward", False)):
                            unscored = [
                                s for s in self.slots.in_state(SlotState.SELECTING)
                                if s.sel_scores is None
                                and s.request.adapter_id is None]
                            score_groups: Dict[int, List[Slot]] = {}
                            for slot in unscored:
                                self._slot_prompt(slot)
                                score_groups.setdefault(slot.bucket, []).append(slot)
                            for b, group in score_groups.items():
                                rows = self._pad_group(group)
                                toks = jnp.stack([s.padded_prompt for s in rows])
                                rids = ([s.request.request_id for s in group]
                                        if tr is not None else None)
                                sb, dt = self._timed(("router", b, len(rows)),
                                                     self.router.scores_batch, toks,
                                                     now=now, requests=rids)
                                now += dt
                                self.router_steps += 1
                                sb = np.asarray(sb)  # el: allow[host-sync] -- host argmax
                                for i, slot in enumerate(group):
                                    slot.sel_scores = sb[i]
                        for slot in self.slots.in_state(SlotState.SELECTING):
                            req = slot.request
                            if ecfg.policy == "dlora":
                                req.selected_adapter = req.true_adapter
                                slot.merged = dlora_mode == "merged"
                                if not slot.merged:
                                    try:
                                        res = self._acquire(
                                            req.selected_adapter, now)
                                    except PoolExhaustedError:
                                        if tr is not None:
                                            tr.sched(now, "defer_pool", request=req)
                                        continue  # pool fully pinned: defer (see below)
                                    now = self._finish_acquire(slot, res, now)
                                else:
                                    slot.adapter_slot = 0
                                    slot.state = SlotState.PREFILL
                                    if tr is not None:
                                        tr.transition(now, slot.index, "selecting",
                                                      "prefill", req)
                                progressed = True
                                continue
                            slot.merged = False
                            if ecfg.policy == "llamacpp":
                                # baseline executes MERGED: the active adapter was
                                # folded into W at admission (cost charged there), so
                                # steps must skip LoRA math entirely — running the
                                # batched path with adapter_slot=0 would silently
                                # apply whatever adapter sits in pool slot 0
                                req.selected_adapter = req.true_adapter
                                slot.merged = True
                            elif (ecfg.policy == "edgelora_no_aas"
                                  or req.adapter_id is not None):
                                # explicit adapter: bypass adaptive
                                # selection (Alg 1 l.1)
                                req.selected_adapter = (req.adapter_id
                                                        if req.adapter_id is not None
                                                        else req.true_adapter)
                            else:
                                # scores are computed (and, for a learned router,
                                # charged) once per request and cached on the slot: a
                                # pool-exhausted deferral below must not re-roll the
                                # oracle RNG or re-charge a router forward on retry
                                scores = slot.sel_scores
                                if scores is None:
                                    if getattr(self.router, "costs_forward", False):
                                        # solo fallback (router_batching off): one
                                        # router forward ≈ one prompt pass (Table 6)
                                        toks = self._slot_prompt(slot)[None, :]
                                        rids = ([req.request_id]
                                                if tr is not None else None)
                                        sb, dt = self._timed(("router", slot.bucket, 1),
                                                             self.router.scores_batch,
                                                             toks, now=now,
                                                             requests=rids)
                                        now += dt
                                        self.router_steps += 1
                                        scores = np.asarray(sb)[0]  # el: allow[host-sync]
                                    else:
                                        scores = np.asarray(self.router.scores(req))
                                    slot.sel_scores = scores
                                # re-select from cached scores each attempt: the pool
                                # contents change while deferred, so a cached top-k
                                # adapter may become acquirable (Algorithm 1 intent)
                                aid, _ = select_adapter(scores, self.manager,
                                                        ecfg.top_k)
                                req.selected_adapter = aid
                            if ecfg.policy != "llamacpp":
                                try:
                                    res = self._acquire(
                                        req.selected_adapter, now)
                                except PoolExhaustedError:
                                    # every pool block is pinned by an in-flight
                                    # request (γ > R under adapter-diverse load):
                                    # leave the slot SELECTING and retry after a
                                    # completion unpins — pins are only held by
                                    # LOADING/PREFILL/GENERATE slots, so the loop
                                    # always progresses elsewhere
                                    if tr is not None:
                                        tr.sched(now, "defer_pool", request=req)
                                    continue
                                slot.sel_scores = None
                                now = self._finish_acquire(slot, res, now)
                            else:
                                slot.sel_scores = None
                                slot.adapter_slot = 0  # merged weights: adapter rides W
                                slot.state = SlotState.PREFILL
                                if tr is not None:
                                    tr.transition(now, slot.index, "selecting",
                                                  "prefill", req)
                            if self.prefix_enabled and \
                                    self._admission_exec_key(req, dlora_mode) is None:
                                # AAS-routed: the adapter was unknown at admission —
                                # match now and swap shared pages into the reserved
                                # table (capacity accounting stays conservative)
                                self._attach_prefix(slot)
                            progressed = True

                    # ---- async swap-in: transfers that have landed ------------
                    if ecfg.async_swap:
                        for slot in self.slots.in_state(SlotState.LOADING):
                            if slot.ready_time <= now:
                                slot.state = SlotState.PREFILL
                                if tr is not None:
                                    tr.transition(now, slot.index, "loading",
                                                  "prefill", slot.request)
                                progressed = True
                        # queue-ahead prefetch: start transfers for upcoming
                        # demand while the channel would otherwise sit idle
                        # (behind any demand loads booked this tick)
                        if ecfg.prefetch_depth > 0 and ecfg.policy != "llamacpp":
                            with (tr.span("adapter.prefetch")
                                  if tr is not None else NO_SPAN):
                                self._run_prefetch(now, dlora_mode)

                    # ---- prefill (gather→batch→scatter) ----------------------
                    prefilling = self.slots.in_state(SlotState.PREFILL)
                    if prefilling:
                        prids = ([s.request.request_id for s in prefilling]
                                 if tr is not None else None)
                        with (tr.span("prefill", requests=prids)
                              if tr is not None else NO_SPAN):
                            # group same-bucket slots (split by merged-ness: merged
                            # steps skip LoRA math entirely — and by prefix length:
                            # prefix-hit rows prefill only their suffix, a different
                            # jit shape); one jit'd [B, bucket − prefix] prefill per
                            # group — heterogeneous adapters batch fine, the
                            # SGMV/einsum delta is per-row
                            chunk = ecfg.prefill_chunk
                            groups: Dict[Tuple[int, bool, int], List[Slot]] = {}
                            for slot in prefilling:
                                self._slot_prompt(slot)
                                # chunked: progress starts at the prefix-cache hit
                                # length (those positions are already served from
                                # shared pages) and groups key off it — same-progress
                                # rows share one jit shape, like same-prefix rows do
                                if slot.prefill_pos < slot.prefix_len:
                                    slot.prefill_pos = slot.prefix_len
                                start = (slot.prefill_pos if self.chunked
                                         else slot.prefix_len)
                                groups.setdefault(
                                    (slot.bucket, slot.merged, start),
                                    []).append(slot)
                            work: List[Tuple[int, bool, int, List[Slot]]] = []
                            for (b, merged, pfx), group in groups.items():
                                if ecfg.prefill_batching:
                                    work.append((b, merged, pfx, group))
                                else:  # pre-batching baseline: one B=1 call per slot
                                    work.extend((b, merged, pfx, [s]) for s in group)
                            for b, merged, start, group in work:
                                span = b - start
                                # whole-span groups take the existing un-chunked
                                # paths (prefill_chunk=None stays bit-identical; a
                                # terminal paged chunk reuses the prefix-suffix
                                # machinery wholesale). Dense mid-prompt progress
                                # (start > 0) always routes through _prefill_chunk —
                                # _prefill_group's suffix branch is paged-only.
                                if not self.chunked or (chunk >= span
                                                        and (start == 0 or self.paged)):
                                    now += self._prefill_group(b, merged, start,
                                                               group, now)
                                else:
                                    now += self._prefill_chunk(
                                        b, merged, start, min(chunk, span), group, now)
                            progressed = True

                    # ---- batched decode (Batch LoRA Inference) ----------------
                    gen = self.slots.in_state(SlotState.GENERATE)
                    if gen and self.paged:
                        # allocate this step's page per sequence up front; a dry
                        # arena preempts the youngest admission (LIFO restart —
                        # greedy decode recomputes the identical stream later)
                        gen = self._secure_decode_blocks(gen, now)
                        progressed = True  # preemption alone is progress
                    if gen:
                        rids = ([s.request.request_id for s in gen]
                                if tr is not None else None)
                        with (tr.span("decode", requests=rids)
                              if tr is not None else NO_SPAN):
                            tokens = np.zeros((ecfg.n_slots,), np.int32)
                            pos = np.zeros((ecfg.n_slots,), np.int32)
                            sids = np.zeros((ecfg.n_slots,), np.int32)
                            for slot in gen:
                                tokens[slot.index] = slot.last_token
                                pos[slot.index] = slot.pos
                                sids[slot.index] = slot.adapter_slot
                            merged_step = (ecfg.policy == "llamacpp"
                                           or (ecfg.policy == "dlora"
                                               and dlora_mode == "merged"))
                            if self.paged:
                                tables, lengths, plens, bwlens = \
                                    self._decode_tables(gen)
                                if merged_step:
                                    (next_toks, self.cache), dt = self._timed(
                                        ("decode_merged",), self._decode_merged_paged,
                                        self.params, jnp.asarray(tokens), self.cache,
                                        tables, lengths, plens, bwlens,
                                        jnp.asarray(pos), now=now, requests=rids)
                                else:
                                    (next_toks, self.cache), dt = self._timed(
                                        ("decode",), self._decode_paged, self.params,
                                        self.lora_pool, jnp.asarray(tokens),
                                        self.cache, tables, lengths, plens, bwlens,
                                        jnp.asarray(pos), jnp.asarray(sids),
                                        now=now, requests=rids)
                            elif merged_step:
                                (next_toks, self.cache), dt = self._timed(
                                    ("decode_merged",), self._decode_merged,
                                    self.params, jnp.asarray(tokens), self.cache,
                                    jnp.asarray(pos), now=now, requests=rids)
                            else:
                                (next_toks, self.cache), dt = self._timed(
                                    ("decode",), self._decode, self.params,
                                    self.lora_pool, jnp.asarray(tokens), self.cache,
                                    jnp.asarray(pos), jnp.asarray(sids),
                                    now=now, requests=rids)
                            now += dt
                            self.decode_steps += 1
                            next_np = np.asarray(next_toks)  # el: allow[host-sync]
                            for slot in gen:
                                req = slot.request
                                slot.last_token = int(next_np[slot.index])
                                slot.pos += 1
                                req.generated += 1
                                req.tokens.append(slot.last_token)
                                if req.generated >= req.output_len \
                                        or slot.pos >= ecfg.max_ctx - 1:
                                    req.finish_time = now
                                    if ecfg.policy != "llamacpp" \
                                            and not slot.merged:
                                        self.manager.unpin(req.selected_adapter)
                                    if tr is not None:
                                        tr.transition(now, slot.index, "generate",
                                                      "idle", req)
                                    if self.paged:
                                        self.kvpool.release(req.request_id)
                                    completed.append(slot.release())
                            progressed = True

                    # ---- per-iteration step time (compute charged this tick) --
                    step_busy = self.busy_time - busy0
                    if step_busy > 0.0:
                        self._note_step(step_busy)

                    # ---- once-per-step metrics sampling (tracing only) --------
                    if tr is not None:
                        if self.paged:
                            tr.metrics.gauge("arena_blocks_used").set(
                                self.kvpool.used_blocks)
                        tr.sample(
                            now,
                            queue_depth=len(self._ready),
                            active_slots=sum(s.state != SlotState.IDLE
                                             for s in self.slots.slots),
                            decode_batch=len(gen),
                            resident_adapters=self.manager.n_resident,
                            loading_adapters=len(self.manager.loading))

                    # ---- idle / load-blocked: jump to the earliest event ------
                    if not progressed:
                        loading = self.slots.in_state(SlotState.LOADING)
                        if loading:
                            wake = min(s.ready_time for s in loading)
                            if not self._ready and self._qi < len(queue):
                                arr = max(now, queue[self._qi].arrival_time)
                                if now < arr < wake:
                                    now = arr  # an arrival may unblock admission
                                    continue
                            # every runnable slot is load-blocked: the clock
                            # stalls on the transfer channel — the serialization
                            # async swap-in exists to minimize
                            self.load_stall_seconds += max(0.0, wake - now)
                            now = max(now, wake)
                        elif self._ready:
                            continue  # unreachable in practice: ready work
                            # re-admits (or an active slot progresses) next tick
                        elif self._qi < len(queue):
                            now = max(now, queue[self._qi].arrival_time)
                        else:
                            break

            if tr is not None:
                tr.finish(now)
                # recompile watchdog: audit every shape the jit cache holds
                # against the bound the power-of-two group padding promises
                tr.watchdog_report = jit_cache_report(
                    self._durations.keys(), buckets=self._buckets,
                    n_slots=ecfg.n_slots, prefill_chunk=ecfg.prefill_chunk,
                    prefix_cache=self.prefix_enabled,
                    block_size=ecfg.kv_block_size, max_ctx=ecfg.max_ctx)
                if tr.strict_watchdog and not tr.watchdog_report["ok"]:
                    raise JitRecompileError(
                        "jit cache exceeded the documented shape bound:\n  "
                        + "\n  ".join(tr.watchdog_report["violations"]))
        finally:
            # hook hygiene (EL006): the observers wired above must
            # never outlive this serve() — a mid-loop exception (pool
            # error, strict-watchdog raise) would otherwise leak them
            # into the next, possibly untraced, run
            self.manager.on_event = None
            if self.paged:
                self.kvpool.on_event = None
        duration = max(now, 1e-9)
        kv_stats = None
        if self.paged:
            kv_stats = {"backend": "paged",
                        "n_blocks": self.kvpool.n_blocks,
                        "block_size": self.kvpool.block_size,
                        **self.kvpool.stats.as_dict(),
                        "deferrals": self.kv_deferrals,
                        "preemptions": self.kv_preemptions}
        prefix_stats = (self.prefix_cache.summary()
                        if self.prefix_enabled else None)
        mst = self.manager.stats
        total_load = ((mst.loads - self._serve_loads0)
                      * self.manager.load_seconds)
        swap_stats = {
            "mode": "async" if ecfg.async_swap else "sync",
            "load_seconds_total": total_load,
            "load_stall_seconds": self.load_stall_seconds,
            "overlapped_load_seconds": max(
                0.0, total_load - self.load_stall_seconds),
            "prefetch_issued": mst.prefetch_issued,
            "prefetch_hits": mst.prefetch_hits,
            "prefetch_waste": mst.prefetch_waste,
            "cancelled_loads": mst.cancelled_loads,
        }
        return summarize(queue, duration, ecfg.slo_seconds,
                         cache_stats=self.manager.stats,
                         energy_proxy=self.busy_time / duration,
                         step_stats={
                             "prefill_steps": self.prefill_steps,
                             "decode_steps": self.decode_steps,
                             "router_steps": self.router_steps,
                             "prefill_batch_hist": dict(
                                 self.prefill_batch_hist),
                             "peak_active_slots": self.peak_active_slots,
                             "kv_stats": kv_stats,
                             "prefix_stats": prefix_stats,
                             "swap_stats": swap_stats,
                             "step_time_hist":
                                 dict(self._step_hist) or None,
                             "max_step_seconds":
                                 (self.max_step_seconds
                                  if self._step_hist else None),
                             "latency_breakdown":
                                 (tr.breakdown_summary()
                                  if tr is not None else None),
                         })

    # ------------------------------------------------------------------
    # priority admission, SLO shedding, step-time accounting
    # ------------------------------------------------------------------

    def _push_ready(self, req: Request, requeued: bool = False) -> None:
        """Enqueue an admissible request. Requeued (KV-preempted) work
        gets class 0 so it re-admits ahead of same-priority arrivals —
        the old two-list discipline, now one heap."""
        heapq.heappush(self._ready, (req.priority, 0 if requeued else 1,
                                     self._push_seq, req))
        self._push_seq += 1

    def _ingest(self, now: float) -> None:
        """Move every arrival the clock has passed into the ready heap
        (the arrival-sorted trace makes this a pointer walk)."""
        q = self._queue
        while self._qi < len(q) and q[self._qi].arrival_time <= now:
            self._push_ready(q[self._qi])
            self._qi += 1

    def _upcoming(self, n: int) -> List[Request]:
        """The next ``n`` requests admission will see, in order: heap
        order over the ready set, then future arrivals — the lookahead
        window dlora's merge heuristic and the prefetcher scan."""
        head = [e[3] for e in heapq.nsmallest(n, self._ready)]
        return (head + self._queue[self._qi:self._qi + n])[:n]

    def _reject_expired(self, req: Request, now: float,
                        rejected: List[Request]) -> bool:
        """Admission control at the heap head (the request is about to
        take a slot). 'timeout': its TTFT deadline already passed while
        it queued. 'shed': the projected TTFT — wait so far plus the
        per-bucket EWMA of recent admit→first-token times — exceeds the
        deadline, so serving it would waste a slot on a guaranteed miss
        (429-style early rejection). No estimate yet (cold bucket) →
        never shed: the controller only acts on evidence. Returns True
        if the request was popped and recorded."""
        wait = now - req.arrival_time
        if wait >= req.ttft_slo:
            why = "timeout"
        else:
            est = self._ttft_ewma.get(self._bucket(req.prompt_len))
            if est is None or wait + est <= req.ttft_slo:
                return False
            why = "shed"
        heapq.heappop(self._ready)
        req.rejected = why
        req.reject_time = now
        rejected.append(req)
        if self.tracer is not None:
            self.tracer.sched(now, why, request=req, wait=wait)
        return True

    def _note_ttft(self, bucket: int, req: Request, t_first: float) -> None:
        """Feed the admission controller's per-bucket admit→first-token
        EWMA (0.5/0.5, like the step-timing EWMA)."""
        if req.admit_time is None:
            return
        obs = max(0.0, t_first - req.admit_time)
        prev = self._ttft_ewma.get(bucket)
        self._ttft_ewma[bucket] = (obs if prev is None
                                   else 0.5 * prev + 0.5 * obs)

    def _note_step(self, dt: float) -> None:
        """Bin one scheduler iteration's charged compute seconds into
        the power-of-two-millisecond step histogram."""
        self.max_step_seconds = max(self.max_step_seconds, dt)
        ms = dt * 1e3
        if ms <= 0.125:
            key = "le_0.125ms"
        else:
            exp = min(14, math.ceil(math.log2(ms)))  # cap: "le_16384ms"
            key = f"le_{2.0 ** exp:g}ms"
        self._step_hist[key] = self._step_hist.get(key, 0) + 1

    def _prefill_group(self, bucket: int, merged: bool, prefix_len: int,
                       group: List[Slot], now: float) -> float:
        """Run one batched prefill over ``group`` (same bucket, same
        merged-ness, same prefix length, mixed adapters) and scatter all
        fresh KV slices into the global cache in one vectorized write.
        Prefix-hit groups (prefix_len > 0, paged + prefix cache only)
        run the suffix-only prefill over their spliced block tables.
        Returns the wall-time charged for the group (once, not per
        member)."""
        tr = self.tracer
        rids = ([s.request.request_id for s in group]
                if tr is not None else None)
        rows = self._pad_group(group)
        lengths = jnp.asarray(
            np.fromiter((s.request.prompt_len for s in rows), np.int32,
                        count=len(rows)))
        cacheb = self._fresh_cache(len(rows))
        tables = None
        if self.paged:
            # per-row block tables (padded replica rows share the real
            # row's sequence, so their duplicate page writes are
            # idempotent exactly like duplicate slot indices)
            mb = self._kv_meta.max_blocks
            tables = jnp.asarray(np.stack(
                [self.kvpool.block_table(s.request.request_id, mb)
                 for s in rows]))
        if prefix_len:
            # suffix-only prefill: the padded prompt minus its cached
            # prefix columns keeps key widths equal to the cold full
            # prefill (bit-exact streams), while compute shrinks by
            # prefix_len / bucket
            toks = jnp.stack([s.padded_prompt[prefix_len:] for s in rows])
            if merged:
                fn = functools.partial(self._prefill_suffix_merged,
                                       prefix_len=prefix_len)
                (first, cacheb), dt = self._timed(
                    ("prefill_sfx_merged", bucket, prefix_len, len(rows)),
                    fn, self.params, toks, cacheb, self.cache, tables,
                    lengths, now=now, requests=rids)
            else:
                sids = jnp.asarray(
                    np.fromiter((s.adapter_slot for s in rows), np.int32,
                                count=len(rows)))
                fn = functools.partial(self._prefill_suffix,
                                       prefix_len=prefix_len)
                (first, cacheb), dt = self._timed(
                    ("prefill_sfx", bucket, prefix_len, len(rows)),
                    fn, self.params, self.lora_pool, toks, cacheb,
                    self.cache, tables, sids, lengths,
                    now=now, requests=rids)
            self.cache = self._scatter_suffix(
                self.cache, cacheb, tables, lengths,
                prefix_len=prefix_len, suffix_len=bucket - prefix_len)
        else:
            toks = jnp.stack([s.padded_prompt for s in rows])
            if merged:
                (first, cacheb), dt = self._timed(
                    ("prefill_merged", bucket, len(rows)),
                    self._prefill_merged, self.params, toks, cacheb,
                    lengths, now=now, requests=rids)
            else:
                sids = jnp.asarray(
                    np.fromiter((s.adapter_slot for s in rows), np.int32,
                                count=len(rows)))
                (first, cacheb), dt = self._timed(
                    ("prefill", bucket, len(rows)), self._prefill,
                    self.params, self.lora_pool, toks, cacheb, sids,
                    lengths, now=now, requests=rids)
            slot_idx = jnp.asarray(
                np.fromiter((s.index for s in rows), np.int32,
                            count=len(rows)))
            if self.paged:
                bwlens = jnp.full((len(rows),), bucket, jnp.int32)
                self.cache = self._paged_write(self.cache, cacheb, tables,
                                               lengths, bwlens, slot_idx)
            else:
                self.cache = self._write_slots(self.cache, cacheb,
                                               slot_idx)
        self.prefill_steps += 1
        self.prefill_batch_hist[len(group)] = \
            self.prefill_batch_hist.get(len(group), 0) + 1
        first_np = np.asarray(first)  # el: allow[host-sync] -- output stream
        for i, slot in enumerate(group):
            req = slot.request
            slot.pos = req.prompt_len
            slot.last_token = int(first_np[i])
            req.first_token_time = now + dt
            req.generated = 1
            req.tokens = [slot.last_token]
            slot.state = SlotState.GENERATE
            if tr is not None:
                # the group's charged step ends at now + dt — exactly
                # when the request's first token exists
                tr.transition(now + dt, slot.index, "prefill",
                              "generate", req)
            self._note_ttft(slot.bucket, req, now + dt)
        if self.prefix_enabled:
            # index every full prompt block (cold rows donate fresh
            # pages; warm rows walk their matched path — a no-op except
            # for newly written private tail blocks)
            for slot in group:
                self.prefix_cache.insert(
                    self._exec_key(slot), slot.request.prompt_tokens,
                    self.kvpool.tables[slot.request.request_id])
        return dt

    def _prefill_chunk(self, bucket: int, merged: bool, start: int,
                       width: int, group: List[Slot], now: float) -> float:
        """One bounded slice of chunked prefill: run prompt positions
        [start, start + width) for every slot in ``group`` (same bucket,
        same merged-ness, same progress) as a suffix prefill over the KV
        earlier chunks wrote, scatter the fresh slice, and either advance
        ``prefill_pos`` (more chunks pending — the slot stays PREFILL and
        decode steps interleave before the next chunk) or emit the first
        token and enter GENERATE (terminal chunk). start == 0 reuses the
        plain prefill step at chunk width; later chunks reuse the
        prefix-suffix machinery (paged) or its dense sibling. Timing keys
        are shape-keyed exactly like the un-chunked paths, so a chunk
        costs what a same-shape prefill costs. Returns the wall-time
        charged for the group."""
        tr = self.tracer
        rids = ([s.request.request_id for s in group]
                if tr is not None else None)
        rows = self._pad_group(group)
        end = start + width
        real = np.fromiter((s.request.prompt_len for s in rows), np.int32,
                           count=len(rows))
        # tokens past the chunk don't exist yet: clamp the lengths the
        # step sees so every row's last-token gather lands inside the
        # chunk (rows finishing here read their real first-token logits;
        # continuing rows read a junk position nobody uses). Scatters
        # get the REAL lengths — right-pad columns must stay invalid.
        lengths = jnp.asarray(np.minimum(real, end))
        cacheb = self._fresh_cache(len(rows))
        toks = jnp.stack([s.padded_prompt[start:end] for s in rows])
        sids = None
        if not merged:
            sids = jnp.asarray(
                np.fromiter((s.adapter_slot for s in rows), np.int32,
                            count=len(rows)))
        if self.paged:
            mb = self._kv_meta.max_blocks
            tables = jnp.asarray(np.stack(
                [self.kvpool.block_table(s.request.request_id, mb)
                 for s in rows]))
            if start == 0:
                if merged:
                    (first, cacheb), dt = self._timed(
                        ("prefill_merged", width, len(rows)),
                        self._prefill_merged, self.params, toks, cacheb,
                        lengths, now=now, requests=rids)
                else:
                    (first, cacheb), dt = self._timed(
                        ("prefill", width, len(rows)), self._prefill,
                        self.params, self.lora_pool, toks, cacheb, sids,
                        lengths, now=now, requests=rids)
            elif merged:
                fn = functools.partial(self._prefill_suffix_merged,
                                       prefix_len=start)
                (first, cacheb), dt = self._timed(
                    ("prefill_sfx_merged", end, start, len(rows)),
                    fn, self.params, toks, cacheb, self.cache, tables,
                    lengths, now=now, requests=rids)
            else:
                fn = functools.partial(self._prefill_suffix,
                                       prefix_len=start)
                (first, cacheb), dt = self._timed(
                    ("prefill_sfx", end, start, len(rows)),
                    fn, self.params, self.lora_pool, toks, cacheb,
                    self.cache, tables, sids, lengths,
                    now=now, requests=rids)
            # scatter_suffix handles start == 0 too (mini ring index ==
            # position); pad columns past each row's real length land in
            # the trash page
            self.cache = self._scatter_suffix(
                self.cache, cacheb, tables, jnp.asarray(real),
                prefix_len=start, suffix_len=width)
        else:
            slot_idx = jnp.asarray(
                np.fromiter((s.index for s in rows), np.int32,
                            count=len(rows)))
            if start == 0:
                if merged:
                    (first, cacheb), dt = self._timed(
                        ("prefill_merged", width, len(rows)),
                        self._prefill_merged, self.params, toks, cacheb,
                        lengths, now=now, requests=rids)
                else:
                    (first, cacheb), dt = self._timed(
                        ("prefill", width, len(rows)), self._prefill,
                        self.params, self.lora_pool, toks, cacheb, sids,
                        lengths, now=now, requests=rids)
                # fresh slots: the whole-ring copy is correct (positions
                # past the chunk are still at their invalid init state)
                self.cache = self._write_slots(self.cache, cacheb,
                                               slot_idx)
            else:
                if merged:
                    fn = functools.partial(self._prefill_sfx_dense_merged,
                                           prefix_len=start)
                    (first, cacheb), dt = self._timed(
                        ("prefill_sfx_dense_merged", end, start,
                         len(rows)),
                        fn, self.params, toks, cacheb, self.cache,
                        slot_idx, lengths, now=now, requests=rids)
                else:
                    fn = functools.partial(self._prefill_sfx_dense,
                                           prefix_len=start)
                    (first, cacheb), dt = self._timed(
                        ("prefill_sfx_dense", end, start, len(rows)),
                        fn, self.params, self.lora_pool, toks, cacheb,
                        self.cache, slot_idx, sids, lengths,
                        now=now, requests=rids)
                self.cache = self._dense_scatter_suffix(
                    self.cache, cacheb, slot_idx, jnp.asarray(real),
                    prefix_len=start, suffix_len=width)
        self.prefill_steps += 1
        self.prefill_batch_hist[len(group)] = \
            self.prefill_batch_hist.get(len(group), 0) + 1
        first_np = np.asarray(first)  # el: allow[host-sync] -- output stream
        for i, slot in enumerate(group):
            req = slot.request
            if req.prompt_len <= end:
                # terminal chunk: same completion protocol as
                # _prefill_group
                slot.pos = req.prompt_len
                slot.last_token = int(first_np[i])
                req.first_token_time = now + dt
                req.generated = 1
                req.tokens = [slot.last_token]
                slot.state = SlotState.GENERATE
                if tr is not None:
                    tr.transition(now + dt, slot.index, "prefill",
                                  "generate", req)
                self._note_ttft(slot.bucket, req, now + dt)
                if self.prefix_enabled:
                    self.prefix_cache.insert(
                        self._exec_key(slot), req.prompt_tokens,
                        self.kvpool.tables[req.request_id])
            else:
                slot.prefill_pos = end
        return dt

    # ------------------------------------------------------------------
    # shared-prefix radix cache (splice, COW, stats)
    # ------------------------------------------------------------------

    def _exec_key(self, slot: Slot) -> Tuple:
        """Execution identity under which prefix KV is shareable: KV at
        depth > 0 depends on the residual stream, hence on the adapter
        and on merged- vs unmerged-LoRA execution."""
        return (slot.merged, slot.request.selected_adapter)

    def _admission_exec_key(self, req: Request,
                            dlora_mode: str) -> Tuple:
        """The execution identity a request will run under, when it is
        already determined at admission time (every policy except
        AAS-routed edgelora, where the router picks the adapter at
        SELECTING). None → unknown: admission reserves conservatively
        and the prefix match happens at selection instead."""
        policy = self.ecfg.policy
        if policy == "llamacpp":
            return (True, req.true_adapter)
        if policy == "dlora":
            # merged-mode admissions only pass the gate on the folded
            # adapter; mode cannot flip between admission and selection
            # (switching requires a fully drained batch)
            return (dlora_mode == "merged", req.true_adapter)
        if req.adapter_id is not None:
            return (False, req.adapter_id)
        if policy == "edgelora_no_aas":
            return (False, req.true_adapter)
        return None

    def _admit_prefix(self, req: Request, exec_key: Tuple) -> int:
        """Admission-time prefix adoption (execution identity known):
        match, splice shared pages, allocate only the suffix. Returns
        the prefix length served from cache (0 on a miss)."""
        blocks = self.prefix_cache.match(exec_key, req.prompt_tokens)
        matched = len(blocks) * self.kvpool.block_size
        prefix_len = min(matched, req.prompt_len - 1)
        if prefix_len <= 0:
            blocks, matched, prefix_len = [], 0, 0
        pair = self.kvpool.adopt_prefix(req.request_id, blocks,
                                        req.prompt_len,
                                        cow_last=prefix_len < matched)
        st = self.prefix_cache.stats
        if pair is not None:
            src, dst = pair
            self.cache = self._copy_block(self.cache, jnp.int32(src),
                                          jnp.int32(dst))
            st.cow_copies += 1
        if prefix_len:
            st.hit_requests += 1
            st.hit_tokens += matched
            st.saved_prefill_tokens += prefix_len
        return prefix_len

    def _attach_prefix(self, slot: Slot) -> None:
        """At SELECTING→PREFILL (adapter now known): match the longest
        cached block-aligned prefix, splice those physical pages into the
        sequence's block table (releasing the private pages admission
        reserved for that span — capacity accounting stays conservative,
        so deferral/preemption semantics are unchanged), and shrink the
        upcoming prefill to the suffix. A whole-prompt block-aligned
        match keeps one suffix token to re-prefill (first-token logits
        need it): the write lands inside the last shared page, which is
        copied on write."""
        req = slot.request
        slot.prefix_len = 0
        blocks = self.prefix_cache.match(self._exec_key(slot),
                                         req.prompt_tokens)
        if not blocks:
            return
        matched = len(blocks) * self.kvpool.block_size
        prefix_len = min(matched, req.prompt_len - 1)
        if prefix_len <= 0:
            return
        pair = self.kvpool.replace_prefix(req.request_id, blocks,
                                          cow_last=prefix_len < matched)
        st = self.prefix_cache.stats
        if pair is not None:
            src, dst = pair
            self.cache = self._copy_block(self.cache, jnp.int32(src),
                                          jnp.int32(dst))
            st.cow_copies += 1
        st.hit_requests += 1
        st.hit_tokens += matched
        st.saved_prefill_tokens += prefix_len
        slot.prefix_len = prefix_len

    def _padded_prompt(self, req: Request, bucket: int) -> jax.Array:
        toks = np.zeros((bucket,), np.int32)
        n = min(req.prompt_len, bucket)
        toks[:n] = np.asarray(req.prompt_tokens)[:n]  # right-padded
        return jnp.asarray(toks)

    # ------------------------------------------------------------------
    # adapter swap-in (reservation routing, queue-ahead prefetch)
    # ------------------------------------------------------------------

    def _acquire(self, adapter_id: int, now: float) -> Reservation:
        """A demand ``manager.acquire``; traced, an ``adapter.acquire``
        span (a miss's ``adapter.load`` nests in it) and a
        ``loads.demand`` count per miss."""
        tr = self.tracer
        if tr is None:
            return self.manager.acquire(adapter_id, now=now)
        with tr.span("adapter.acquire", adapter=adapter_id):
            res = self.manager.acquire(adapter_id, now=now)
        if res.loaded:
            tr.metrics.counter("loads.demand").inc()
        return res

    def _finish_acquire(self, slot: Slot, res: Any,
                        now: float) -> float:
        """Pin the reserved adapter and route the slot by swap mode:
        async parks it in LOADING until the transfer's ready_time (other
        slots keep prefilling/decoding); sync stalls the clock to
        ready_time — the single explicit charge per load that replaced
        the old ``_pending_load_cost`` side-channel. Returns the
        (possibly advanced) clock."""
        tr = self.tracer
        self.manager.pin(res.adapter_id)
        slot.adapter_slot = res.slot
        if self.ecfg.async_swap:
            if res.ready_time > now:
                slot.ready_time = res.ready_time
                slot.state = SlotState.LOADING
                if tr is not None:
                    tr.transition(now, slot.index, "selecting", "loading",
                                  slot.request, adapter=res.adapter_id)
            else:
                slot.state = SlotState.PREFILL
                if tr is not None:
                    tr.transition(now, slot.index, "selecting", "prefill",
                                  slot.request)
            return now
        if res.ready_time > now:
            self.load_stall_seconds += res.ready_time - now
            # sync mode still spends a real LOADING interval on the
            # timeline (the whole engine stalls through it) — record it
            # as one, so load_stall shows up in the latency breakdown
            if tr is not None:
                tr.transition(now, slot.index, "selecting", "loading",
                              slot.request, adapter=res.adapter_id)
            now = res.ready_time
            slot.state = SlotState.PREFILL
            if tr is not None:
                tr.transition(now, slot.index, "loading", "prefill",
                              slot.request)
            return now
        slot.state = SlotState.PREFILL
        if tr is not None:
            tr.transition(now, slot.index, "selecting", "prefill",
                          slot.request)
        return now

    def _known_adapter(self, req: Request, dlora_mode: str) -> Optional[int]:
        """The pool adapter a waiting request will demand, when already
        determined (None: AAS picks at SELECTING, or the policy runs
        merged and never touches the pool)."""
        if req.adapter_id is not None:
            return req.adapter_id
        policy = self.ecfg.policy
        if policy == "edgelora_no_aas":
            return req.true_adapter
        if policy == "dlora" and dlora_mode != "merged":
            return req.true_adapter
        return None

    def _predicted_adapter(self, req: Request,
                           dlora_mode: str) -> Optional[int]:
        """Known adapter, or a cheap AAS prediction: a bookkeeping-only
        router (oracle) scores a waiting request for free, so we can run
        the cache-aware selection it will make on admission; a learned
        router's forward costs a prompt pass, so only the selection the
        request ran under before a KV preemption is reused. None: not
        predictable, or already resident (nothing to warm)."""
        aid = self._known_adapter(req, dlora_mode)
        if aid is not None:
            return aid
        if self.ecfg.policy != "edgelora":
            return None
        if not getattr(self.router, "costs_forward", False):
            if req.sel_scores is None:  # once per request, not per tick
                req.sel_scores = np.asarray(self.router.scores(req))
            aid, cached = select_adapter(req.sel_scores, self.manager,
                                         self.ecfg.top_k)
            return None if cached else aid
        return req.prefetch_hint

    def _run_prefetch(self, now: float, dlora_mode: str) -> None:
        """Queue-ahead prefetch: start swap-ins for upcoming demand so
        the transfer channel overlaps with compute. Targets are the
        ready heap in admission order — KV-preempted requeue leads, then
        arrived-but-unadmitted work by priority — each with a known
        adapter or a cheap AAS prediction (``_predicted_adapter``).
        Bounded by ``prefetch_depth``; the whole lookahead window is
        passed as the manager's protect set, so a colder prefetch can
        never evict a hotter (sooner-needed) adapter — and pins protect
        the rest. (Pool-deferred SELECTING slots are *not* targets:
        deferral means every block is pinned, and the moment one frees,
        the slot's own demand acquire — which runs before the prefetcher
        every tick — takes it.)"""
        ecfg = self.ecfg
        self._ingest(now)
        targets: List[int] = []
        waiting = [e[3] for e in heapq.nsmallest(
            4 * ecfg.prefetch_depth, self._ready)]
        for r in waiting:
            aid = self._predicted_adapter(r, dlora_mode)
            if aid is not None:
                targets.append(aid)
        seen: set = set()
        todo: List[int] = []
        for aid in targets:
            if aid not in seen:
                seen.add(aid)
                todo.append(aid)
        todo = todo[:ecfg.prefetch_depth]
        protect = set(todo)
        # saturation guard: speculation must never book the serialized
        # channel more than a lookahead window ahead of the clock — a
        # demand load issued next tick would otherwise queue behind a
        # pile of speculative transfers
        horizon = now + ecfg.prefetch_depth * self.manager.load_seconds
        for aid in todo:
            if self.manager.channel_free_at > horizon:
                break
            res = self.manager.prefetch(aid, now=now, protect=protect)
            if res is not None and self.tracer is not None:
                self.tracer.metrics.counter("loads.prefetch").inc()

    # ------------------------------------------------------------------
    # paged-KV scheduling (block tables, preemption)
    # ------------------------------------------------------------------

    def _decode_tables(self, gen: List[Slot]) -> Tuple[Any, ...]:
        """[n_slots, max_blocks] physical page table + [n_slots] written
        lengths / prompt lengths / prefill buckets for a decode step.
        Rows of slots not decoding this tick are -1 / 0 — their gathers
        read the trash page and their writes land there, so they can't
        corrupt live sequences."""
        mb = self._kv_meta.max_blocks
        tables = np.full((self.ecfg.n_slots, mb), -1, np.int32)
        lengths = np.zeros((self.ecfg.n_slots,), np.int32)
        plens = np.zeros((self.ecfg.n_slots,), np.int32)
        bwlens = np.zeros((self.ecfg.n_slots,), np.int32)
        for slot in gen:
            tables[slot.index] = self.kvpool.block_table(
                slot.request.request_id, mb)
            lengths[slot.index] = slot.pos  # tokens written pre-step
            plens[slot.index] = slot.request.prompt_len
            bwlens[slot.index] = slot.bucket  # padded prefill write span
        return (jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(plens), jnp.asarray(bwlens))

    def _secure_decode_blocks(self, gen: List[Slot],
                              now: float) -> List[Slot]:
        """Allocate one page-extension per decoding sequence, oldest
        admission first. When the arena is dry, preempt the *youngest*
        active slot (LIFO, vLLM-style restart-recompute): its pages are
        freed, its request re-enters the queue ahead of new arrivals,
        and greedy decode later reproduces the identical stream. The
        init-time capacity check (arena ≥ one max_ctx sequence)
        guarantees the oldest admission always makes progress."""
        if self.tracer is not None:
            self.tracer.clock(now)  # arena events land at this step
        secured: List[Slot] = []
        for slot in sorted(gen, key=lambda s: s.admit_seq):
            if slot.state != SlotState.GENERATE:
                continue  # preempted as an earlier slot's victim
            rid = slot.request.request_id
            alive = True
            while not self.kvpool.can_append(rid, 1):
                victims = [s for s in self.slots.slots
                           if s.state != SlotState.IDLE and s is not slot
                           and s not in secured]
                if victims:
                    self._preempt(max(victims,
                                      key=lambda s: s.admit_seq), now)
                else:
                    self._preempt(slot, now)
                    alive = False
                    break
            if alive:
                self.kvpool.append_tokens(rid, 1)
                secured.append(slot)
        return [s for s in gen if s in secured]

    def _preempt(self, slot: Slot, now: float) -> None:
        """Evict an in-flight request to free its KV pages: restart
        semantics — all partial output is discarded and the request
        re-admits (and re-prefills) once capacity returns."""
        req = slot.request
        tr = self.tracer
        if tr is not None:
            # close the slot's open span before release mutates state;
            # preempted=True folds its in-slot time into the request's
            # 'preempted' (discarded-work) breakdown segment
            tr.transition(now, slot.index, slot.state.value, "idle",
                          req, preempted=True)
            tr.sched(now, "preempt", request=req, slot=slot.index)
            tr.sched(now, "requeue", request=req)
        self.kvpool.release(req.request_id)
        if self.ecfg.policy != "llamacpp" and not slot.merged \
                and slot.state in (SlotState.LOADING, SlotState.PREFILL,
                                   SlotState.GENERATE):
            self.manager.unpin(req.selected_adapter)
        if req.selected_adapter is not None:
            req.prefetch_hint = req.selected_adapter
        req.selected_adapter = None
        req.first_token_time = None
        req.generated = 0
        req.tokens = []
        # restart semantics reset the admission clock too: the TTFT
        # estimator must not learn from a partially-served admission
        req.admit_time = None
        slot.release()
        self._push_ready(req, requeued=True)
        self.kv_preemptions += 1
