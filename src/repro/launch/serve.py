"""Serving launcher: run the EdgeLoRA engine on a synthetic workload.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --reduced --n-adapters 64 --rate 2.0 --duration 30 \
        --policy edgelora

``--reduced`` (the default: a tiny same-family variant, 2 layers at
d_model 256) is what CPU runs and tests use. ``--full`` runs the
architecture at its published widths, which is what the chip serves
(``chip_smoke.py`` drives it that way on one TPU). The launcher wires
workload → engine → metrics and prints a paper-style summary row.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import List, Tuple

from repro.configs import get_config, reduced_config
from repro.core.slots import Request
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EdgeLoRAEngine, EngineConfig, OutOfMemoryError
from repro.serving.trace import EngineTracer
from repro.serving.workload import WorkloadConfig, generate_trace


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--policy", default="edgelora",
                    choices=["edgelora", "edgelora_no_aas", "llamacpp", "dlora"])
    ap.add_argument("--n-adapters", type=int, default=20)
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=3)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--cv", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--max-ctx", type=int, default=256)
    ap.add_argument("--memory-budget", type=float, default=2e9)
    ap.add_argument("--cache-policy", default="lru", choices=["lru", "lfu"])
    ap.add_argument("--lora-backend", default=None,
                    choices=["auto", "einsum", "sgmv"],
                    help="batched-LoRA compute path (default: the model "
                         "config's 'auto' — sgmv on TPU, einsum elsewhere)")
    ap.add_argument("--kv-backend", default=None,
                    choices=["dense", "paged"],
                    help="KV cache layout (default: the model config's, "
                         "'dense'). 'dense' reserves a max-ctx ring per "
                         "slot; 'paged' shares one block arena across "
                         "slots via per-sequence block tables — same "
                         "token streams, strictly better capacity under "
                         "skewed context lengths")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV page (paged backend)")
    ap.add_argument("--kv-arena-blocks", type=int, default=None,
                    help="KV arena pages (paged backend; default sizes "
                         "the arena to dense-equivalent capacity — set "
                         "lower to overcommit)")
    ap.add_argument("--prefix-cache", action="store_true", default=False,
                    help="shared-prefix radix KV cache: requests whose "
                         "block-aligned prompt prefix was already "
                         "prefilled under the same (adapter, merged) "
                         "identity splice the cached pages and prefill "
                         "only the suffix (implies --kv-backend paged "
                         "when unset; streams are bit-identical, only "
                         "prefill compute and arena footprint change)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable the shared-prefix cache (default)")
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    help="per-adapter shared system prompt length in the "
                         "synthetic workload (the repeated per-tenant "
                         "prefix --prefix-cache exploits)")
    ap.add_argument("--shared-prefix-frac", type=float, default=1.0,
                    help="fraction of each adapter's requests that open "
                         "with its system prompt")
    ap.add_argument("--async-swap", dest="async_swap",
                    action="store_true", default=True,
                    help="asynchronous adapter swap-in (default): a pool "
                         "miss books a transfer on the serialized "
                         "host→HBM channel and the slot waits in LOADING "
                         "while other slots keep running; the clock only "
                         "stalls when every runnable slot is load-blocked")
    ap.add_argument("--no-async-swap", dest="async_swap",
                    action="store_false",
                    help="synchronous swap-in: every pool miss charges "
                         "adapter_bytes/disk_bandwidth straight to the "
                         "global clock (the pre-async baseline; token "
                         "streams are identical either way except that "
                         "cache-aware AAS with --top-k > 1 reads pool "
                         "residency at selection time by design, so "
                         "timing shifts can steer which adapter it picks)")
    ap.add_argument("--prefetch-depth", type=int, default=4,
                    help="queue-ahead prefetch: warm the pool for up to "
                         "this many waiting/requeued requests with a "
                         "known (or score-predicted) adapter; 0 disables "
                         "(async swap only)")
    ap.add_argument("--disk-bandwidth", type=float, default=1.0e9,
                    help="adapter swap-in bytes/s (host→HBM transfer "
                         "channel; lower values make cold adapters "
                         "costlier and the async/prefetch win larger)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: bound every prefill call to "
                         "at most this many prompt tokens, interleaving "
                         "remaining chunks with decode steps (bounds the "
                         "per-iteration step time a long prompt can "
                         "impose on decoding tenants; default off — off "
                         "is bit-identical to the pre-chunking engine)")
    ap.add_argument("--no-admission-control", dest="admission_control",
                    action="store_false", default=True,
                    help="disable SLO admission control (default on; it "
                         "only ever affects requests carrying a TTFT "
                         "deadline — see --interactive-frac): hopeless "
                         "requests are no longer shed/timed out, they "
                         "just miss their deadlines")
    ap.add_argument("--interactive-frac", type=float, default=0.0,
                    help="fraction of workload requests tagged "
                         "interactive: priority 0 plus the TTFT/TPOT "
                         "deadlines below; the rest become priority-1 "
                         "batch traffic (0 = the pre-SLO workload)")
    ap.add_argument("--interactive-ttft-slo", type=float, default=2.0,
                    help="arrival→first-token deadline (s) for "
                         "interactive requests")
    ap.add_argument("--interactive-tpot-slo", type=float, default=None,
                    help="per-decode-token deadline (s) for interactive "
                         "requests (reporting only)")
    ap.add_argument("--long-prompt-frac", type=float, default=0.0,
                    help="fraction of requests whose unique tail is "
                         "extended by a --long-input-range draw (the "
                         "heavy-tailed prompt mix chunked prefill helps)")
    ap.add_argument("--long-input-range", type=int, nargs=2,
                    default=(128, 192), metavar=("LO", "HI"),
                    help="extra tail tokens for long-prompt requests")
    ap.add_argument("--no-prefill-batching", dest="prefill_batching",
                    action="store_false",
                    help="one B=1 prefill per slot (pre-batching baseline)")
    ap.add_argument("--no-router-batching", dest="router_batching",
                    action="store_false",
                    help="one router forward per SELECTING slot")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record an engine trace and write Perfetto/"
                         "Chrome-trace JSON to PATH (open in "
                         "https://ui.perfetto.dev or chrome://tracing; "
                         "the file also carries the raw event log, "
                         "metrics time series, per-request latency "
                         "breakdowns, and the jit-recompile watchdog "
                         "report under the 'edgelora' key — see "
                         "docs/observability.md). Token streams and the "
                         "summary are bit-identical with or without it")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    return ap


def build(args: argparse.Namespace) -> Tuple[List[Request], EdgeLoRAEngine]:
    """Model config, synthetic trace and engine for parsed CLI ``args``
    (the engine raises ``OutOfMemoryError`` when the llamacpp policy's
    preload exceeds the budget)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    cfg = dataclasses.replace(
        cfg, lora=dataclasses.replace(cfg.lora, n_adapters=args.n_adapters))

    if args.prefix_cache and args.kv_backend is None:
        args.kv_backend = "paged"  # the shared pages live in the arena

    wl = WorkloadConfig(
        n_adapters=args.n_adapters, alpha=args.alpha,
        request_rate=args.rate, cv=args.cv, duration=args.duration,
        input_range=(8, 64), output_range=(8, 32),
        system_prompt_len=args.system_prompt_len,
        shared_prefix_frac=args.shared_prefix_frac,
        interactive_frac=args.interactive_frac,
        interactive_ttft_slo=args.interactive_ttft_slo,
        interactive_tpot_slo=args.interactive_tpot_slo,
        long_prompt_frac=args.long_prompt_frac,
        long_input_range=tuple(args.long_input_range),
        vocab_size=cfg.vocab_size, seed=args.seed)
    trace = generate_trace(wl)

    # buckets must cover system prompt + longest tail (the engine extends
    # to max_ctx anyway; keep small buckets for the short-prompt traffic)
    ecfg = EngineConfig(
        n_slots=args.n_slots, top_k=args.top_k, policy=args.policy,
        max_ctx=args.max_ctx, prompt_buckets=(32, 64),
        memory_budget=args.memory_budget, cache_policy=args.cache_policy,
        lora_backend=args.lora_backend,
        kv_backend=args.kv_backend, kv_block_size=args.kv_block_size,
        kv_arena_blocks=args.kv_arena_blocks,
        prefix_cache=args.prefix_cache,
        prefill_chunk=args.prefill_chunk,
        admission_control=args.admission_control,
        async_swap=args.async_swap, prefetch_depth=args.prefetch_depth,
        disk_bandwidth=args.disk_bandwidth,
        prefill_batching=args.prefill_batching,
        router_batching=args.router_batching, seed=args.seed)
    tracer = EngineTracer() if args.trace else None
    return trace, EdgeLoRAEngine(cfg, ecfg, tracer=tracer)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    try:
        trace, engine = build(args)
    except OutOfMemoryError as e:
        print(f"OOM: {e}")
        return 2
    summary = engine.serve(trace)
    tracer = engine.tracer
    if tracer is not None:
        tracer.export(args.trace)
        print(f"# trace written to {args.trace} "
              f"({len(tracer.events)} events; open in ui.perfetto.dev "
              f"or inspect with tools/trace_report.py)", file=sys.stderr)
    print(f"# lora_backend={engine.lora_backend} "
          f"kv_backend={engine.kv_backend}", file=sys.stderr)
    if args.json:
        print(json.dumps(summary.__dict__, default=float, indent=2))
    else:
        print(f"policy={args.policy} n={args.n_adapters} "
              f"completed={summary.n_completed}/{summary.n_requests} "
              f"throughput={summary.throughput:.3f} req/s "
              f"avg_latency={summary.avg_latency:.3f}s "
              f"first_token={summary.avg_first_token:.3f}s "
              f"slo={summary.slo_attainment:.1%} "
              f"hit_rate={summary.cache_hit_rate:.1%} "
              f"{summary.batching_row()} {summary.kv_row()} "
              f"{summary.prefix_row()} {summary.swap_row()} "
              f"{summary.slo_row()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
