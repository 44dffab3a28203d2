"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Absolute µs are CPU-container
scale; each row's *derived* field carries the paper-relative quantity
(throughput ratios, SLO, hit rates, accuracies). The roofline/§Perf
numbers live in EXPERIMENTS.md (driven by repro.launch.dryrun, not here).
"""
import sys
import time


def main() -> None:
    t0 = time.time()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (adapter_swap, batched_lora_micro, paged_kv,
                            prefill_batching, prefix_cache, router_bench,
                            serving_tables, slo_scheduling)
    print("name,us_per_call,derived")
    # paper tables on the serving engine
    serving_tables.table4_throughput_vs_adapters()
    serving_tables.table5_6_slo_first_token()
    serving_tables.table7_8_adapter_locality()
    serving_tables.table7_lfu_variant()
    serving_tables.ablation_pool_size()
    serving_tables.ablation_rank_memory()
    serving_tables.table9_10_workload_skewness()
    serving_tables.table11_power_proxy()
    serving_tables.table14_slots()
    serving_tables.table6_learned_router_overhead()
    # batched prompt-pass compute (sequential vs batched prefill/router;
    # also writes BENCH_prefill_batching.json for the perf trajectory)
    prefill_batching.main()
    # paged vs dense KV capacity at fixed arena bytes (+ stream parity,
    # page-gather kernel check; writes BENCH_paged_kv.json)
    paged_kv.main()
    # shared-prefix radix cache: warm-vs-cold prefill + arena footprint
    # vs tenancy (writes BENCH_prefix_cache.json)
    prefix_cache.main()
    # async adapter swap-in vs the synchronous baseline on a cold-heavy
    # workload (+ stream parity; writes BENCH_adapter_swap.json)
    adapter_swap.main()
    # chunked prefill pareto (short-TTFT tail vs throughput) + SLO
    # admission control under overload (writes BENCH_slo_scheduling.json)
    slo_scheduling.main()
    # batched LoRA micro + kernels
    batched_lora_micro.fig6_batched_vs_sequential()
    batched_lora_micro.backend_einsum_vs_sgmv()
    batched_lora_micro.sgmv_kernel_check()
    batched_lora_micro.flash_decode_check()
    # router quality
    router_bench.table12_router_accuracy()
    print(f"# total_bench_seconds={time.time() - t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
