"""Smoke run of the serving engine on one TPU at qwen2-0.5b's published widths.

    python chip_smoke.py

Runs in one process and holds one chip. It exits non-zero, printing no
result, unless JAX's first device is a TPU. Then:

1. SGMV parity: ``kernels/ops.sgmv`` compiled for the chip (the compiled
   program must hold the Pallas kernel, ``tpu_custom_call``) against
   ``kernels/ref.sgmv_ref`` with random nonzero A and B and mixed slot
   ids, at qwen2-0.5b's four LoRA projection widths and at a decode and a
   prefill token count, with bf16 pools and with the engine's f32 pool.
   The serve runs cannot catch a wrong kernel: every engine adapter
   starts with B = 0, so its delta is exactly zero.
2. Two serves through the CLI's own build path (``repro.launch.serve``:
   ``--arch qwen2-0.5b --full --n-adapters 1024``), one with dense KV and
   one with paged KV. Every request must complete with its requested
   output length, batched LoRA must run the compiled SGMV kernels, and the
   paged run must fetch pages with the compiled gather kernel. Each trace
   is served twice: cold (compiles every step shape) and warm.

Any failed check raises. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

ARCH = "qwen2-0.5b"
# a burst of 8 requests within 80 ms: decode batches fill all 4 slots
# with mixed adapters, and some prefills run as groups
SERVE_ARGS = ["--arch", ARCH, "--full", "--n-adapters", "1024",
              "--n-slots", "4", "--max-ctx", "128", "--rate", "100",
              "--duration", "0.08", "--seed", "0"]
TOKEN_COUNTS = (8, 256)        # a decode batch and a prefill group
# Both sides see the same bf16 inputs and the reference runs at f32
# "highest" precision. The kernel's result carries two bf16 roundings:
# the f32 shrink output entering the expand matmul at default MXU
# precision and the bf16 output itself, each at most 2^-9 of the
# magnitude; its f32 accumulations add far less. 2^-6 of the largest
# reference value leaves 4x headroom over their 2^-8 sum.
SGMV_TOL = 2.0 ** -6


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def sgmv_parity(cfg, seed: int = 0) -> dict:
    """Max |kernel − ref| / max |ref| per (d_in, d_out, T)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    n_slots, rank, scale = (cfg.lora.max_resident, cfg.lora.rank,
                            cfg.lora.scale)
    widths = [(cfg.d_model, cfg.d_model), (cfg.d_model, cfg.kv_size),
              (cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)]
    rng = np.random.default_rng(seed)
    errors = {}
    # bf16 pools, and the engine's f32 pool, whose tiles the kernels round
    # to bf16 in VMEM: the reference takes the pool rounded the same way
    for pool_dtype, tag in ((jnp.bfloat16, ""), (jnp.float32, "/f32pool")):
        for d_in, d_out in widths:
            a = jnp.asarray(rng.normal(size=(n_slots, rank, d_in))
                            / np.sqrt(d_in), pool_dtype)
            b = jnp.asarray(rng.normal(size=(n_slots, d_out, rank))
                            / np.sqrt(rank), pool_dtype)
            a16, b16 = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
            for t in TOKEN_COUNTS:
                what = f"sgmv {d_in}->{d_out} T={t}{tag}"
                x = jnp.asarray(rng.normal(size=(t, d_in)), jnp.bfloat16)
                slots = jnp.asarray(rng.integers(0, n_slots, t), jnp.int32)
                compiled = ops.sgmv.lower(
                    x, a, b, slots, scale, n_slots=n_slots, blk_t=None,
                    interpret=False).compile()
                check("tpu_custom_call" in compiled.as_text(),
                      f"{what}: no Pallas kernel compiled")
                y = np.asarray(compiled(x, a, b, slots, scale), np.float32)
                with jax.default_matmul_precision("highest"):
                    y_ref = np.asarray(ref.sgmv_ref(x, a16, b16, slots,
                                                    scale))
                check(y.shape == y_ref.shape and bool(np.isfinite(y).all()),
                      f"{what}: shape {y.shape} or non-finite values")
                err = float(np.abs(y - y_ref).max() / np.abs(y_ref).max())
                errors[f"{d_in}->{d_out}/T={t}{tag}"] = err
                check(err <= SGMV_TOL,
                      f"{what}: relative error {err} > {SGMV_TOL}")
    return errors


def serve_run(argv: list) -> dict:
    """Build trace and engine as ``launch/serve.py`` does, serve a copy of
    the trace cold, then the trace warm; check every request."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(argv)
    trace, engine = serve.build(args)
    warm_trace = copy.deepcopy(trace)
    t0 = time.perf_counter()
    cold = engine.serve(trace)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = engine.serve(warm_trace)
    warm_s = time.perf_counter() - t0
    for summary in (cold, warm):
        check(summary.n_completed == summary.n_requests == len(trace),
              f"completed {summary.n_completed}/{summary.n_requests} of "
              f"{len(trace)}")
    vocab = engine.cfg.vocab_size
    for r in trace + warm_trace:
        check(r.finish_time is not None and r.generated == r.output_len
              == len(r.tokens), f"request {r.request_id}: {r.generated} "
              f"of {r.output_len} tokens")
        check(all(0 <= t < vocab for t in r.tokens),
              f"request {r.request_id}: token out of range")
    return {"engine": engine, "requests": warm_trace,
            "completed": warm.n_completed, "requested": warm.n_requests,
            "cold_s": cold_s, "warm_s": warm_s,
            "shapes": len(engine._durations)}


def check_chip_path(engine, paged: bool) -> None:
    """The engine runs the compiled Pallas kernels, not a CPU stand-in."""
    check(engine.lora_backend == "sgmv",
          f"lora_backend={engine.lora_backend}, expected sgmv")
    check(engine._sgmv_interpret is False, "SGMV kernels in interpret mode")
    if paged:
        check(engine.page_gather is not None
              and engine.page_gather.keywords["interpret"] is False,
              "paged run without the compiled page-gather kernel")


def main() -> int:
    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{device.platform}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"cache_dir={enable_compile_cache()}")
    print(f"device_kind={device.device_kind} count={len(devices)}")

    t0 = time.perf_counter()
    errors = sgmv_parity(get_config(ARCH))
    print(f"sgmv_parity seconds={time.perf_counter() - t0:.3f} "
          f"tol={SGMV_TOL} max_rel_err=" + json.dumps(errors))

    streams = {}
    for kv in ("dense", "paged"):
        run = serve_run(SERVE_ARGS + ["--kv-backend", kv])
        check_chip_path(run["engine"], paged=kv == "paged")
        streams[kv] = [r.tokens for r in run["requests"]]
        print(f"serve kv={kv} completed={run['completed']}/"
              f"{run['requested']} step_shapes={run['shapes']} "
              f"cold_s={run['cold_s']:.3f} warm_s={run['warm_s']:.3f} "
              f"compile_s~{run['cold_s'] - run['warm_s']:.3f}")
    same = sum(d == p for d, p in zip(streams["dense"], streams["paged"]))
    print(f"dense/paged identical token streams: {same}/{len(streams['dense'])}")

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
