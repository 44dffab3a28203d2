"""Compile a configuration's serving steps for a described TPU v5e, without
a chip, and report what the compiler says.

    JAX_PLATFORMS=cpu python bench/aot_rehearsal.py --config starcoder2-7b-l16 \
        [--group 16] [--bucket 256] [--n-slots 16] [--max-ctx 384]

Builds the engine's own jitted prefill and decode steps (compiled SGMV,
dense KV) from shapes alone, and the SGMV data path (``kernels/ops.sgmv``)
at each LoRA projection's widths (the configuration's model module's
``lora_modules``) for a decode-sized and a prefill-sized token count. For
each program it prints one JSON line: the bytes of ``memory_analysis()``
and whether the compiled text holds the Pallas kernel
(``tpu_custom_call``). Nothing runs and nothing is timed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True)
    p.add_argument("--group", type=int, default=16)
    p.add_argument("--bucket", type=int, default=256)
    p.add_argument("--n-slots", type=int, default=16)
    p.add_argument("--max-ctx", type=int, default=384)
    args = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness.engine import model_config
    from bench.models import module_of
    from repro.kernels import ops
    from repro.models import build_model
    from repro.serving.engine import EdgeLoRAEngine, EngineConfig

    with open(os.path.join(ROOT, "bench", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    mcfg = model_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def report(name, lowered):
        compiled = lowered.compile()
        print(json.dumps({"program": name, "config": args.config,
                          "tpu_custom_call": "tpu_custom_call"
                          in compiled.as_text(), **_mem(compiled)}),
              flush=True)

    # the engine's own steps, built without weights or pool (the dense
    # KV cache it allocates on the host is a few hundred MB at most)
    engine = EdgeLoRAEngine.__new__(EdgeLoRAEngine)
    engine.cfg = mcfg
    engine.ecfg = EngineConfig(n_slots=args.n_slots, max_ctx=args.max_ctx,
                               kv_backend="dense", lora_backend="sgmv")
    engine.model = build_model(mcfg)
    engine.lora_backend, engine._sgmv_interpret = "sgmv", False
    engine.paged = False
    engine._build_steps()
    model, key = build_model(mcfg), jax.random.PRNGKey(0)
    params = on_chip(jax.eval_shape(model.init, key))
    pool = on_chip(jax.eval_shape(
        lambda k: model.init_lora(k, n_slots=mcfg.lora.max_resident), key))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    g = args.group
    cache1 = on_chip(jax.eval_shape(lambda: model.init_cache(g, args.max_ctx)))
    report(f"prefill group={g} bucket={args.bucket}", engine._prefill.lower(
        params, pool, i32(g, args.bucket), cache1, i32(g), i32(g)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(args.n_slots, args.max_ctx)))
    n = args.n_slots
    report(f"decode n_slots={n}", engine._decode.lower(
        params, pool, i32(n), cache, i32(n), i32(n)))
    r, slots = mcfg.lora.rank, mcfg.lora.max_resident
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)  # noqa: E731
    for name, (d_in, d_out) in module_of(cfg).lora_modules(cfg).items():
        for t in (n, g * args.bucket):
            report(f"sgmv {name} {d_in}->{d_out} T={t}", ops.sgmv.lower(
                bf16(t, d_in), bf16(slots, r, d_in), bf16(slots, d_out, r),
                i32(t), mcfg.lora.scale, n_slots=slots, blk_t=None,
                interpret=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
