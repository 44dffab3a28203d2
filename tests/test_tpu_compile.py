"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e, at qwen2-0.5b's published widths.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(tiles the lowering rejects, too much fast memory). Interpret-mode tests
cannot catch either. Each compile must keep the Pallas kernel
(``tpu_custom_call``) in the program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import build_model
from repro.serving import kvpool as kvlib
from repro.serving.engine import EngineConfig

CFG = get_config("qwen2-0.5b")
# (d_in, d_out) of the LoRA projections: q/o, k/v, up/gate, down
WIDTHS = [(CFG.d_model, CFG.d_model), (CFG.d_model, CFG.kv_size),
          (CFG.d_model, CFG.d_ff), (CFG.d_ff, CFG.d_model)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# bf16 pools, and the engine's pair (bf16 x, f32 pool read in place) at
# its decode batch (16 slots) and its largest prefill group (16 x 256)
@pytest.mark.parametrize("t,pool_dtype", [
    (8, jnp.bfloat16), (256, jnp.bfloat16),
    (16, jnp.float32), (4096, jnp.float32)],
    ids=["decode", "prefill", "decode-f32pool", "prefill-f32pool"])
@pytest.mark.parametrize("d_in,d_out", WIDTHS,
                         ids=[f"{i}to{o}" for i, o in WIDTHS])
def test_sgmv_compiles_for_v5e(one_chip, d_in, d_out, t, pool_dtype):
    n_slots, rank = CFG.lora.max_resident, CFG.lora.rank
    compiled = ops.sgmv.lower(
        _sds((t, d_in), jnp.bfloat16, one_chip),
        _sds((n_slots, rank, d_in), pool_dtype, one_chip),
        _sds((n_slots, d_out, rank), pool_dtype, one_chip),
        _sds((t,), jnp.int32, one_chip), CFG.lora.scale,
        n_slots=n_slots, blk_t=None, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_gather_compiles_for_v5e(one_chip):
    """The page fetch over the arena the engine builds for qwen2-0.5b at
    its default EngineConfig (one arena leaf: K or V of every layer)."""
    ecfg = EngineConfig()
    model = build_model(CFG)
    template = jax.eval_shape(
        lambda: model.init_cache(ecfg.n_slots, ecfg.max_ctx))
    bs = ecfg.kv_block_size
    per_seq = -(-(ecfg.max_ctx + 1) // bs)
    meta = kvlib.paged_meta(template, ecfg.n_slots * per_seq, bs,
                            ecfg.max_ctx)
    arena = jax.eval_shape(lambda: kvlib.build_arena(
        model.init_cache(ecfg.n_slots, ecfg.max_ctx), meta))
    leaves = jax.tree.leaves(arena)
    leaf = max(leaves, key=lambda a: a.size)
    assert leaf.shape[1:3] == (meta.n_blocks + 1, bs)
    compiled = ops.paged_gather.lower(
        _sds(leaf.shape, leaf.dtype, one_chip),
        _sds((ecfg.n_slots, meta.max_blocks), jnp.int32, one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
