"""SGMV Pallas kernels — the paper's Batch LoRA Inference hot loop on TPU.

The paper groups requests by adapter (u-batches) and runs one GEMM per
unique adapter (Punica-style SGMV on GPU). The TPU-native formulation:

* tokens are pre-sorted into **adapter-homogeneous blocks** of ``blk_t``
  (grouping/padding lives in ``ops.py`` — it is the paper's gather step);
* a *scalar-prefetched* ``block_slots`` array tells each grid step which
  adapter's tile to DMA: the A/B BlockSpec ``index_map`` reads
  ``block_slots[i]``, so the weight tile streams HBM→VMEM exactly once per
  block and the MXU always sees dense [blk_t, d]×[d, r] work;
* the d dimension is tiled (``blk_d``) with an f32 VMEM accumulator so the
  working set fits VMEM for d_ff-sized projections (up to 49k here).

MXU alignment: blk_d is a multiple of 128 (or the whole width, see
``_fit``) and blk_t a multiple of 8; the LoRA rank r (16/32)
rides the sublane dimension (multiple of 8), so tiles are well-formed —
the rank<128 lane waste in the expand GEMM is real and is reported in the
roofline "useful FLOPs" ratio rather than hidden.

Kernels are validated in interpret mode on CPU against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLK_T = 128
DEFAULT_BLK_D = 512
LANE = 128


def _fit(n: int, requested: int) -> int:
    """Lane-dim tile for a width-n axis: the largest multiple of 128 that
    divides n and is ≤ requested, else n itself. The TPU lowering accepts
    a block's last dim only if it is a multiple of 128 or the whole axis
    (qwen2-0.5b's 896 and 4864 give 128 and 256, not 448 and 304)."""
    for b in range(min(requested, n) // LANE * LANE, 0, -LANE):
        if n % b == 0:
            return b
    return n


def _shrink_kernel(slots_ref, x_ref, a_ref, o_ref, acc_ref, *, nd: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], a_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nd - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def sgmv_shrink(x: jax.Array, a_stack: jax.Array, block_slots: jax.Array,
                *, blk_t: int = DEFAULT_BLK_T, blk_d: int = DEFAULT_BLK_D,
                interpret: bool = False) -> jax.Array:
    """x: [T, d_in] with T = nb·blk_t adapter-homogeneous blocks;
    a_stack: [R, r, d_in]; block_slots: [nb] int32. Returns [T, r] f32."""
    t, d_in = x.shape
    r = a_stack.shape[1]
    assert t % blk_t == 0, (t, blk_t)
    blk_d = _fit(d_in, blk_d)
    nb, nd = t // blk_t, d_in // blk_d

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nd),
        in_specs=[
            pl.BlockSpec((blk_t, blk_d), lambda i, j, slots: (i, j)),
            pl.BlockSpec((1, r, blk_d), lambda i, j, slots: (slots[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((blk_t, r), lambda i, j, slots: (i, 0)),
        scratch_shapes=[pltpu.VMEM((blk_t, r), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_shrink_kernel, nd=nd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, r), jnp.float32),
        interpret=interpret,
    )(block_slots, x, a_stack)


def _expand_kernel(slots_ref, s_ref, b_ref, y_ref):
    y_ref[...] = jax.lax.dot_general(
        s_ref[...], b_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


def sgmv_expand(s: jax.Array, b_stack: jax.Array, block_slots: jax.Array,
                *, blk_t: int = DEFAULT_BLK_T, blk_d: int = DEFAULT_BLK_D,
                out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """s: [T, r]; b_stack: [R, d_out, r]; block_slots: [nb].
    Returns [T, d_out]."""
    t, r = s.shape
    d_out = b_stack.shape[1]
    assert t % blk_t == 0, (t, blk_t)
    blk_d = _fit(d_out, blk_d)
    nb, nd = t // blk_t, d_out // blk_d

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nd),
        in_specs=[
            pl.BlockSpec((blk_t, r), lambda i, j, slots: (i, 0)),
            pl.BlockSpec((1, blk_d, r), lambda i, j, slots: (slots[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((blk_t, blk_d), lambda i, j, slots: (i, j)),
    )
    return pl.pallas_call(
        _expand_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d_out), out_dtype),
        interpret=interpret,
    )(block_slots, s, b_stack)
