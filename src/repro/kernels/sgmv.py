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
  working set fits VMEM for d_ff-sized projections (up to 49k here). The
  tile is the whole width where the kernel's tiles fit ``VMEM_BUDGET``
  (``lane_tile``), so a decode-sized call walks one lane step per token
  block;
* the adapter stacks are read in their stored dtype (the serving pool is
  f32) and each tile is rounded to the activation dtype in VMEM, so only
  the tiles of the slots a block names are read and converted.

MXU alignment: blk_d is a multiple of 128 (or the whole width, see
``_fit``) and blk_t a multiple of 8; the LoRA rank r (16/32) rides the
sublane dimension of both adapter tiles (multiple of 8), so tiles are
well-formed — the rank<128 lane waste of the shrink output is real and
is reported in the roofline "useful FLOPs" ratio rather than hidden.

Kernels are validated in interpret mode on CPU against ``ref.py``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLK_T = 128
DEFAULT_BLK_D = 512
LANE = 128
# VMEM that a kernel's tiles may take when its lane tile is chosen from the
# shapes: half of the v5e's 16 MiB scoped-VMEM default, because Mosaic
# allocates more inside the kernel body (the adapter tile rounded to the
# activation dtype, the dot's f32 result) and the count below sees none of
# it. The AOT compiles in tests/test_tpu_compile.py hold the chip's
# compiler to this choice.
VMEM_BUDGET = 8 * 2**20


def _fit(n: int, requested: int) -> int:
    """Lane-dim tile for a width-n axis: the largest multiple of 128 that
    divides n and is ≤ requested, else n itself. The TPU lowering accepts
    a block's last dim only if it is a multiple of 128 or the whole axis
    (qwen2-0.5b's 896 and 4864 give 128 and 256, not 448 and 304)."""
    for b in range(min(requested, n) // LANE * LANE, 0, -LANE):
        if n % b == 0:
            return b
    return n


def _vmem_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes of a [rows, cols] tile as VMEM lays it out: cols padded to
    128 lanes, rows to the dtype's sublane tile (8 rows of 32-bit words,
    so 16 for bf16)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return -(-rows // sub) * sub * -(-cols // LANE) * LANE * item


def shrink_vmem(blk_t: int, blk_d: int, r: int, x_dtype, a_dtype) -> int:
    """VMEM of ``sgmv_shrink``: its x, A and output tiles, each
    double-buffered by the pipeline, and the f32 accumulator."""
    out = _vmem_bytes(blk_t, r, jnp.float32)
    return 2 * (_vmem_bytes(blk_t, blk_d, x_dtype)
                + _vmem_bytes(r, blk_d, a_dtype) + out) + out


def expand_vmem(blk_t: int, blk_d: int, r: int, b_dtype, out_dtype) -> int:
    """VMEM of ``sgmv_expand``: its s, Bᵀ and output tiles, each
    double-buffered by the pipeline."""
    return 2 * (_vmem_bytes(blk_t, r, jnp.float32)
                + _vmem_bytes(r, blk_d, b_dtype)
                + _vmem_bytes(blk_t, blk_d, out_dtype))


def lane_tile(width: int, vmem: Callable[[int], int],
              blk_d: Optional[int] = None) -> int:
    """A kernel's tile over its lane axis of ``width``.

    An explicit ``blk_d`` keeps the fixed rule, ``_fit(width, blk_d)``.
    ``None`` takes the whole width if the kernel's VMEM at that tile,
    ``vmem(tile)``, fits ``VMEM_BUDGET``, else the widest multiple of 128
    that divides the width and fits; never narrower than
    ``_fit(width, DEFAULT_BLK_D)``, which is taken when nothing wider fits.
    """
    if blk_d is not None:
        return _fit(width, blk_d)
    floor = _fit(width, DEFAULT_BLK_D)
    wider = [width] + [b for b in range(width // LANE * LANE, floor, -LANE)
                       if width % b == 0]
    return next((b for b in wider if vmem(b) <= VMEM_BUDGET), floor)


def _shrink_kernel(slots_ref, x_ref, a_ref, o_ref, acc_ref, *, nd: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], a_ref[0].astype(x_ref.dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nd - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def sgmv_shrink(x: jax.Array, a_stack: jax.Array, block_slots: jax.Array,
                *, blk_t: int = DEFAULT_BLK_T, blk_d: int = DEFAULT_BLK_D,
                interpret: bool = False) -> jax.Array:
    """x: [T, d_in] with T = nb·blk_t adapter-homogeneous blocks;
    a_stack: [R, r, d_in] in any float dtype, each tile rounded to x's in
    VMEM; block_slots: [nb] int32. Returns [T, r] f32."""
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


def _expand_kernel(slots_ref, s_ref, bt_ref, y_ref, *, compute_dtype):
    y_ref[...] = jax.lax.dot_general(
        s_ref[...], bt_ref[0].astype(compute_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


def sgmv_expand(s: jax.Array, b_stack: jax.Array, block_slots: jax.Array,
                *, blk_t: int = DEFAULT_BLK_T, blk_d: int = DEFAULT_BLK_D,
                compute_dtype, out_dtype=jnp.float32,
                interpret: bool = False) -> jax.Array:
    """s: [T, r]; b_stack: [R, d_out, r] in any float dtype, each tile
    rounded to ``compute_dtype`` in VMEM; block_slots: [nb].
    Returns [T, d_out]."""
    t, r = s.shape
    d_out = b_stack.shape[1]
    assert t % blk_t == 0, (t, blk_t)
    blk_d = _fit(d_out, blk_d)
    nb, nd = t // blk_t, d_out // blk_d
    # The TPU stores a [.., d_out, r] array with d_out minor (r = 16 would
    # pad 8x on the lanes), so the kernel reads Bᵀ [R, r, d_out]: on the
    # chip the transpose is a bitcast, and the tile is (r, blk_d) unpadded.
    bt_stack = jnp.swapaxes(b_stack, 1, 2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nd),
        in_specs=[
            pl.BlockSpec((blk_t, r), lambda i, j, slots: (i, 0)),
            pl.BlockSpec((1, r, blk_d), lambda i, j, slots: (slots[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((blk_t, blk_d), lambda i, j, slots: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_expand_kernel, compute_dtype=compute_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d_out), out_dtype),
        interpret=interpret,
    )(block_slots, s, bt_stack)
