"""Public jit'd wrappers around the Pallas kernels.

``sgmv`` implements the complete Batch LoRA Inference data path of the
paper's Fig. 6: gather tokens into adapter-homogeneous u-batches (sorted +
padded to the kernel block size), run the grouped shrink/expand GEMMs, and
scatter results back to the original batch order. Everything is static-
shaped (jit-friendly): the padded token count is ``n_blocks`` blocks of
``blk_t`` rows (``sgmv_grid`` gives the whole grid of a call).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.paged_gather import paged_gather_pages
from repro.kernels.sgmv import (DEFAULT_BLK_T, expand_vmem, lane_tile,
                                sgmv_expand, sgmv_shrink, shrink_vmem)


def auto_blk_t(t: int, n_slots: int, requested: int = DEFAULT_BLK_T) -> int:
    """Token-block size for a T-token, R-slot sgmv problem.

    Padded work is up to (T//blk_t + min(T, R)) · blk_t rows, so
    decode-sized batches (T ≈ R) want small blocks while prefill wants the
    full MXU-aligned 128. Target the per-slot run length, clamped to
    [8, requested] and rounded up to a power of two (sublane-aligned).

    T is the *total* flattened token count: with the engine's batched
    multi-slot prefill it is B · bucket (all grouped requests' prompt
    tokens in one call), so multi-request groups naturally climb toward
    the full MXU block while a lone B=1 prefill of a short bucket keeps
    a smaller block and less per-adapter padding.
    """
    per_slot = max(8, -(-t // max(1, n_slots)))
    blk = 1 << (per_slot - 1).bit_length()
    return max(8, min(requested, blk))


def n_blocks(t: int, n_slots: int, blk_t: int) -> int:
    """Static bound on the token blocks of a T-token, R-slot grouping.

    Slot g's run of c_g tokens takes ceil(c_g/blk_t) blocks, and
    Σ ceil(c_g/blk_t) ≤ ⌊T/blk_t⌋ + (non-empty slots) ≤ ⌊T/blk_t⌋ +
    min(T, R); every non-empty block holds a token, so there are at most
    T. At decode (T = 16, R = 32, blk_t = 8) that is 16 blocks, not the
    34 of ceil(T/blk_t) + R; at prefill (T ≫ R) the two agree.
    """
    return min(t, t // blk_t + min(t, n_slots))


class SgmvGrid(NamedTuple):
    """The kernels' tiles and grid for one ``sgmv`` call."""

    blk_t: int       # token rows per block
    nb: int          # token blocks (both kernels)
    blk_d_in: int    # shrink's lane tile over d_in
    blk_d_out: int   # expand's lane tile over d_out
    nd_in: int       # shrink's lane steps per block
    nd_out: int      # expand's lane steps per block


def sgmv_grid(t: int, d_in: int, d_out: int, rank: int, n_slots: int,
              x_dtype, a_dtype, b_dtype, blk_t: Optional[int] = DEFAULT_BLK_T,
              blk_d: Optional[int] = None) -> SgmvGrid:
    """Tiles and grid of ``sgmv`` for x [T, d_in] and an R-slot pool of
    rank-``rank`` adapters. blk_t=None: ``auto_blk_t``; blk_d: each
    kernel's lane tile by ``sgmv.lane_tile`` (None: from the VMEM budget,
    an int: ``sgmv._fit`` of it to each width)."""
    if blk_t is None:
        blk_t = auto_blk_t(t, n_slots)
    bi = lane_tile(d_in, lambda b: shrink_vmem(blk_t, b, rank, x_dtype,
                                               a_dtype), blk_d)
    # the expand's output is f32 (sgmv_expand's default out_dtype)
    bo = lane_tile(d_out, lambda b: expand_vmem(blk_t, b, rank, b_dtype,
                                                jnp.float32), blk_d)
    return SgmvGrid(blk_t, n_blocks(t, n_slots, blk_t), bi, bo,
                    d_in // bi, d_out // bo)


class Grouping(NamedTuple):
    """Static-shaped u-batch layout for a batch of per-token adapter slots."""

    padded_pos: jax.Array   # [T] position of each (sorted) token in padded buf
    perm: jax.Array         # [T] sort permutation (tokens grouped by slot)
    block_slots: jax.Array  # [nb] adapter slot owning each kernel block
    n_padded: int           # nb * blk_t (static)


def plan_grouping(token_slots: jax.Array, n_slots: int,
                  blk_t: int = DEFAULT_BLK_T) -> Grouping:
    """Compute the gather/scatter plan for ``token_slots`` [T] int32.

    Tokens are sorted by slot; each slot's run is padded to a multiple of
    blk_t so every kernel block is adapter-homogeneous. Padded size
    (static): ``n_blocks(T, n_slots, blk_t)·blk_t``.
    """
    t = token_slots.shape[0]
    nb = n_blocks(t, n_slots, blk_t)
    n_padded = nb * blk_t

    perm = jnp.argsort(token_slots, stable=True)
    sorted_slots = token_slots[perm]
    # per-slot counts and padded layout offsets
    counts = jnp.bincount(token_slots, length=n_slots)          # [R]
    padded_counts = -(-counts // blk_t) * blk_t                 # [R]
    starts = jnp.concatenate([jnp.zeros((1,), padded_counts.dtype),
                              jnp.cumsum(padded_counts)[:-1]])  # [R]
    # rank of each sorted token within its slot run
    idx = jnp.arange(t)
    run_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    rank = idx - run_start[sorted_slots]
    padded_pos = starts[sorted_slots].astype(jnp.int32) + rank.astype(jnp.int32)
    # slot owning each block (blocks beyond the last used one point at the
    # last used slot, so the kernels fetch no new adapter tile for them;
    # they process zero-padding and are scattered nowhere)
    block_starts = starts // blk_t                               # [R]
    block_ids = jnp.arange(nb)
    # block b belongs to slot g iff block_starts[g] <= b < block_starts[g] +
    # padded_counts[g]/blk_t ; searchsorted over the cumulative block counts
    cum_blocks = jnp.cumsum(padded_counts // blk_t)
    block_slots = jnp.searchsorted(cum_blocks, block_ids, side="right")
    block_slots = jnp.minimum(block_slots, sorted_slots[-1]).astype(jnp.int32)
    return Grouping(padded_pos, perm, block_slots, n_padded)


@functools.partial(jax.jit, static_argnames=("n_slots", "blk_t", "blk_d",
                                             "interpret", "use_kernel"))
def sgmv(x: jax.Array, a_stack: jax.Array, b_stack: jax.Array,
         token_slots: jax.Array, scale: float, *, n_slots: int,
         blk_t: Optional[int] = DEFAULT_BLK_T, blk_d: Optional[int] = None,
         interpret: bool = True, use_kernel: bool = True) -> jax.Array:
    """Grouped LoRA delta for a heterogeneous-adapter batch.

    x: [T, d_in]; a_stack: [R, r, d_in]; b_stack: [R, d_out, r];
    token_slots: [T] int32 in [0, R). Returns [T, d_out] = scale·B_s(A_s x).
    The stacks may be wider than x (the serving pool is f32): the kernels
    round each adapter tile they read to x.dtype.

    blk_t=None picks a block size from (T, R) via ``auto_blk_t`` — the
    batched-LoRA layers use this so decode steps (T = a few slots) don't
    pay 128-row padding per adapter. blk_d=None picks each kernel's lane
    tile from the VMEM budget (see ``sgmv_grid``). use_kernel=False falls
    back to the ref gather-einsum (the baseline the benchmarks compare
    against).
    """
    if not use_kernel:
        return (scale * ref.sgmv_ref(x, a_stack, b_stack, token_slots, 1.0)
                ).astype(x.dtype)
    t, d_in = x.shape
    grid = sgmv_grid(t, d_in, b_stack.shape[1], a_stack.shape[1], n_slots,
                     x.dtype, a_stack.dtype, b_stack.dtype, blk_t, blk_d)
    plan = plan_grouping(token_slots, n_slots, grid.blk_t)
    # gather into padded u-batch layout (the paper's Fig. 6 gather)
    xbuf = jnp.zeros((plan.n_padded, d_in), x.dtype)
    xbuf = xbuf.at[plan.padded_pos].set(x[plan.perm])
    s = sgmv_shrink(xbuf, a_stack, plan.block_slots, blk_t=grid.blk_t,
                    blk_d=grid.blk_d_in, interpret=interpret)
    y = sgmv_expand(s, b_stack, plan.block_slots, blk_t=grid.blk_t,
                    blk_d=grid.blk_d_out, compute_dtype=x.dtype,
                    interpret=interpret)
    # scatter back to original order (Fig. 6 scatter)
    y_sorted = y[plan.padded_pos]
    out = jnp.zeros((t, b_stack.shape[1]), y.dtype).at[plan.perm].set(y_sorted)
    return (scale * out).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def paged_gather(arena: jax.Array, tables: jax.Array, *,
                 interpret: bool = True, use_kernel: bool = True
                 ) -> jax.Array:
    """Gather block-table-addressed KV pages into contiguous sequences.

    arena: [ng, n_pages, block_size, ...] (trailing dims are flattened
    into one feature axis for the kernel and restored after); tables:
    [B, MB] int32 physical page ids, -1 beyond each sequence's length.
    -1 routes to the *last* page (``tables % n_pages``): the serving
    arena reserves that slot as the trash page, so invalid entries never
    read a live sequence's KV even before the downstream position mask
    applies. Returns [ng, B, MB·block_size, ...]. ``use_kernel=False``
    is the pure-jnp gather the paged serving engine uses off-TPU; the
    Pallas path routes each page through the BlockSpec index_map
    (scalar-prefetch DMA, see ``kernels/paged_gather.py``) — both are
    exact gathers of the same pages.
    """
    ng, n_pages, bs = arena.shape[:3]
    rest = arena.shape[3:]
    b, mb = tables.shape
    if not use_kernel:
        pages = arena[:, tables % n_pages]
        return pages.reshape(ng, b, mb * bs, *rest)
    flat = arena.reshape(ng, n_pages, bs, -1)
    out = paged_gather_pages(flat, tables, interpret=interpret)
    return out.reshape(ng, b, mb * bs, *rest)


@functools.partial(jax.jit, static_argnames=("window", "chunked", "softcap",
                                             "blk_c", "interpret",
                                             "use_kernel"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_pos: jax.Array, q_pos: jax.Array, *,
                     window: Optional[int] = None, chunked: bool = False,
                     softcap: Optional[float] = None, blk_c: int = 512,
                     interpret: bool = True, use_kernel: bool = True
                     ) -> jax.Array:
    """Flash-decode over the ring cache (see decode_attention.py)."""
    if not use_kernel:
        return ref.decode_attention_ref(q, k, v, kv_pos, q_pos,
                                        window=window, chunked=chunked,
                                        softcap=softcap)
    return flash_decode(q, k, v, kv_pos, q_pos, window=window,
                        chunked=chunked, softcap=softcap, blk_c=blk_c,
                        interpret=interpret)
