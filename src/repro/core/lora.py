"""LoRA core: adapter parameters, application modes, merge/unmerge.

This module implements the three ways the paper computes a LoRA-augmented
linear ``y = x W + s · x Aᵀ Bᵀ``:

* ``merged``   — ΔW = s·BA folded into W (paper Fig. 2b). Zero extra latency,
                 but the weight belongs to exactly one tenant.
* ``single``   — unmerged, one adapter for the whole batch (training, or the
                 llama.cpp baseline's "same adapter per step" restriction).
* ``batched``  — the paper's **Batch LoRA Inference** (Fig. 6): every request
                 in the batch may use a different adapter; the base GEMM runs
                 over the full batch and the LoRA contribution is computed
                 from a *stacked adapter pool* indexed per request.

The stacked pool is the device-side face of the heterogeneous memory
manager: ``A_stack[R, r, d_in]`` / ``B_stack[R, d_out, r]`` hold ``R =
max_resident`` adapter slots, updated in place (``load_adapter_into_slot``)
so serving never reallocates or recompiles — the TPU analog of the paper's
pre-allocated memory pool.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class LoRAMode(NamedTuple):
    """How to apply LoRA inside a linear layer.

    kind: 'none' | 'single' | 'batched'
    adapter_ids: [batch] int32 slot indices (batched mode only).
    scale: alpha / rank.
    backend: 'einsum' (gather-einsum reference, the CPU fallback) or
        'sgmv' (grouped Pallas kernels, the TPU serving path) — batched
        mode only. See ``resolve_lora_backend`` for the 'auto' policy.
    interpret: run the sgmv Pallas kernels in interpret mode (required
        off-TPU; ignored by the einsum backend).

    Note: construct LoRAMode *inside* jit'd functions (string fields are
    not valid jit argument leaves); every model entry point does so.
    """

    kind: str = "none"
    adapter_ids: Optional[jax.Array] = None
    scale: float = 1.0
    backend: str = "einsum"
    interpret: bool = True


def resolve_lora_backend(requested: str = "auto") -> str:
    """Map the ModelConfig/EngineConfig knob to a concrete backend.

    'auto' selects the Pallas SGMV kernels on TPU and the gather-einsum
    path everywhere else (interpret-mode Pallas is correct but slow, so
    CPU runs keep einsum unless a test explicitly opts in to 'sgmv').
    """
    if requested == "auto":
        return "sgmv" if jax.default_backend() == "tpu" else "einsum"
    if requested not in ("einsum", "sgmv"):
        raise ValueError(f"unknown lora backend {requested!r}")
    return requested


def resolve_lora_exec(requested: str = "auto") -> Tuple[str, bool]:
    """(backend, interpret) for this process — the single source of the
    execution policy shared by the serving engine and the launch layer:
    Pallas kernels run compiled on TPU, interpret mode everywhere else.
    """
    return resolve_lora_backend(requested), jax.default_backend() != "tpu"


def init_lora_pair(rng: jax.Array, d_in: int, d_out: int, rank: int,
                   *, stack: Tuple[int, ...] = (),
                   dtype: Any = jnp.float32,
                   ) -> Dict[str, jax.Array]:
    """A (A, B) pair, optionally stacked over leading dims (layers, slots).

    A ~ N(0, 1/r) (Kaiming-ish), B = 0 so the adapter starts as identity —
    standard LoRA init.
    """
    ka, _ = jax.random.split(rng)
    a = jax.random.normal(ka, (*stack, rank, d_in), dtype=dtype) / jnp.sqrt(rank)
    b = jnp.zeros((*stack, d_out, rank), dtype=dtype)
    return {"A": a, "B": b}


def lora_delta_single(x: jax.Array, a: jax.Array, b: jax.Array,
                      scale: float) -> jax.Array:
    """s · x Aᵀ Bᵀ for one adapter shared across the batch.

    x: [..., d_in]; A: [r, d_in]; B: [d_out, r].
    """
    shrink = jnp.einsum("...d,rd->...r", x, a.astype(x.dtype))
    return scale * jnp.einsum("...r,or->...o", shrink, b.astype(x.dtype))


def lora_delta_batched(x: jax.Array, a_stack: jax.Array, b_stack: jax.Array,
                       adapter_ids: jax.Array, scale: float,
                       backend: str = "einsum",
                       interpret: bool = True) -> jax.Array:
    """Batch LoRA Inference: per-request adapters from the stacked pool.

    x: [B, S, d_in] (or [B, d_in]); A_stack: [R, r, d_in];
    B_stack: [R, d_out, r]; adapter_ids: [B] int32 slots.

    backend='einsum': gather-einsum — materializes only the per-request
    adapters ([B, r, d_in]), never the whole pool against the whole batch.
    backend='sgmv': the token batch is flattened to [T, d_in] with
    per-token slot ids and routed through the Pallas SGMV data path
    (``repro.kernels.ops.sgmv``: grouping plan + grouped shrink/expand
    GEMMs + scatter) so every MXU block is adapter-homogeneous.
    """
    if backend == "sgmv":
        return _lora_delta_sgmv(x, a_stack, b_stack, adapter_ids, scale,
                                interpret)
    a_sel = a_stack[adapter_ids].astype(x.dtype)  # [B, r, d_in]
    b_sel = b_stack[adapter_ids].astype(x.dtype)  # [B, d_out, r]
    if x.ndim == 3:
        shrink = jnp.einsum("bsd,brd->bsr", x, a_sel)
        return scale * jnp.einsum("bsr,bor->bso", shrink, b_sel)
    shrink = jnp.einsum("bd,brd->br", x, a_sel)
    return scale * jnp.einsum("br,bor->bo", shrink, b_sel)


def _lora_delta_sgmv(x: jax.Array, a_stack: jax.Array, b_stack: jax.Array,
                     adapter_ids: jax.Array, scale: float,
                     interpret: bool) -> jax.Array:
    """Flatten [B, S, d]→[T, d] with per-token slots, run ops.sgmv,
    reshape back. Token counts need not be multiples of the kernel block
    size — the grouping plan pads each adapter's run internally."""
    from repro.kernels import ops  # deferred: keep core importable w/o pallas

    adapter_ids = jnp.asarray(adapter_ids, jnp.int32)
    if x.ndim == 3:
        b, s, d_in = x.shape
        token_slots = jnp.repeat(adapter_ids, s, total_repeat_length=b * s)
        flat = x.reshape(b * s, d_in)
    else:
        token_slots = adapter_ids
        flat = x
    # the pool goes in as stored (f32): the kernels round each tile they
    # read to x.dtype, the einsum backend's semantics, so no step converts
    # the slots its batch does not use
    delta = ops.sgmv(flat, a_stack, b_stack, token_slots, scale,
                     n_slots=a_stack.shape[0], blk_t=None, blk_d=None,
                     interpret=interpret)
    return delta.reshape(*x.shape[:-1], b_stack.shape[1])


def apply_lora(x: jax.Array, pair: Optional[Dict[str, jax.Array]],
               mode: LoRAMode) -> jax.Array:
    """LoRA delta for ``x`` given this module's (stacked) pair and the mode.

    pair['A'] shapes:  single → [r, d_in];  batched → [R, r, d_in].
    Returns zeros(d_out-shaped delta) when mode.kind == 'none' or pair is
    None — callers just add it unconditionally.
    """
    if pair is None or mode.kind == "none":
        return jnp.zeros((), x.dtype)  # scalar zero broadcasts in the add
    if mode.kind == "single":
        return lora_delta_single(x, pair["A"], pair["B"], mode.scale)
    if mode.kind == "batched":
        return lora_delta_batched(x, pair["A"], pair["B"],
                                  mode.adapter_ids, mode.scale,
                                  backend=mode.backend,
                                  interpret=mode.interpret)
    raise ValueError(f"unknown LoRA mode {mode.kind!r}")


def merge_lora(w: jax.Array, pair: Dict[str, jax.Array], scale: float,
               sign: float = 1.0) -> jax.Array:
    """W ± s·(BA)ᵀ — the paper's merged inference / adapter swap-by-merge.

    w: [d_in, d_out]; A: [r, d_in]; B: [d_out, r]. sign=-1 unmerges.
    """
    delta = jnp.einsum("or,rd->do", pair["B"], pair["A"])  # [d_in, d_out]
    return w + sign * scale * delta.astype(w.dtype)


def load_adapter_into_slot(stack_tree: Any, adapter_tree: Any,
                           slot: jax.Array | int) -> Any:
    """Write one adapter's (A, B) pytree into pool slot ``slot`` in place.

    stack_tree leaves: [R, ...]; adapter_tree leaves: [...]. This is the
    pool-block write of the heterogeneous memory manager: fixed-size,
    allocation-free, jit-able (donate the stack for true in-place update).
    """
    def _upd(stack: jax.Array, item: jax.Array) -> jax.Array:
        return jax.lax.dynamic_update_index_in_dim(
            stack, item.astype(stack.dtype), slot, axis=0)
    return jax.tree.map(_upd, stack_tree, adapter_tree)


load_adapter_into_slot_jit = jax.jit(load_adapter_into_slot,
                                     donate_argnums=(0,),
                                     static_argnames=())
