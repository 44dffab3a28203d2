"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

All kernels run in interpret mode on CPU (TPU is the target; interpret
executes the kernel body exactly)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.sgmv import (DEFAULT_BLK_D, VMEM_BUDGET, _fit,
                                expand_vmem, shrink_vmem)

# qwen2-0.5b's LoRA projections (d_in, d_out): q/o, up/gate, down, k/v
QWEN2_WIDTHS = [(896, 896), (896, 4864), (4864, 896), (896, 128)]


# ---------------------------------------------------------------------------
# SGMV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,d_in,d_out,r,n_slots,blk_t,blk_d", [
    (16, 128, 128, 8, 2, 8, 128),
    (64, 256, 512, 16, 4, 16, 128),
    (37, 384, 256, 32, 5, 8, 128),   # ragged T
    (128, 512, 384, 16, 8, 32, 256),
    # qwen2-0.5b widths: 896 → 128-wide lane tiles, 4864 → 256-wide
    (24, 896, 896, 16, 4, None, 512),
    (24, 896, 4864, 16, 4, None, 512),
    (24, 4864, 896, 16, 4, None, 512),
])
def test_sgmv_vs_ref(dtype, t, d_in, d_out, r, n_slots, blk_t, blk_d):
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.normal(size=(t, d_in)), dtype)
    a = jnp.asarray(rng.normal(size=(n_slots, r, d_in)), dtype)
    b = jnp.asarray(rng.normal(size=(n_slots, d_out, r)), dtype)
    slots = jnp.asarray(rng.integers(0, n_slots, t), jnp.int32)
    y = ops.sgmv(x, a, b, slots, 0.5, n_slots=n_slots, blk_t=blk_t,
                 blk_d=blk_d, interpret=True)
    y_ref = 0.5 * ref.sgmv_ref(x, a, b, slots, 1.0)
    tol = 5e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol * 10)


def test_sgmv_single_adapter_matches_dense():
    """One shared adapter: SGMV == plain x Aᵀ Bᵀ."""
    rng = np.random.default_rng(0)
    t, d, r = 32, 256, 16
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(1, r, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1, d, r)), jnp.float32)
    slots = jnp.zeros((t,), jnp.int32)
    y = ops.sgmv(x, a, b, slots, 1.0, n_slots=1, blk_t=8, interpret=True)
    dense = (x @ a[0].T) @ b[0].T
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=2e-5,
                               atol=1e-4)


@pytest.mark.parametrize("t", [16, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("d_in,d_out", QWEN2_WIDTHS,
                         ids=[f"{i}to{o}" for i, o in QWEN2_WIDTHS])
def test_sgmv_f32_pool_vs_ref(d_in, d_out, t):
    """The engine's dtype pair: bf16 x against the f32 pool, read in place
    with each tile rounded to bf16 in the kernel. The reference takes the
    pool rounded to bf16 first (what the einsum backend computes)."""
    n_slots, r = 32, 16
    rng = np.random.default_rng(t + d_in + d_out)
    x = jnp.asarray(rng.normal(size=(t, d_in)), jnp.bfloat16)
    a = jnp.asarray(rng.normal(size=(n_slots, r, d_in)) / np.sqrt(d_in),
                    jnp.float32)
    b = jnp.asarray(rng.normal(size=(n_slots, d_out, r)) / np.sqrt(r),
                    jnp.float32)
    slots = jnp.asarray(rng.integers(0, n_slots, t), jnp.int32)
    y = ops.sgmv(x, a, b, slots, 2.0, n_slots=n_slots, blk_t=None,
                 interpret=True)
    assert y.dtype == jnp.bfloat16
    y_ref = np.asarray(ref.sgmv_ref(x, a.astype(jnp.bfloat16),
                                    b.astype(jnp.bfloat16), slots, 2.0))
    np.testing.assert_allclose(np.asarray(y, np.float32), y_ref,
                               rtol=1e-2, atol=1e-2 * np.abs(y_ref).max())


@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 40), n_slots=st.integers(1, 48),
       seed=st.integers(0, 2**31 - 1))
def test_sgmv_grouping_property(t, n_slots, seed):
    """plan_grouping: permutation is a bijection, every block homogeneous,
    padded positions unique and within bounds; blocks past the last used
    one name the last used slot (no new adapter tile is fetched)."""
    rng = np.random.default_rng(seed)
    slots = jnp.asarray(rng.integers(0, n_slots, t), jnp.int32)
    plan = ops.plan_grouping(slots, n_slots, blk_t=8)
    assert plan.n_padded == 8 * ops.n_blocks(t, n_slots, 8)
    perm = np.asarray(plan.perm)
    assert sorted(perm.tolist()) == list(range(t))
    pos = np.asarray(plan.padded_pos)
    assert len(set(pos.tolist())) == t            # injective scatter
    assert pos.max() < plan.n_padded
    block_slots = np.asarray(plan.block_slots)
    sorted_slots = np.asarray(slots)[perm]
    for token_idx, p in enumerate(pos):
        assert block_slots[p // 8] == sorted_slots[token_idx]
    assert (block_slots[pos.max() // 8:] == sorted_slots[-1]).all()


@pytest.mark.parametrize("t,n_slots,blk_t,n_padded", [
    (16, 32, 8, 128),       # qwen2 decode: 16 blocks, not 34
    (4096, 32, 128, 8192),  # largest prefill group: the bound is unchanged
    (1, 32, 8, 8),
])
def test_sgmv_block_bound(t, n_slots, blk_t, n_padded):
    """The static block count is ⌊T/blk_t⌋ + min(T, R), at most T."""
    slots = jnp.arange(t, dtype=jnp.int32) % n_slots
    assert ops.plan_grouping(slots, n_slots, blk_t).n_padded == n_padded


@settings(max_examples=10, deadline=None)
@given(t=st.integers(1, 24), n_slots=st.integers(1, 4),
       seed=st.integers(0, 2**31 - 1))
def test_sgmv_hypothesis_allclose(t, n_slots, seed):
    rng = np.random.default_rng(seed)
    d_in, d_out, r = 128, 128, 8
    x = jnp.asarray(rng.normal(size=(t, d_in)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(n_slots, r, d_in)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n_slots, d_out, r)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, n_slots, t), jnp.int32)
    y = ops.sgmv(x, a, b, slots, 1.0, n_slots=n_slots, blk_t=8,
                 interpret=True)
    y_ref = ref.sgmv_ref(x, a, b, slots, 1.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-5,
                               atol=1e-4)


@pytest.mark.parametrize("n,requested", [
    (896, 512), (4864, 512), (128, 512), (256, 256), (384, 128),
    (512, 512), (64, 512), (896, 64), (1000, 512), (14336, 512)])
def test_fit_lane_tile(n, requested):
    """The lane tile is a multiple of 128 dividing n and ≤ the request,
    the largest such, or else the whole width (what the TPU lowering
    accepts for a block's last dim)."""
    b = _fit(n, requested)
    fits = [m for m in range(128, min(n, requested) + 1, 128) if n % m == 0]
    assert b == (max(fits) if fits else n)
    assert n % b == 0 and (b % 128 == 0 or b == n)


@pytest.mark.parametrize("d_in,d_out", QWEN2_WIDTHS,
                         ids=[f"{i}to{o}" for i, o in QWEN2_WIDTHS])
def test_sgmv_grid_decode_whole_width(d_in, d_out):
    """At qwen2 decode (T = 16, R = 32, bf16 x, f32 pool) each kernel
    takes its whole lane axis in one step over 16 token blocks."""
    g = ops.sgmv_grid(16, d_in, d_out, 16, 32, jnp.bfloat16, jnp.float32,
                      jnp.float32, blk_t=None)
    assert (g.blk_t, g.nb, g.nd_in, g.nd_out) == (8, 16, 1, 1)
    assert (g.blk_d_in, g.blk_d_out) == (d_in, d_out)


def test_sgmv_grid_tiles_wide_prefill():
    """A width whose tiles do not fit the budget is still tiled: d_ff
    18432 (starcoder2-7b) at blk_t 128, for both kernels."""
    g = ops.sgmv_grid(4096, 18432, 18432, 16, 8, jnp.bfloat16, jnp.float32,
                      jnp.float32, blk_t=128)
    assert g.nd_in > 1 and g.nd_out > 1


@pytest.mark.parametrize("x_dtype,pool_dtype", [
    (jnp.bfloat16, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
    (jnp.float32, jnp.float32)])
@pytest.mark.parametrize("blk_t", [8, 128])
@pytest.mark.parametrize("width", [128, 640, 896, 1000, 4608, 4864, 14336,
                                   18432, 49152])
def test_budget_lane_tile(width, blk_t, x_dtype, pool_dtype):
    """The budget's lane tile divides the width, is a multiple of 128 or
    the whole width, fits VMEM_BUDGET, and is never narrower than the
    fixed request's ``_fit(width, 512)`` (taken only when nothing wider
    fits)."""
    r, floor = 16, _fit(width, DEFAULT_BLK_D)
    g = ops.sgmv_grid(4 * blk_t, width, width, r, 4, x_dtype, pool_dtype,
                      pool_dtype, blk_t=blk_t)
    for tile, vmem in [
            (g.blk_d_in,
             lambda b: shrink_vmem(blk_t, b, r, x_dtype, pool_dtype)),
            (g.blk_d_out,
             lambda b: expand_vmem(blk_t, b, r, pool_dtype, jnp.float32))]:
        assert width % tile == 0 and (tile % 128 == 0 or tile == width)
        assert tile >= floor
        assert vmem(tile) <= VMEM_BUDGET or tile == floor
        wider = [b for b in range(tile + 1, width + 1)
                 if width % b == 0 and (b % 128 == 0 or b == width)]
        assert all(vmem(b) > VMEM_BUDGET for b in wider)


def test_explicit_blk_d_keeps_fit():
    """An explicit blk_d keeps the fixed rule: _fit to each width."""
    g = ops.sgmv_grid(16, 896, 4864, 16, 32, jnp.bfloat16, jnp.float32,
                      jnp.float32, blk_t=None, blk_d=512)
    assert (g.blk_d_in, g.blk_d_out) == (_fit(896, 512), _fit(4864, 512))


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kh,hd,c,blk_c,window,chunked,softcap", [
    (2, 8, 2, 64, 128, 32, None, False, None),
    (1, 4, 4, 32, 64, 16, None, False, 50.0),
    (3, 8, 2, 64, 256, 64, 64, False, None),      # sliding window
    (2, 4, 1, 64, 128, 32, 32, True, None),       # chunked (llama4)
])
def test_flash_decode_vs_ref(dtype, b, h, kh, hd, c, blk_c, window,
                             chunked, softcap):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(b, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, c, kh, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, c, kh, hd)), dtype)
    kv_pos = jnp.broadcast_to(jnp.arange(c), (b, c)).astype(jnp.int32)
    kv_pos = jnp.where(kv_pos < c - 10, kv_pos, -1)  # some empty slots
    qpos = jnp.int32(c - 11)
    out = flash_decode(q, k, v, kv_pos, qpos, window=window,
                       chunked=chunked, softcap=softcap, blk_c=blk_c,
                       interpret=True)
    out_ref = ref.decode_attention_ref(q, k, v, kv_pos, qpos, window=window,
                                       chunked=chunked, softcap=softcap)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               rtol=tol, atol=tol * 10)


def test_flash_decode_int8_fused_dequant():
    """Q8_0-style cache: kernel dequant == reference on dequantized
    values exactly; within quantization error of the fp path."""
    from repro.models.attention import _quantize_kv
    rng = np.random.default_rng(3)
    b, h, kh, hd, c = 2, 8, 2, 64, 128
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, c, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, c, kh, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(c), (b, c)).astype(jnp.int32)
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    out_q = flash_decode(q, kq, vq, pos, jnp.int32(c - 1), k_scale=ks,
                         v_scale=vs, blk_c=32, interpret=True)
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    out_dref = ref.decode_attention_ref(q, kd, vd, pos, jnp.int32(c - 1))
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_dref),
                               rtol=1e-4, atol=1e-5)
    out_fp = ref.decode_attention_ref(q, k, v, pos, jnp.int32(c - 1))
    assert float(jnp.max(jnp.abs(out_q - out_fp))) < 0.05


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind_kw", [
    ("global", {}),
    ("local", {"sliding_window": 16}),
    ("local", {"sliding_window": 16, "chunked_local": True}),
    ("global", {"attn_logit_softcap": 30.0}),
])
def test_flash_prefill_vs_blockwise(dtype, kind_kw):
    """Prefill flash kernel vs the pure-JAX blockwise oracle (which is
    itself tested against naive attention in test_attention.py)."""
    import dataclasses
    from repro.configs import get_config, reduced_config
    from repro.kernels.flash_prefill import flash_prefill
    from repro.models.attention import blockwise_attention
    kind, kw = kind_kw
    rng = np.random.default_rng(0)
    b, s, h, kh, hd = 2, 64, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kh, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kh, hd)), dtype)
    pos = jnp.arange(s)
    cfg = reduced_config(get_config("qwen2-0.5b"))
    cfg = dataclasses.replace(cfg, attn=dataclasses.replace(cfg.attn, **kw))
    ref_out = blockwise_attention(q, k, v, pos, pos, kind=kind, cfg=cfg,
                                  block_q=16, block_kv=16)
    out = flash_prefill(q, k, v, causal=True,
                        window=kw.get("sliding_window"),
                        chunked=kw.get("chunked_local", False),
                        softcap=kw.get("attn_logit_softcap"),
                        blk_q=16, blk_kv=16, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol * 10)


def test_flash_decode_matches_model_decode_attention():
    """Kernel agrees with the model's pure-JAX decode attention path."""
    from repro.configs import get_config, reduced_config
    from repro.models import attention as attn_lib
    cfg = reduced_config(get_config("qwen2-0.5b"))
    rng = np.random.default_rng(3)
    b, h, kh, hd, c = 2, cfg.n_heads, cfg.n_kv_heads, \
        cfg.resolved_head_dim, 64
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    cache = {
        "k": jnp.asarray(rng.normal(size=(b, c, kh, hd)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(b, c, kh, hd)), jnp.float32),
        "pos": jnp.broadcast_to(jnp.arange(c), (b, c)).astype(jnp.int32),
    }
    model_out = attn_lib.decode_attention(q, cache, jnp.int32(c - 1),
                                          kind="global", cfg=cfg)
    kern_out = flash_decode(q, cache["k"], cache["v"], cache["pos"],
                            jnp.int32(c - 1), blk_c=16, interpret=True)
    np.testing.assert_allclose(np.asarray(kern_out), np.asarray(model_out),
                               rtol=1e-4, atol=1e-4)
