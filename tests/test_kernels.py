"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

All kernels run in interpret mode on CPU (TPU is the target; interpret
executes the kernel body exactly)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.sgmv import _fit


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


@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 40), n_slots=st.integers(1, 6),
       seed=st.integers(0, 2**31 - 1))
def test_sgmv_grouping_property(t, n_slots, seed):
    """plan_grouping: permutation is a bijection, every block homogeneous,
    padded positions unique and within bounds."""
    rng = np.random.default_rng(seed)
    slots = jnp.asarray(rng.integers(0, n_slots, t), jnp.int32)
    plan = ops.plan_grouping(slots, n_slots, blk_t=8)
    perm = np.asarray(plan.perm)
    assert sorted(perm.tolist()) == list(range(t))
    pos = np.asarray(plan.padded_pos)
    assert len(set(pos.tolist())) == t            # injective scatter
    assert pos.max() < plan.n_padded
    block_slots = np.asarray(plan.block_slots)
    sorted_slots = np.asarray(slots)[perm]
    for token_idx, p in enumerate(pos):
        assert block_slots[p // 8] == sorted_slots[token_idx]


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
